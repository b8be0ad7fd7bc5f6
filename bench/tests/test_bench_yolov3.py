"""The ``yolov3`` family against the program, on this CPU at a small
size that keeps every kind of layer (stride-2 convs, residual blocks,
two routes with upsample, three heads): the forward pass, the served
detections and their suppression, a tiny cell run to ``correct``
through the copy's own ``run.py``; and the published configuration's
counts."""
import json

import numpy as np
import pytest

from bench import compare, run
from bench.families import Candidates
from bench.tests.test_bench_family_plugin import _copy, jax_config_kept  # noqa: F401

FAM = run.load_module("families", "yolov3")
PUBLISHED = json.loads((run.BENCH / "configs" / "yolov3-416.json")
                       .read_text())
SMALL = dict(PUBLISHED, name="yolov3-64", yolov3=dict(
    PUBLISHED["yolov3"], image_size=64, n_classes=3, stem=2,
    stage_widths=[4, 8, 16, 32, 64], stage_blocks=[1, 1, 1, 1, 1]),
    # a threshold the small random detector's pairs reach, and fewer
    # rows than its survivors
    serving=dict(PUBLISHED["serving"], score_thr=0.01, max_out=8))
SERVE = SMALL["serving"]


def _program(cfg, serve=SERVE):
    import jax
    from repro.detector import yolov3_detect
    pc = FAM.program_config(cfg)
    return jax.jit(lambda p, x: yolov3_detect(
        p, pc, x, score_thr=serve["score_thr"], iou_thr=serve["iou_thr"],
        max_out=serve["max_out"]))


def test_published_config_is_the_program_default_and_counts():
    from repro.detector import YOLOv3Config
    assert FAM.program_config(PUBLISHED) == YOLOv3Config()
    assert FAM.flops_per_frame(PUBLISHED) == 65_864_075_264
    by = FAM.flops_by_scope(PUBLISHED)
    # padding taps left out: each scope just under its 2*H*W*k*k*Ci*Co
    assert 0.95 * 49_031_610_368 < by["backbone"] < 49_031_610_368
    assert 0.9 * 16_832_464_896 < by["neck"] < 16_832_464_896
    assert FAM.bytes_by_scope(PUBLISHED, 1.0)["nms"] == 3_577_392
    assert FAM.n_anchors(PUBLISHED["yolov3"]) == 10_647
    assert PUBLISHED["reduced"].keys() == {"weights"}


def test_family_weights_have_the_program_layout():
    import jax
    from repro.detector import init_yolov3
    pc = FAM.program_config(SMALL)
    got = jax.tree.map(lambda x: (x.shape, x.dtype),
                       FAM.make_params(SMALL, 2**31 + 17))
    want = jax.tree.map(lambda x: (x.shape, x.dtype),
                        jax.eval_shape(lambda: init_yolov3(
                            pc, jax.random.PRNGKey(0))))
    assert got == want


def test_forward_matches_the_reference():
    import jax
    from repro.detector import yolov3_forward
    pc = FAM.program_config(SMALL)
    params = FAM.make_params(SMALL, 2**31 + 19)
    x = FAM.calibration_frames(64, 3)[2:6]
    got = jax.jit(lambda p, i: yolov3_forward(p, pc, i))(params, x)
    with jax.default_matmul_precision("highest"):
        want = FAM.forward_fn(SMALL["yolov3"])(params, x)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seed", [2**31 + 23, 5])
def test_served_detections_match_the_reference(seed):
    params = FAM.make_params(SMALL, seed)
    x = FAM.calibration_frames(64, seed)[1:]
    got = [np.asarray(o) for o in _program(SMALL)(params, x)]
    cands = FAM.candidates(SMALL, params, x, "highest")
    served = [tuple(o[f] for o in got[:4]) for f in range(len(x))]
    nums = compare.detector_numbers(served, cands, SERVE, FAM.survivors)
    assert nums["det_gap"] < 1e-5
    assert nums["cls_gap"] == 0.0 and nums["nms_miss"] == 0.0
    n_cand = [int((c.scores >= SERVE["score_thr"]).sum()) for c in cands]
    assert got[4].tolist() == n_cand
    # some frame has more survivors than rows
    assert max(len(FAM.survivors(c, dict(SERVE, max_out=10**6)).anchor)
               for c in cands) > SERVE["max_out"]


CASES = {
    # ties across anchors and classes: the lower a * C + c first
    "ties": (np.array([[0.0, 0.0, 0.25, 0.25], [0.5, 0.5, 0.75, 0.75],
                       [0.0, 0.5, 0.25, 0.75]]),
             np.array([[0.75, 0.75], [0.75, 0.5], [0.875, 0.75]]), 4),
    # two classes overlapping fully on one box, and a copy of the box
    "classes_overlap_fully": (
        np.array([[0.125, 0.125, 0.5, 0.5], [0.125, 0.125, 0.5, 0.5],
                  [0.625, 0.625, 0.75, 0.75]]),
        np.array([[0.875, 0.625], [0.75, 0.9375], [0.0, 0.5625]]), 8),
    "more_than_max_out": (
        np.stack([np.arange(12) / 16, np.zeros(12), np.arange(12) / 16
                  + 1 / 32, np.full(12, 0.5)], -1),
        0.5 + np.arange(24).reshape(12, 2) / 64, 5),
    "no_candidates": (np.array([[0.0, 0.0, 0.5, 0.5]]),
                      np.array([[0.25, 0.125]]), 4),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_suppression_matches_the_family_survivors(name):
    import jax
    from repro.kernels.class_nms import class_nms
    boxes, scores, m = CASES[name]
    serve = dict(score_thr=0.5, iou_thr=0.45, max_out=m)
    a, c, s, v = (np.asarray(o)[0] for o in jax.jit(
        lambda b, sc: class_nms(b, sc, **serve))(
            boxes[None].astype(np.float32),
            scores.T[None].astype(np.float32)))
    rows = FAM.survivors(Candidates(boxes, scores, None), serve)
    assert a[v].tolist() == rows.anchor.tolist()
    assert c[v].tolist() == rows.cls.tolist()
    np.testing.assert_allclose(s[v], rows.scores)
    if name == "more_than_max_out":
        assert v.all()
    if name == "no_candidates":
        assert not v.any()


def test_tiny_yolov3_cell_runs_correct(tmp_path, monkeypatch,
                                      jax_config_kept):  # noqa: F811
    before = sorted(p.relative_to(run.ROOT) for p in run.BENCH.rglob("*")
                    if "__pycache__" not in p.parts)
    cell = "yolov3-64-eth14-steady"
    copy = _copy(tmp_path, monkeypatch, "yolov3",
                 (run.BENCH / "families" / "yolov3.py").read_text(), SMALL,
                 cell)
    out = copy.run_cell(cell, 2**31 + 905, 1.0, False, cameras=2,
                        check_device=False, log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["window"]["detected"] > 0
    assert out["checks"]["nms_miss"]["value"] == 0.0
    assert sorted(p.relative_to(run.ROOT) for p in run.BENCH.rglob("*")
                  if "__pycache__" not in p.parts) == before


def _lag_ctx(lat_ms, trace=None, frames=None, cameras=16, fps=30.0):
    from types import SimpleNamespace
    return {"emit_ms": np.asarray(lat_ms, np.float64),
            "frames": len(lat_ms) if frames is None else frames,
            "mix": SimpleNamespace(cameras=cameras, fps=fps),
            "seconds": 30.0, "trace": trace}


@pytest.mark.parametrize("case", ["steady", "growing", "traced", "failed"])
def test_emit_lag_growth_reads_how_fast_emits_fall_behind(case):
    n = 14400                       # 30 s of 16 cameras at 30 fps
    due_ms = np.arange(n) * 1e3 / 480.0
    if case == "steady":
        assert run.read_metric("emit_lag_growth",
                               _lag_ctx(np.full(n, 40.0))) == 0.0
    elif case == "growing":
        # 250 ms of latency gained per second of due time
        got = run.read_metric("emit_lag_growth",
                              _lag_ctx(40.0 + 0.25 * due_ms))
        assert got == pytest.approx(250.0, rel=1e-3)
    elif case == "traced":
        from types import SimpleNamespace
        lat = 40.0 + 0.25 * due_ms
        # what the profiler adds after the trace began does not count
        late = due_ms + lat >= (30.0 - 3.0) * 1e3
        lat[late] += 20e3
        got = run.read_metric("emit_lag_growth", _lag_ctx(
            lat, trace=SimpleNamespace(window_s=3.0)))
        assert got == pytest.approx(250.0, rel=1e-3)
    else:
        assert run.read_metric("emit_lag_growth", _lag_ctx(
            np.full(n - 1, 40.0), frames=n)) is None
