"""The harness decides ``correct`` by the plain reference: on this CPU,
at a size a test run holds, a sound run passes, the lower-precision
control fails, and so does a run with the timed path broken underneath
in each way a one-chip cell can break.  These runs skip the harness's
look for a chip and drive the rest of a run as the benchmark does."""
import numpy as np
import pytest

from bench import run

CELL = "minissd64-eth14-steady"
SECONDS = 1.0
CAMERAS = 6


@pytest.fixture(scope="module", autouse=True)
def restore_jax_config():
    """``run_cell`` configures JAX for a benchmark process; give the
    next test file in this worker the configuration it had."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    for k, v in old.items():
        jax.config.update(k, v)


def _run(name=CELL, cameras=CAMERAS, seconds=SECONDS, **kw):
    return run.run_cell(name, 2**31 + 77, seconds, False, cameras=cameras,
                        check_device=False, log=lambda s: None, **kw)


def _failing(out):
    return sorted(k for k, c in out["checks"].items()
                  if c["limit"] is not None and c["value"] > c["limit"])


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == CAMERAS * 14
    assert list(out["checks"]) == ["failed", "det_gap", "cls_gap",
                                   "nms_miss", "track_miss", "track_gap"]
    assert list(out)[-1] == "checks"


def test_control_fails():
    out = _run(control=True)
    assert not out["correct"]
    assert _failing(out), out["checks"]
    limits = run.Cell(CELL).limits
    assert all(v <= limits[k] for k, v in out["program"].items()), \
        out["program"]


def test_tracker_step_that_keeps_its_state_fails(monkeypatch):
    import repro.tracking as trk
    step = trk.step

    def stuck(state, *args, **kw):
        return state, step(state, *args, **kw)[1]

    monkeypatch.setattr(trk, "step", stuck)
    out = _run()
    assert not out["correct"]
    assert "track_miss" in _failing(out)


def _patch_detect(monkeypatch, change):
    from repro.serving import DetectionEngine
    detect = DetectionEngine._detect_batch

    def broken(self, images, rids=None, **kw):
        out, wall = detect(self, images, rids, **kw)
        out = tuple(np.array(o) for o in out)
        change(*out, list(rids))
        return out, wall

    monkeypatch.setattr(DetectionEngine, "_detect_batch", broken)


def test_half_of_each_batch_left_out_fails(monkeypatch):
    # micro-batches are mostly one frame long here, so "half of each
    # batch" is every frame with an odd request id
    def drop_half(boxes, scores, classes, valid, rids):
        for j, rid in enumerate(rids):
            if rid >= 0 and rid % 2:
                valid[j] = False

    _patch_detect(monkeypatch, drop_half)
    out = _run()
    assert not out["correct"]
    assert "nms_miss" in _failing(out)


def test_answer_altered_where_produced_fails(monkeypatch):
    def shift(boxes, scores, classes, valid, rids):
        boxes[0] += np.float32(0.01)

    _patch_detect(monkeypatch, shift)
    out = _run()
    assert not out["correct"]
    assert "det_gap" in _failing(out)
