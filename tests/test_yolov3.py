"""YOLOv3 in the program: the published configuration's size, the exact
class-aware suppression against its oracle, decode against its
formulas, the detector dispatch by configuration type, the engine
serving a small YOLOv3 with its ``nms_candidates`` counter, and detect
programs that take their weights as arguments."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.detector import (SSDConfig, YOLOv3Config, detector_for,
                            init_ssd, init_yolov3, yolov3_forward)
from repro.detector.yolo import decode
from repro.kernels import ref
from repro.kernels.class_nms import class_nms

# every kind of layer at a small size: stride-2 convs, residual blocks,
# two routes with upsample, three heads
SMALL = YOLOv3Config(image_size=64, n_classes=3, stem=2,
                     stage_widths=(4, 8, 16, 32, 64),
                     stage_blocks=(1, 1, 1, 1, 1))


def test_published_config_counts_its_parameters():
    shapes = jax.eval_shape(lambda: init_yolov3(YOLOv3Config(),
                                                jax.random.PRNGKey(0)))
    n = {"trainable": 0, "bn_stats": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        key = path[-1].key
        n["bn_stats" if key in ("mean", "var") else "trainable"] += (
            int(np.prod(leaf.shape)))
    assert n == {"trainable": 61_949_149, "bn_stats": 52_608}
    assert len(shapes["backbone"]) + len(shapes["neck"]) == 72
    assert len(shapes["heads"]) == 3
    assert YOLOv3Config().n_anchors == 10_647


def _boxes(rng, a):
    xy = rng.random((a, 2)).astype(np.float32) * 0.8
    wh = 0.05 + rng.random((a, 2)).astype(np.float32) * 0.3
    return np.concatenate([xy, xy + wh], -1)


def _case(name):
    """(boxes (B, A, 4), scores (B, C, A), max_out) of one named case."""
    rng = np.random.default_rng(CASES.index(name))
    if name.startswith("random"):
        b, a, c = 3, 60, 4
        boxes = np.stack([_boxes(rng, a) for _ in range(b)])
        scores = rng.random((b, c, a)).astype(np.float32) ** 3
        return boxes, scores, 12
    if name == "ties":
        # equal scores across anchors and classes: lower a * C + c first
        boxes = np.stack([_boxes(rng, 6)])
        scores = np.full((1, 3, 6), 0.75, np.float32)
        scores[0, 1, 2] = 0.9
        return boxes, scores, 8
    if name == "more_than_max_out":
        # 20 disjoint boxes in each of two classes: 40 survivors for 8 rows
        x = np.arange(20, dtype=np.float32) / 20
        boxes = np.stack([x, x, x + 0.04, x + 0.04], -1)[None]
        scores = (0.5 + rng.random((1, 2, 20)) / 2).astype(np.float32)
        return boxes, scores, 8
    if name == "no_candidates":
        boxes = np.stack([_boxes(rng, 10)] * 2)
        return boxes, np.full((2, 3, 10), 0.2, np.float32), 4
    if name == "classes_overlap_fully":
        # one box scored in every class, and a copy of it: the copy is
        # suppressed in each class, the box survives in each
        boxes = np.array([[[0.1, 0.1, 0.4, 0.4], [0.1, 0.1, 0.4, 0.4],
                           [0.6, 0.6, 0.7, 0.7]]], np.float32)
        scores = np.array([[[0.9, 0.8, 0.1], [0.7, 0.95, 0.6],
                            [0.55, 0.52, 0.0]]], np.float32)
        return boxes, scores, 8
    raise KeyError(name)


CASES = ["random_a", "random_b", "random_c", "ties", "more_than_max_out",
         "no_candidates", "classes_overlap_fully"]


@pytest.mark.parametrize("name", CASES)
def test_class_nms_matches_its_oracle(name):
    boxes, scores, m = _case(name)
    kw = dict(score_thr=0.5, iou_thr=0.45, max_out=m)
    got = jax.jit(lambda b, s: class_nms(b, s, **kw))(boxes, scores)
    want = ref.class_nms_ref(boxes, scores, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    valid = np.asarray(got[3])
    if name == "more_than_max_out":
        assert valid.all()
    if name == "no_candidates":
        assert not valid.any()
    if name == "classes_overlap_fully":
        rows = {(int(a), int(c)) for a, c, v in zip(got[0][0], got[1][0],
                                                    valid[0]) if v}
        assert rows == {(0, 0), (1, 1), (0, 2), (2, 1)}
    if name == "ties":
        flat = np.asarray(got[0][0]) * 3 + np.asarray(got[1][0])
        assert flat[0] == 2 * 3 + 1 and valid[0].sum() > 3
        assert (np.diff(flat[1:valid[0].sum()]) > 0).all()


def _op_sequence(a, c):
    f = jax.jit(lambda b, s: class_nms(b, s, score_thr=0.5, iou_thr=0.45,
                                       max_out=32))
    text = f.lower(jax.ShapeDtypeStruct((8, a, 4), jnp.float32),
                   jax.ShapeDtypeStruct((8, c, a), jnp.float32)).as_text()
    return [re.sub(r"\d+", "", line.split("=")[-1].split(":")[0])
            for line in text.splitlines() if "=" in line]


def test_class_nms_program_does_not_grow_with_pairs():
    small, published = _op_sequence(252, 3), _op_sequence(10_647, 80)
    assert small == published


def test_decode_follows_the_published_formulas():
    rng = np.random.default_rng(3)
    outs = [rng.normal(size=(2, g, g, 3 * 8)).astype(np.float32)
            for g in SMALL.grids]
    boxes, scores = jax.jit(lambda o: decode(SMALL, o))(outs)
    boxes, scores = np.asarray(boxes), np.asarray(scores)
    assert boxes.shape == (2, SMALL.n_anchors, 4)
    assert scores.shape == (2, 3, SMALL.n_anchors)
    sig = lambda z: 1 / (1 + np.exp(-z.astype(np.float64)))   # noqa: E731
    base = 0
    for h, (g, mask) in enumerate(zip(SMALL.grids, SMALL.masks)):
        for cy, cx, k in ((0, 0, 0), (g - 1, 1, 2), (1, g - 1, 1)):
            a = base + (cy * g + cx) * 3 + k
            t = outs[h][1, cy, cx, 8 * k:8 * k + 8]
            pw, ph = SMALL.anchors[mask[k]]
            x, y = (sig(t[0]) + cx) / g, (sig(t[1]) + cy) / g
            w, hh = pw * np.exp(t[2]) / 64, ph * np.exp(t[3]) / 64
            np.testing.assert_allclose(
                boxes[1, a], [x - w / 2, y - hh / 2, x + w / 2, y + hh / 2],
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(scores[1, :, a],
                                       sig(t[4]) * sig(t[5:]), rtol=1e-5)
        base += g * g * 3


def test_forward_gives_three_heads_at_their_strides():
    params = init_yolov3(SMALL, jax.random.PRNGKey(1))
    x = jnp.zeros((2, 64, 64, 3))
    outs = jax.jit(lambda p, i: yolov3_forward(p, SMALL, i))(params, x)
    assert [o.shape for o in outs] == [(2, g, g, 24) for g in (2, 4, 8)]
    assert all(np.isfinite(np.asarray(o)).all() for o in outs)


def test_detector_is_chosen_by_config_type():
    kw = dict(score_thr=0.5, iou_thr=0.45, max_out=8)
    assert detector_for(SMALL, **kw).counter == "nms_candidates"
    assert detector_for(SSDConfig(), **kw).counter is None
    with pytest.raises(TypeError):
        detector_for(object(), **kw)


def test_yolov3_refuses_the_pallas_flag():
    from repro.serving import DetectionEngine
    with pytest.raises(ValueError, match="Pallas"):
        DetectionEngine(cfg=SMALL, use_pallas=True)


def test_sharded_paths_refuse_a_detector_other_than_ssd():
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import ShardedDetectionEngine
    from repro.serving.sharded import make_spmd_detect
    with pytest.raises(ValueError, match="SSDConfig"):
        ShardedDetectionEngine(n_shards=2, cfg=SMALL)
    mesh = make_serving_mesh(1)
    with pytest.raises(ValueError, match="SSDConfig"):
        ShardedDetectionEngine(n_shards=1, mesh=mesh, cfg=SMALL)
    with pytest.raises(ValueError, match="SSDConfig"):
        make_spmd_detect(SMALL, None, mesh)


class _Span:
    """Stand-in for ``obs.spans.span`` that keeps each span's args."""
    log = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)
        _Span.log.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        self.args.update(args)


def test_engine_serves_yolov3_and_counts_candidates(monkeypatch):
    from repro.serving import (DetectionEngine, FrameRequest,
                               ServingRuntime)
    from repro.serving import engine as engine_mod
    params = init_yolov3(SMALL, jax.random.PRNGKey(2))
    # heads biased so that some pairs pass a low threshold
    params["heads"] = [dict(h, b=h["b"] + 1.0) for h in params["heads"]]
    eng = DetectionEngine(cfg=SMALL, params=params, n_replicas=1,
                          track_and_interpolate=True, score_thr=0.4,
                          iou_thr=0.45, max_out=8)
    rng = np.random.default_rng(0)
    imgs = rng.random((3, 64, 64, 3)).astype(np.float32)
    _Span.log = []
    monkeypatch.setattr(engine_mod, "span", _Span)
    out, _ = eng._detect_batch(np.concatenate([imgs, imgs[:1] * 0]),
                               rids=[0, 1, 2, -1])
    assert len(out) == 4 and out[0].shape == (4, 8, 4)
    det = [s for s in _Span.log if s.name == "serve.detect"][0]
    full = eng._infer(jnp.asarray(np.concatenate([imgs, imgs[:1] * 0])))
    assert det.args["nms_candidates"] == int(np.asarray(full[4])[:3].sum())
    assert det.args["nms_candidates"] > 0
    assert det.args["frames"] == 3 and det.args["padded"] == 1
    rt = ServingRuntime(eng)
    rt.ingest([FrameRequest(i, imgs[i % 3], i / 30, stream_id=i % 2)
               for i in range(8)])
    rt.advance(1.0)
    rep = rt.epoch_boundary()
    assert len(rep["responses"]) == 8


def _weight_bytes_in(text, leaf):
    hexed = np.asarray(leaf, np.float32).tobytes()[:16].hex().upper()
    return hexed in text


@pytest.mark.parametrize("family", ["ssd", "yolov3"])
def test_detect_program_takes_weights_as_arguments(family):
    from repro.serving import DetectionEngine
    cfg = SSDConfig() if family == "ssd" else SMALL
    init = init_ssd if family == "ssd" else init_yolov3
    x = np.zeros((2, cfg.image_size, cfg.image_size, 3), np.float32)
    texts = []
    for seed in (1, 2):
        params = init(cfg, jax.random.PRNGKey(seed))
        eng = DetectionEngine(cfg=cfg, params=params)
        text = eng._infer.lower(x).as_text()
        assert "@jit_infer" in text
        main = re.search(r"func\.func public @main\((.*?)\) ->", text,
                         re.S).group(1)
        assert main.count("%arg") == len(jax.tree.leaves(params)) + 1
        assert not any(_weight_bytes_in(text, leaf)
                       for leaf in jax.tree.leaves(params)
                       if np.asarray(leaf).std() > 0)
        texts.append(text)
    assert texts[0] == texts[1]
