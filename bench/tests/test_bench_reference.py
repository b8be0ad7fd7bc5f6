"""The plain reference agrees with the program where both are exact, on
this CPU: the detector path end to end, and the tracker on pixel-scale
detection sequences that exercise matching, coasting, eviction and
death."""
import numpy as np
import pytest

from bench import compare, reference, run


def test_detector_reference_matches_decode_detections():
    import jax
    from repro.detector import SSDConfig, decode_detections, make_anchors
    cell = run.Cell("minissd64-eth14-steady")
    ssd, serve = cell.ssd, cell.serve
    params = reference.make_params(ssd, 11)
    cfg = SSDConfig()
    np.testing.assert_array_equal(make_anchors(cfg), reference.anchors(ssd))
    x = np.random.default_rng(0).random((4, 64, 64, 3)).astype(np.float32)
    got = jax.jit(lambda p, im: decode_detections(
        p, cfg, im, make_anchors(cfg), **{k: serve[k] for k in (
            "score_thr", "iou_thr", "max_out")}))(params, x)
    dl, ob, lg = (np.asarray(v) for v in reference.forward_fn(ssd)(params, x))
    anc = reference.anchors(ssd)
    cands = [reference.decode(dl[f], ob[f], lg[f], anc) + (lg[f],)
             for f in range(4)]
    served = [tuple(np.asarray(o)[f] for o in got) for f in range(4)]
    nums = compare.detector_numbers(served, cands, serve)
    assert nums["det_gap"] < 1e-5
    assert nums["cls_gap"] == 0.0 and nums["nms_miss"] == 0.0
    assert sum(int(np.asarray(got[3])[f].sum()) for f in range(4)) > 0


def _sequence(rng, n_frames=40, D=8):
    """Objects moving in pixel space, with noisy detections, random
    misses and a few dropped frames."""
    pos = rng.uniform(50, 500, (6, 2))
    vel = rng.normal(0, 3, (6, 2))
    size = rng.uniform(20, 60, (6, 2))
    cls = rng.integers(0, 3, 6)
    out = []
    for k in range(n_frames):
        c = pos + k * vel
        boxes = np.zeros((D, 4), np.float32)
        valid = np.zeros(D, bool)
        classes = np.zeros(D, np.int32)
        seen = [o for o in range(6) if rng.random() < 0.8]
        for d, o in enumerate(seen):
            b = np.concatenate([c[o] - size[o] / 2, c[o] + size[o] / 2])
            boxes[d] = b + rng.normal(0, 1.5, 4)
            valid[d], classes[d] = True, cls[o]
        scores = np.where(valid, rng.uniform(0.4, 1.0, D), 0.0)
        out.append((rng.random() < 0.2, boxes, scores.astype(np.float32),
                    classes, valid))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tracker_reference_matches_the_program_tracker(seed):
    import jax.numpy as jnp
    from repro.tracking import TrackerConfig, coast, init_state, output, step
    rng = np.random.default_rng(seed)
    cfg = TrackerConfig(capacity=8)         # small: forces eviction
    prm = reference.TrackerParams(capacity=8)
    state = init_state(1, cfg)
    trk = reference.Track(prm)
    matched = 0
    for dropped, boxes, scores, classes, valid in _sequence(rng):
        if dropped:
            state = coast(state, cfg)
            trk.coast()
            got = [np.asarray(a)[0] for a in output(state, cfg)]
            want = trk.output()
            np.testing.assert_array_equal(got[4], want[4])
            np.testing.assert_array_equal(got[3], want[3])
            np.testing.assert_allclose(got[0], want[0], atol=1e-3)
            continue
        state, tid = step(state, *(jnp.asarray(a)[None] for a in (
            boxes, scores, classes, valid)), cfg)
        want = trk.step(boxes, scores, classes, valid)
        np.testing.assert_array_equal(np.asarray(tid)[0], want)
        matched += int((np.asarray(state.hits)[0] > 1).sum())
    assert matched > 0
