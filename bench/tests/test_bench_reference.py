"""The plain reference agrees with the program where both are exact, on
this CPU: the detector path end to end, and the tracker on pixel-scale
detection sequences that exercise matching, coasting, eviction and
death."""
import numpy as np
import pytest

from bench import compare, generator, reference, run


def test_detector_reference_matches_decode_detections():
    import jax
    from repro.detector import SSDConfig, decode_detections, make_anchors
    cell = run.Cell("minissd64-eth14-steady")
    fam, serve = cell.family, cell.serve
    params = fam.make_params(cell.config, 11)
    cfg = fam.program_config(cell.config)
    assert cfg == SSDConfig()
    np.testing.assert_array_equal(make_anchors(cfg),
                                  fam.anchors(cell.config["ssd"]))
    x = np.random.default_rng(0).random((4, 64, 64, 3)).astype(np.float32)
    got = jax.jit(lambda p, im: decode_detections(
        p, cfg, im, make_anchors(cfg), **{k: serve[k] for k in (
            "score_thr", "iou_thr", "max_out")}))(params, x)
    cands = fam.candidates(cell.config, params, x, "highest")
    served = [tuple(np.asarray(o)[f] for o in got) for f in range(4)]
    nums = compare.detector_numbers(served, cands, serve, fam.survivors)
    assert nums["det_gap"] < 1e-5
    assert nums["cls_gap"] == 0.0 and nums["nms_miss"] == 0.0
    assert sum(int(np.asarray(got[3])[f].sum()) for f in range(4)) > 0


# What the reference check read on the frames of ``_fixed_served`` before
# the detector's reference moved into ``bench/families/ssd.py``, when
# ``run.reference_check`` called ``reference.forward_fn``, ``decode`` and
# ``nms`` itself: the program's numbers, then the control's.
BEFORE_THE_MOVE = (
    {"det_gap": 1.1329253402081463e-07, "cls_gap": 0.04046659916639328,
     "nms_miss": 0.0009191176470588235, "track_miss": 0.0,
     "track_gap": 7.103965872775274e-08},
    {"det_gap": 3.113500158802296e-07, "cls_gap": 0.0, "nms_miss": 0.0,
     "track_miss": 0.0, "track_gap": 0.0047493577003479})
FIXED_SEED = 2**31 + 5


def _fixed_served(cell, seed):
    """One second of 3 cameras, every fifth frame interpolated: the
    program's detections of each frame (one class flipped and one
    survivor dropped, so that every number reads), the tracker replayed
    in float32."""
    import types
    mix = cell.mix
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pool = generator.render_pool(mix, cell.family.image_size(cell.config))
    offsets = generator.camera_offsets(rng, mix.cameras, mix.pool_frames)
    fr = run.Frames(mix, 1.0, pool, offsets)
    eng = run.build_engine(cell, cell.family.make_params(cell.config, seed))
    out = [np.array(o) for o in eng._infer(np.stack([r.image
                                                     for r in fr.reqs]))]
    first = np.flatnonzero(out[3][0])[0]
    out[2][0, first] = (out[2][0, first] + 1) % 3
    out[3][1, np.flatnonzero(out[3][1])[-1]] = False
    streams = {}
    for j, r in enumerate(fr.reqs):
        streams.setdefault(r.stream_id, []).append(types.SimpleNamespace(
            rid=r.rid, interpolated=j % 5 == 3, boxes=out[0][j],
            scores=out[1][j], classes=out[2][j], valid=out[3][j]))
    rep, finals = compare.replay_tracker(streams, reference.TrackerParams(),
                                         np.float32)
    responses = sorted((types.SimpleNamespace(
        rid=a.rid, stream_id=s, interpolated=b.interpolated, boxes=b.boxes,
        scores=b.scores, classes=b.classes, valid=b.valid,
        track_ids=b.track_ids)
        for s, rs in streams.items() for a, b in zip(rs, rep[s])),
        key=lambda r: r.rid)
    return responses, finals, fr, pool


def test_reference_check_reads_as_before_the_family_move():
    cell = run.Cell("minissd64-eth14-steady", cameras=3)
    responses, finals, fr, pool = _fixed_served(cell, FIXED_SEED)
    nums, ctl, _ = run.reference_check(cell, FIXED_SEED, responses, finals,
                                       fr, pool, True)
    assert (nums, ctl) == BEFORE_THE_MOVE


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
