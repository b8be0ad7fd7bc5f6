"""Tracking subsystem: association-kernel bit-compatibility, Kalman
behaviour, track lifecycle (birth/confirm/coast/kill), dropped-frame
interpolation quality, and the serving engine's track-and-interpolate
mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (ParallelDetector, ProxyDetector,
                        SequenceSynchronizer, SyntheticVideo,
                        evaluate_map, evaluate_map_dets, track_quality)
from repro.core.quality import proxy_detect_fn, responses_to_detections
from repro.core.simulator import simulate
from repro.core.stream import ETH_SUNNYDAY, FrameStream
from repro.kernels import ops, ref
from repro.tracking import (TrackerConfig, coast, fill_stream, init_state,
                            output, step)


# ------------------------------------------------- association kernel
def _rand_assoc(rng, B, T, D):
    def boxes(n):
        tl = rng.uniform(0, 400, (B, n, 2))
        wh = rng.uniform(10, 80, (B, n, 2))
        return jnp.asarray(np.concatenate([tl, tl + wh], -1), jnp.float32)
    return (boxes(T), boxes(D),
            jnp.asarray(rng.random((B, T)) > 0.3),
            jnp.asarray(rng.random((B, D)) > 0.3),
            jnp.asarray(rng.integers(0, 3, (B, T)), jnp.int32),
            jnp.asarray(rng.integers(0, 3, (B, D)), jnp.int32))


@pytest.mark.parametrize("B,T,D", [(3, 8, 5), (2, 5, 9), (4, 16, 16),
                                   (1, 1, 1), (2, 7, 3)])
def test_greedy_assign_bit_compat(B, T, D):
    """Pallas kernel and XLA twin must match the oracle exactly."""
    rng = np.random.default_rng(B * 100 + T * 10 + D)
    tb, db, tm, dm, tc, dc = _rand_assoc(rng, B, T, D)
    kw = dict(t_mask=tm, d_mask=dm, t_cls=tc, d_cls=dc, iou_thr=0.2)
    r = np.asarray(ref.greedy_assign_ref(tb, db, tm, dm, tc, dc, 0.2))
    x = np.asarray(ops.greedy_assign(tb, db, use_pallas=False, **kw))
    p = np.asarray(ops.greedy_assign(tb, db, use_pallas=True, **kw))
    assert np.array_equal(x, r)
    assert np.array_equal(p, r)


def test_greedy_assign_semantics():
    """Best pair wins first; class mismatch forbids a match; a retired
    column can't be claimed twice."""
    tb = jnp.asarray([[[0, 0, 10, 10], [20, 0, 30, 10]]], jnp.float32)
    # det 0 overlaps track 0 strongly and track 1 not at all; det 1
    # overlaps both weakly but clears the gate only for track 1
    db = jnp.asarray([[[1, 0, 11, 10], [21, 2, 31, 12]]], jnp.float32)
    m = np.asarray(ops.greedy_assign(tb, db, use_pallas=False))
    assert m.tolist() == [[0, 1]]
    # class gate: track 0 is class 1, detections class 0 -> only track 1
    m = np.asarray(ops.greedy_assign(
        tb, db, t_cls=jnp.asarray([[1, 0]]),
        d_cls=jnp.asarray([[0, 0]]), use_pallas=False))
    assert m.tolist() == [[-1, 1]]
    # dead track slot never matches
    m = np.asarray(ops.greedy_assign(
        tb, db, t_mask=jnp.asarray([[False, True]]), use_pallas=False))
    assert m.tolist() == [[-1, 1]]


# ------------------------------------------------------ tracker core
def _one_det(cx, cy, w=20.0, h=30.0, score=0.9, cls=1, cap=8):
    boxes = np.zeros((1, cap, 4), np.float32)
    scores = np.zeros((1, cap), np.float32)
    classes = np.zeros((1, cap), np.int32)
    valid = np.zeros((1, cap), bool)
    boxes[0, 0] = [cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2]
    scores[0, 0] = score
    classes[0, 0] = cls
    valid[0, 0] = True
    return (jnp.asarray(boxes), jnp.asarray(scores), jnp.asarray(classes),
            jnp.asarray(valid))


def test_kalman_learns_constant_velocity():
    """After a few noiseless updates the filter's coasted prediction
    follows the object's true constant-velocity path."""
    cfg = TrackerConfig(capacity=8)
    state = init_state(1, cfg)
    vx, vy = 3.0, -2.0
    for i in range(6):
        state, _ = step(state, *_one_det(100 + vx * i, 200 + vy * i), cfg)
    vel = np.asarray(state.vel)[0, 0]
    assert abs(vel[0] - vx) < 0.2 and abs(vel[1] - vy) < 0.2
    for k in range(1, 4):                       # coast 3 frames
        state = coast(state, cfg)
        b, _, _, _, emit = (np.asarray(a) for a in output(state, cfg))
        assert emit[0, 0]
        cx = (b[0, 0, 0] + b[0, 0, 2]) / 2
        cy = (b[0, 0, 1] + b[0, 0, 3]) / 2
        assert abs(cx - (100 + vx * (5 + k))) < 1.0
        assert abs(cy - (200 + vy * (5 + k))) < 1.0


def test_lifecycle_birth_confirm_coast_kill():
    cfg = TrackerConfig(capacity=4, min_hits=2, max_coast=3)
    state = init_state(1, cfg)
    # birth: first detection creates an unconfirmed (silent) track
    state, det_tid = step(state, *_one_det(50, 50), cfg)
    assert int(np.asarray(det_tid)[0, 0]) == 0
    assert int(state.active.sum()) == 1
    assert not bool(np.asarray(output(state, cfg)[-1]).any())
    # confirm: second match makes it emittable
    state, _ = step(state, *_one_det(52, 51), cfg)
    assert bool(np.asarray(output(state, cfg)[-1])[0, 0])
    # coast: emitted while within max_coast...
    for _ in range(cfg.max_coast):
        state = coast(state, cfg)
        assert bool(np.asarray(output(state, cfg)[-1])[0, 0])
    # ...then killed
    state = coast(state, cfg)
    assert int(state.active.sum()) == 0
    # the freed slot is reused with a fresh id
    state, det_tid = step(state, *_one_det(300, 300), cfg)
    assert int(state.active.sum()) == 1
    assert int(np.asarray(det_tid)[0, 0]) == 1


def test_unconfirmed_false_positive_stays_silent():
    """A one-off false positive births a track that never confirms and
    is never emitted."""
    cfg = TrackerConfig(capacity=4, min_hits=2, max_coast=2)
    state = init_state(1, cfg)
    state, _ = step(state, *_one_det(50, 50), cfg)
    for _ in range(3):
        state = coast(state, cfg)
        assert not bool(np.asarray(output(state, cfg)[-1]).any())
    assert int(state.active.sum()) == 0


def test_capacity_overflow_is_masked():
    """More unmatched detections than free slots: the extras are simply
    not born (masked update), nothing corrupts the table."""
    cfg = TrackerConfig(capacity=2)
    state = init_state(1, cfg)
    boxes = np.zeros((1, 4, 4), np.float32)
    for d in range(4):
        boxes[0, d] = [100 * d, 0, 100 * d + 20, 30]
    scores = np.full((1, 4), 0.9, np.float32)
    classes = np.zeros((1, 4), np.int32)
    valid = np.ones((1, 4), bool)
    state, det_tid = step(state, jnp.asarray(boxes), jnp.asarray(scores),
                          jnp.asarray(classes), jnp.asarray(valid), cfg)
    assert int(state.active.sum()) == 2
    assert (np.asarray(det_tid)[0] >= 0).sum() == 2


def test_full_table_evicts_lowest_score_coasting_track():
    """Regression: with zero free slots, new detections were silently
    dropped (det_tid -1, no birth).  Now the lowest-score COASTING
    track is evicted to make room, matched tracks are never touched,
    and the newborn claims the evicted slot."""
    cfg = TrackerConfig(capacity=3, iou_thr=0.3, min_hits=1)
    state = init_state(1, cfg)
    # fill the table: three tracks with distinct scores
    boxes = np.zeros((1, 3, 4), np.float32)
    for d, (x, s) in enumerate(zip((0, 200, 400), (0.9, 0.4, 0.7))):
        boxes[0, d] = [x, 0, x + 20, 30]
    scores = np.asarray([[0.9, 0.4, 0.7]], np.float32)
    classes = np.zeros((1, 3), np.int32)
    valid = np.ones((1, 3), bool)
    state, _ = step(state, jnp.asarray(boxes), jnp.asarray(scores),
                    jnp.asarray(classes), jnp.asarray(valid), cfg)
    assert int(state.active.sum()) == 3          # table full
    # next frame: tracks 0 and 2 re-match, track 1 (score 0.4) coasts,
    # and a brand-new detection arrives with nowhere to go
    boxes2 = np.zeros((1, 3, 4), np.float32)
    boxes2[0, 0] = [2, 0, 22, 30]
    boxes2[0, 1] = [402, 0, 422, 30]
    boxes2[0, 2] = [800, 0, 820, 30]             # the overflow birth
    scores2 = np.asarray([[0.9, 0.7, 0.95]], np.float32)
    state, det_tid = step(state, jnp.asarray(boxes2),
                          jnp.asarray(scores2), jnp.asarray(classes),
                          jnp.asarray(valid), cfg)
    tids = np.asarray(det_tid)[0]
    assert (tids >= 0).all()                     # nothing dropped
    assert tids[2] == 3                          # fresh id for the birth
    live = set(np.asarray(state.track_id)[0][np.asarray(state.active)[0]])
    assert live == {0, 2, 3}                     # score-0.4 coaster evicted
    # a full table of MATCHED tracks still never evicts (no coasters)
    state2 = init_state(1, cfg)
    state2, _ = step(state2, jnp.asarray(boxes), jnp.asarray(scores),
                     jnp.asarray(classes), jnp.asarray(valid), cfg)
    big = np.zeros((1, 4, 4), np.float32)
    big[0, :3] = boxes[0] + 1.0
    big[0, 3] = [800, 0, 820, 30]
    sc = np.asarray([[0.9, 0.4, 0.7, 0.95]], np.float32)
    state2, tid2 = step(state2, jnp.asarray(big), jnp.asarray(sc),
                        jnp.zeros((1, 4), jnp.int32),
                        jnp.ones((1, 4), bool), cfg)
    assert int(np.asarray(tid2)[0, 3]) == -1     # overflow, no coaster
    live2 = set(np.asarray(state2.track_id)[0]
                [np.asarray(state2.active)[0]])
    assert live2 == {0, 1, 2}


# -------------------------------------------- interpolation quality
def test_interpolated_map_beats_stale_reuse():
    """The acceptance bar: on the synthetic benchmark video, filling
    dropped frames with tracker-coasted boxes beats the paper's
    stale-reuse fill at every tested executor count."""
    for n in (1, 2, 3, 4):
        det = ParallelDetector("ETH-Sunnyday", "yolov3", ["ncs2"] * n)
        paced = simulate(FrameStream(det.video), det.scheduler)
        synced = SequenceSynchronizer().order(paced)
        stale = evaluate_map(det.video, synced, det.detector)
        tracked = fill_stream(det.video, paced, det.detector)
        assert len(tracked) == paced.n_frames          # full coverage
        assert [t.index for t in tracked] == list(range(paced.n_frames))
        tmap = evaluate_map_dets(det.video, tracked)
        assert tmap > stale, (n, tmap, stale)
        tq = track_quality(det.video, tracked)
        assert tq["coverage"] > 0.8
        assert tq["id_switches"] < 40


def test_report_track_columns():
    r = ParallelDetector("ETH-Sunnyday", "yolov3",
                         ["ncs2"] * 2).run(track=True)
    assert r.map_tracked > r.map_score
    assert 0.0 < r.track_coverage <= 1.0
    assert r.id_switches >= 0


def test_evaluate_map_dets_matches_evaluate_map_on_fresh_frames():
    """With zero drops the tracked stream is exactly the fresh
    detections, so both scorers must agree."""
    det = ParallelDetector("ETH-Sunnyday", "yolov3", ["ncs2"] * 7)
    paced = simulate(FrameStream(det.video), det.scheduler)
    if paced.dropped:                 # 7 sticks: no drops expected
        pytest.skip("unexpected drops")
    synced = SequenceSynchronizer().order(paced)
    m_sync = evaluate_map(det.video, synced, det.detector)
    dets = det.detector.detect_many(det.video, range(paced.n_frames))
    m_dets = evaluate_map_dets(det.video, dets)
    assert m_dets == pytest.approx(m_sync, abs=1e-12)


def test_synchronizer_tags_interpolated_frames():
    det = ParallelDetector("ETH-Sunnyday", "yolov3", ["ncs2"])
    paced = simulate(FrameStream(det.video), det.scheduler)
    sync = SequenceSynchronizer()
    tagged = sync.order_tracked(paced)
    assert [s.index for s in tagged] == list(range(paced.n_frames))
    processed = set(paced.processed_indices)
    for s in tagged:
        if s.index in processed:
            assert not s.interpolated and not s.stale
        else:
            assert s.interpolated and s.stale


# ------------------------------------------------- serving engine
def test_engine_track_and_interpolate_covers_stream_and_beats_drops():
    """Acceptance: stream rate 2x the single-replica detection rate —
    track-and-interpolate covers 100% of arrival frames and its
    full-stream mAP beats the drop-frames baseline."""
    from repro.serving import DetectionEngine, FrameRequest
    video = SyntheticVideo(ETH_SUNNYDAY)
    oracle = proxy_detect_fn(video, ProxyDetector("yolov3",
                                                  "ETH-Sunnyday"))
    mu, n = 2.5, 80
    frames = [FrameRequest(i, np.zeros((4, 4, 3), np.float32),
                           i / (2.0 * mu)) for i in range(n)]

    def run(**kw):
        eng = DetectionEngine(n_replicas=1, detect_fn=oracle,
                              service_time=1.0 / mu, **kw)
        out = eng.serve(frames)
        dets = responses_to_detections(out["responses"], n)
        return out, evaluate_map_dets(video, dets)

    out_d, map_d = run(drop_when_busy=True)
    assert out_d["coverage"] < 0.8                  # 2x overload drops
    out_t, map_t = run(track_and_interpolate=True)
    assert out_t["coverage"] == 1.0
    assert out_t["interpolated"] == len(out_d["dropped"]) > 0
    assert [r.rid for r in out_t["responses"]] == list(range(n))
    assert map_t > map_d
    for r in out_t["responses"]:
        if r.interpolated:
            assert r.replica == -1 and r.track_ids is not None


def test_engine_adaptive_micro_batching_matches_fixed():
    """Queue-depth-sized micro-batches must not change detections, and
    an overloaded stream must produce multi-frame batches."""
    from repro.serving import DetectionEngine, FrameRequest
    video = SyntheticVideo(ETH_SUNNYDAY)
    oracle = proxy_detect_fn(video, ProxyDetector("yolov3",
                                                  "ETH-Sunnyday"))
    frames = [FrameRequest(i, np.zeros((4, 4, 3), np.float32), i / 10.0)
              for i in range(24)]
    batch_sizes = []
    orig = DetectionEngine._detect_batch

    def spy(self, images, rids=None):
        batch_sizes.append(sum(1 for r in rids if r >= 0))
        return orig(self, images, rids)

    DetectionEngine._detect_batch = spy
    try:
        adaptive = DetectionEngine(n_replicas=2, detect_fn=oracle,
                                   service_time=0.4).serve(frames)
        fixed = DetectionEngine(n_replicas=2, detect_fn=oracle,
                                service_time=0.4,
                                micro_batch=1).serve(frames)
    finally:
        DetectionEngine._detect_batch = orig
    assert max(batch_sizes) > 1                     # depth-driven batching
    ra = sorted(adaptive["responses"], key=lambda r: r.rid)
    rf = sorted(fixed["responses"], key=lambda r: r.rid)
    assert [r.rid for r in ra] == [r.rid for r in rf]
    for a, b in zip(ra, rf):
        assert np.array_equal(a.valid, b.valid)
        assert np.array_equal(a.boxes[a.valid], b.boxes[b.valid])
