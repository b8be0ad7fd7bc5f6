"""Multi-camera (NVR) serving: interleaved-stream micro-batches keep
per-stream arrival order, the lockstep B>1 tracker is bit-identical to
B independent B=1 runs, per-stream accounting sums to the global
totals, and an 8-camera overloaded run keeps per-stream coverage 1.0
with one tracker launch per tick."""
import jax.numpy as jnp
import numpy as np

from repro.core import (SyntheticVideo, evaluate_streams,
                        proxy_detect_fn_streams)
from repro.core.stream import ETH_SUNNYDAY
from repro.serving import (DetectionEngine, FrameRequest,
                           make_nvr_streams)
from repro.tracking import TrackerConfig, coast, init_state, output, step

make_streams = make_nvr_streams     # shared workload builder (serving)


def engine_for(frames, frame_of, videos, dets, **kw):
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    return DetectionEngine(detect_fn=oracle, **kw)


# ----------------------------------------------- interleaved batching
def test_interleaved_micro_batches_keep_per_stream_order():
    """Frames from different cameras share micro-batches (at least one
    fused launch must mix streams), yet each camera's responses come
    back in that camera's arrival order with consecutive seq."""
    n_streams, n_frames = 3, 8
    frames, frame_of, videos, dets = make_streams(n_streams, n_frames,
                                                  rate=10.0)
    batch_streams = []
    orig = DetectionEngine._detect_batch

    def spy(self, images, rids=None):
        batch_streams.append({frame_of[r][0] for r in rids if r >= 0})
        return orig(self, images, rids)

    DetectionEngine._detect_batch = spy
    try:
        eng = engine_for(frames, frame_of, videos, dets, n_replicas=2,
                         service_time=0.5)
        out = eng.serve(frames)
    finally:
        DetectionEngine._detect_batch = orig
    assert any(len(s) > 1 for s in batch_streams)   # streams co-batched
    assert out["n_streams"] == n_streams
    assert len(out["responses"]) == n_streams * n_frames
    for s in range(n_streams):
        rs = out["streams"][s]
        assert [r.seq for r in rs] == list(range(n_frames))
        assert all(r.stream_id == s for r in rs)
        arrivals = [frame_of[r.rid][1] for r in rs]
        assert arrivals == sorted(arrivals)         # per-stream order
        emits = out["emit_t"][s]                    # per-camera release
        assert len(emits) == len(rs)                # clock: monotone,
        assert emits == sorted(emits)               # never decreasing


# -------------------------------------------------- lockstep tracker
def _rand_tick(rng, D, present_p=0.7):
    """One stream-tick of detections (or None for a drop)."""
    if rng.random() > present_p:
        return None
    n = int(rng.integers(1, D + 1))
    tl = rng.uniform(0, 300, (n, 2))
    wh = rng.uniform(15, 60, (n, 2))
    boxes = np.zeros((D, 4), np.float32)
    boxes[:n] = np.concatenate([tl, tl + wh], -1)
    scores = np.zeros(D, np.float32)
    scores[:n] = rng.uniform(0.5, 1.0, n)
    classes = np.zeros(D, np.int32)
    classes[:n] = rng.integers(0, 3, n)
    valid = np.zeros(D, bool)
    valid[:n] = True
    return boxes, scores, classes, valid


def test_lockstep_b_gt_1_matches_independent_b1_runs():
    """The acceptance bar for the batched NVR tracker: stepping B
    streams in lockstep — streams without a detection this tick ride
    the same launch with an all-invalid row — must be bit-for-bit
    identical to B independent B=1 step/coast runs."""
    cfg = TrackerConfig(capacity=12)
    B, D, n_ticks = 4, 6, 15
    rng = np.random.default_rng(7)
    seqs = [[_rand_tick(rng, D) for _ in range(n_ticks)]
            for _ in range(B)]

    # lockstep: one launch per tick over all B streams
    state = init_state(B, cfg)
    lock_tids = []
    for k in range(n_ticks):
        boxes = np.zeros((B, D, 4), np.float32)
        scores = np.zeros((B, D), np.float32)
        classes = np.zeros((B, D), np.int32)
        valid = np.zeros((B, D), bool)
        any_det = False
        for b in range(B):
            tick = seqs[b][k]
            if tick is not None:
                boxes[b], scores[b], classes[b], valid[b] = tick
                any_det = True
        if any_det:
            state, tid = step(state, jnp.asarray(boxes),
                              jnp.asarray(scores), jnp.asarray(classes),
                              jnp.asarray(valid), cfg)
            lock_tids.append(np.asarray(tid))
        else:
            state = coast(state, cfg)
            lock_tids.append(np.full((B, D), -1, np.int32))
    lock_out = [np.asarray(a) for a in output(state, cfg)]

    # B independent single-stream runs
    for b in range(B):
        st = init_state(1, cfg)
        for k in range(n_ticks):
            tick = seqs[b][k]
            if tick is None:
                st = coast(st, cfg)
                tid = np.full((1, D), -1, np.int32)
            else:
                st, tid = step(st, *(jnp.asarray(a[None])
                                     for a in tick), cfg)
            assert np.array_equal(np.asarray(tid)[0], lock_tids[k][b]), \
                (b, k)
        for name in st._fields:
            lv = np.asarray(getattr(state, name))[b]
            iv = np.asarray(getattr(st, name))[0]
            assert np.array_equal(lv, iv), (b, name)
        ind_out = [np.asarray(a) for a in output(st, cfg)]
        for lo, io in zip(lock_out, ind_out):
            assert np.array_equal(lo[b], io[0]), b


# ------------------------------------------------ per-stream accounting
def test_per_stream_accounting_sums_to_global():
    """Drop-mode NVR run: per-stream frames/drops/responses must sum to
    the global report's totals, and per-stream coverage must match each
    camera's own ratio."""
    n_streams, n_frames = 4, 20
    frames, frame_of, videos, dets = make_streams(n_streams, n_frames,
                                                  rate=5.0)
    eng = engine_for(frames, frame_of, videos, dets, n_replicas=1,
                     service_time=0.4, drop_when_busy=True)
    out = eng.serve(frames)
    ps = out["per_stream"]
    assert set(ps) == set(range(n_streams))
    assert sum(v["frames"] for v in ps.values()) == len(frames)
    assert sum(v["dropped"] for v in ps.values()) == len(out["dropped"])
    assert len(out["dropped"]) > 0                  # 4x overload drops
    n_resp = sum(len(out["streams"][s]) for s in ps)
    assert n_resp == len(out["responses"])
    for s, v in ps.items():
        assert v["coverage"] == len(out["streams"][s]) / v["frames"]
    global_cov = len(out["responses"]) / len(frames)
    assert out["coverage"] == global_cov


def test_eight_camera_tracked_run_full_coverage_one_launch_per_tick(
        monkeypatch):
    """An 8-camera overloaded run under track_and_interpolate completes
    with per-stream coverage 1.0, exactly one tracker launch per tick
    (counted at the ``trk.step``/``trk.coast`` call sites, not only by
    the engine's own counter), per-stream arrival order, and a
    per-stream mAP win over the drop-frames baseline."""
    import repro.tracking as trk
    n_streams, n_frames = 8, 24
    frames, frame_of, videos, dets = make_streams(n_streams, n_frames,
                                                  rate=2.0)

    def run(**kw):
        eng = engine_for(frames, frame_of, videos, dets, n_replicas=2,
                         service_time=0.4, **kw)
        return eng.serve(frames)

    out_d = run(drop_when_busy=True)
    calls = {"n": 0}
    for name in ("step", "coast"):
        def spy(*a, _launch=getattr(trk, name), **kw):
            calls["n"] += 1
            return _launch(*a, **kw)
        monkeypatch.setattr(trk, name, spy)
    out_t = run(track_and_interpolate=True)
    assert out_t["coverage"] == 1.0
    assert out_t["n_streams"] == n_streams
    assert out_t["tracker_ticks"] == n_frames
    assert out_t["tracker_launches"] == n_frames    # one launch per tick
    assert calls["n"] == n_frames
    for s in range(n_streams):
        v = out_t["per_stream"][s]
        assert v["coverage"] == 1.0
        assert v["frames"] == n_frames
        assert [r.seq for r in out_t["streams"][s]] == list(range(n_frames))
    # interpolated frames: tracker-tagged, replica -1, tracked ids
    n_interp = sum(r.interpolated for r in out_t["responses"])
    assert n_interp == out_t["interpolated"] == len(out_d["dropped"]) > 0
    for r in out_t["responses"]:
        if r.interpolated:
            assert r.replica == -1 and r.track_ids is not None
    # per-stream quality: shared compute, per-camera accuracy accounting
    q_t = evaluate_streams(videos, out_t["streams"], n_frames)
    q_d = evaluate_streams(videos, out_d["streams"], n_frames)
    assert set(q_t["per_stream"]) == set(range(n_streams))
    assert q_t["map_mean"] > q_d["map_mean"]
    assert q_t["coverage_mean"] > 0.7


def test_single_stream_results_invariant_to_stream_relabeling():
    """A lone camera must get bit-identical boxes whether it is called
    stream 0 (the implicit single-stream default) or stream 42."""
    n_frames = 16
    video = SyntheticVideo(ETH_SUNNYDAY)

    def run(sid):
        frames, frame_of, videos, dets = make_streams(1, n_frames,
                                                      rate=5.0, video=video)
        frames = [FrameRequest(f.rid, f.image, f.t_arrival, sid)
                  for f in frames]
        frame_of = {rid: (sid, k) for rid, (_, k) in frame_of.items()}
        eng = engine_for(frames, frame_of, {sid: video},
                         {sid: dets[0]}, n_replicas=1,
                         service_time=0.4, track_and_interpolate=True)
        return eng.serve(frames)

    a, b = run(0), run(42)
    assert a["coverage"] == b["coverage"] == 1.0
    assert list(a["per_stream"]) == [0] and list(b["per_stream"]) == [42]
    for ra, rb in zip(a["responses"], b["responses"]):
        assert ra.interpolated == rb.interpolated
        assert np.array_equal(ra.boxes, rb.boxes)
        assert np.array_equal(ra.valid, rb.valid)
        assert np.array_equal(np.asarray(ra.track_ids),
                              np.asarray(rb.track_ids))
