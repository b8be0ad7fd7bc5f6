"""Unit tests for the shared tick pipeline (``repro.serving.pipeline``).

Covers the stage-pipeline refactor's kernel-level contracts — the
engine-level (report) equivalences live in
``tests/test_serving_properties.py``:

* the chunking helpers the engines now share are equivalent to the
  historical per-engine copies (delegation, not drift);
* portable track rows round trip bit-identically (export -> rebuild,
  any subset/reordering), and an all-fresh rebuild == ``init_state``;
* a ``TrackTable`` reads those rows only on demand, and the pipeline
  takes it as the next table only for the same streams in order;
* the fused one-jit tick program is bit-identical to the staged
  ``step``/``output`` chain, tick by tick, on every ``TrackerState``
  field, the per-detection track-id assignment and the output tuple;
* a fused tick over an all-invalid detection row is bit-identical to
  ``coast`` (the invariant that lets fused mode run ONE program);
* a ``fused_window`` scan (one launch per K-tick window) matches the
  staged chain tick by tick — stacked det_tid, stacked outputs, final
  table — including a detection-free tick mid-window;
* the post-processor hook composes: identity hook changes nothing,
  a mutating hook's output reaches the report.
"""
import numpy as np
import pytest

import repro.tracking as trk
from repro.core import proxy_detect_fn_streams
from repro.serving import (DetectionEngine, TickPipeline, TickState,
                           make_nvr_streams)
from repro.serving.pipeline import (TrackTable, bucket,
                                    build_tracker_state, confirmed_ids,
                                    export_track_rows, sorted_chunk)
from repro.tracking import TrackerConfig

CFG = TrackerConfig(capacity=16)


def random_dets(rng, B, D):
    tl = rng.uniform(0, 400, (B, D, 2)).astype(np.float32)
    wh = rng.uniform(10, 60, (B, D, 2)).astype(np.float32)
    return (np.concatenate([tl, tl + wh], -1),
            rng.uniform(0.5, 1.0, (B, D)).astype(np.float32),
            rng.integers(0, 3, (B, D)).astype(np.int32),
            rng.random((B, D)) > 0.2)


def assert_states_equal(a, b):
    for f in type(a)._fields:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f


# ------------------------------------------------------ chunking helpers
def test_bucket_matches_engine_delegate():
    for k in range(1, 40):
        assert bucket(k) == DetectionEngine._bucket(k)
        assert bucket(k) >= k and bucket(k) & (bucket(k) - 1) == 0


def test_sorted_chunk_single_and_stable():
    frames, _, _, _ = make_nvr_streams(2, 4, 5.0)
    one = sorted_chunk(frames[0])
    assert one == [frames[0]]
    shuffled = [frames[2], frames[0], frames[3], frames[1]]
    out = sorted_chunk(shuffled)
    assert [f.t_arrival for f in out] == sorted(f.t_arrival
                                                for f in frames[:4])
    # stable under arrival ties: equal keys keep input order
    frames[1].t_arrival = frames[0].t_arrival
    tied = sorted_chunk([frames[1], frames[0]])
    assert [f.rid for f in tied] == [frames[1].rid, frames[0].rid]


# --------------------------------------------------- portable track rows
def seeded_state(seed=0, B=3, D=5, ticks=4):
    rng = np.random.default_rng(seed)
    state = trk.init_state(B, CFG)
    for _ in range(ticks):
        state, _ = trk.step(state, *random_dets(rng, B, D), CFG)
    return state


def test_track_rows_round_trip_bit_identical():
    state = seeded_state()
    rows = trk.export_rows(state)
    assert_states_equal(trk.rows_to_state(rows, CFG), state)
    # keyed by stream id + rebuilt in a different order/subset
    sids = [7, 3, 9]
    by_sid = export_track_rows(state, sids)
    sub = build_tracker_state(by_sid, [9, 7], CFG)
    assert np.array_equal(np.asarray(sub.track_id[0]),
                          np.asarray(state.track_id[2]))
    assert np.array_equal(np.asarray(sub.track_id[1]),
                          np.asarray(state.track_id[0]))


def test_track_rows_fresh_equals_init_state():
    ref = trk.init_state(3, CFG)
    assert_states_equal(trk.rows_to_state([None] * 3, CFG), ref)
    assert_states_equal(build_tracker_state(None, [1, 2, 3], CFG), ref)
    assert_states_equal(build_tracker_state({}, [1, 2, 3], CFG), ref)
    # partial seed: carried row lands in ITS batch slot, others fresh
    state = seeded_state()
    rows = export_track_rows(state, [5, 6, 7])
    mixed = build_tracker_state({6: rows[6]}, [5, 6], CFG)
    assert np.array_equal(np.asarray(mixed.track_id[1]),
                          np.asarray(state.track_id[1]))
    assert np.array_equal(np.asarray(mixed.track_id[0]),
                          np.asarray(ref.track_id[0]))


def test_track_table_reads_rows_on_demand():
    """A ``TrackTable`` pulls nothing until a row is read, then reads
    ``export_track_rows``' rows; ``take`` hands the device table over,
    and rows not read before it can no longer be read."""
    state = seeded_state()
    sids = [7, 3, 9]
    eager = export_track_rows(state, sids)
    table = TrackTable(state, sids)
    assert len(table) == 3 and list(table) == sids and 3 in table
    assert table._rows is None            # nothing pulled yet
    for sid in sids:
        for f, v in eager[sid].items():
            assert np.array_equal(table[sid][f], v), (sid, f)
    assert table.pull() == 0              # already read
    assert_states_equal(table.take(), state)
    assert table.taken and table[9]["next_id"] == eager[9]["next_id"]
    unread = TrackTable(state, sids)
    unread.take()
    with pytest.raises(RuntimeError, match="handed to a later segment"):
        unread[7]
    # the pipeline takes a table for the same streams in the same order
    # and builds from its rows otherwise
    pipe = TickPipeline(CFG)
    assert pipe.seed(sids, resident=TrackTable(state, sids)) is state
    assert pipe.resident == 1
    pipe = TickPipeline(CFG)
    sub = pipe.seed([9, 7], resident=TrackTable(state, sids))
    assert pipe.resident == 0
    assert_states_equal(sub, build_tracker_state(eager, [9, 7], CFG))


def test_confirmed_ids_reads_the_emit_mask():
    state = seeded_state()
    rows = trk.export_rows(state)
    for b, row in enumerate(rows):
        emit = np.asarray(state.active[b]) & (
            np.asarray(state.hits[b]) >= CFG.min_hits)
        assert confirmed_ids(row, CFG) == sorted(
            int(t) for t in np.asarray(state.track_id[b])[emit])


# ------------------------------------------------------- fused tick program
@pytest.mark.parametrize("B,D", [(1, 4), (3, 5)])
def test_fused_tick_bit_identical_to_staged_chain(B, D):
    rng = np.random.default_rng(42)
    staged = TickPipeline(CFG)
    fused = TickPipeline(CFG, fused=True)
    s1 = staged.seed(list(range(B)))
    s2 = fused.seed(list(range(B)))
    for k in range(8):
        dets = random_dets(rng, B, D)
        if k == 5:            # a detection-free tick mid-sequence
            s1, o1 = staged.coast(s1, det_width=D)
            s2, o2 = fused.coast(s2, det_width=D)
            assert o1 is None and o2 is not None
        else:
            s1, tid1, o1 = staged.tick(s1, *dets)
            s2, tid2, o2 = fused.tick(s2, *dets)
            assert np.array_equal(tid1, tid2), k
            assert o1 is None and o2 is not None
        assert_states_equal(s1, s2)
        for a, b in zip(staged.output(s1), o2):
            assert np.array_equal(np.asarray(a), np.asarray(b)), k
    assert staged.launches == fused.launches == 8
    assert export_track_rows(s1, range(B)).keys() \
        == export_track_rows(s2, range(B)).keys()


def test_fused_window_bit_identical_to_staged_chain():
    from repro.serving.pipeline import fused_window
    rng = np.random.default_rng(7)
    B, D, K = 2, 5, 6
    ticks = [random_dets(rng, B, D) for _ in range(K)]
    ticks[3] = (np.zeros((B, D, 4), np.float32),
                np.zeros((B, D), np.float32),
                np.zeros((B, D), np.int32),
                np.zeros((B, D), bool))      # a detection-free tick
    s1 = trk.init_state(B, CFG)
    tids, outs = [], []
    for t in ticks:
        s1, tid = trk.step(s1, *t, CFG)
        tids.append(np.asarray(tid))
        outs.append([np.asarray(a) for a in trk.output(s1, CFG)])
    stacked = tuple(np.stack([t[i] for t in ticks]) for i in range(4))
    s2, wtid, wout = fused_window(trk.init_state(B, CFG), *stacked, CFG)
    assert_states_equal(s1, s2)
    for k in range(K):
        assert np.array_equal(np.asarray(wtid)[k], tids[k]), k
        for i, a in enumerate(wout):
            assert np.array_equal(np.asarray(a)[k], outs[k][i]), (k, i)


def test_fused_all_invalid_row_equals_coast():
    rng = np.random.default_rng(3)
    B, D = 2, 6
    pipe = TickPipeline(CFG, fused=True)
    state = pipe.seed([0, 1])
    for _ in range(3):
        state, _, _ = pipe.tick(state, *random_dets(rng, B, D))
    ref = trk.coast(trk.rows_to_state(trk.export_rows(state), CFG), CFG)
    state, out = pipe.coast(state, det_width=D)
    assert_states_equal(state, ref)
    for a, b in zip(out, trk.output(ref, CFG)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ post-processor hook
def serve_nvr(post_process=None, seed=0):
    frames, frame_of, videos, dets = make_nvr_streams(2, 8, 4.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    eng = DetectionEngine(detect_fn=oracle, n_replicas=2,
                          service_time=0.3, track_and_interpolate=True,
                          post_process=post_process)
    return eng.serve(frames)


def test_post_process_identity_hook_is_inert():
    from test_sharded_serving import assert_reports_identical
    assert_reports_identical(serve_nvr(), serve_nvr(lambda t: t))


def test_post_process_stage_rewrites_detections():
    thr = 0.9

    def gate(tick: TickState) -> TickState:
        keep = tick.valid & (np.asarray(tick.scores) >= thr)
        return tick._replace(valid=keep)

    out = serve_nvr(gate)
    for r in out["responses"]:
        if not r.interpolated:
            v = np.asarray(r.valid, bool)
            assert np.all(np.asarray(r.scores)[v] >= thr)
