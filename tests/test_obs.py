"""Observability stack: trace recording semantics, streaming latency
histograms (merge == whole-run), the trace-replay invariant audit
(including its power to CATCH corrupted traces), and the Perfetto /
Chrome export.  Everything runs on the deterministic virtual clock, so
every recorded trace and every quantile replays bit-identically.
"""
import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import proxy_detect_fn_streams
from repro.obs import (LatencyHistogram, NullRecorder, TraceRecorder,
                       audit_events, audit_recorder,
                       detection_latency_keys, events_from_chrome,
                       merge_hist_dicts, quantile_of_dict,
                       to_chrome_trace)
from repro.serving import (DetectionEngine, FaultSchedule, FrameRequest,
                           Request, ServingEngine,
                           ShardedDetectionEngine, Watchdog,
                           make_nvr_streams)
from test_sharded_serving import assert_reports_identical


def nvr(n_streams=4, n_frames=16, **kw):
    frames, frame_of, videos, dets = make_nvr_streams(n_streams,
                                                      n_frames, rate=4.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    base = dict(detect_fn=oracle, n_replicas=2, service_time=0.02,
                track_and_interpolate=True)
    base.update(kw)
    return frames, base


# ===================================================== recorder basics
def test_recorder_event_schema_and_code_order():
    rec = TraceRecorder()
    rec.record("arrive", 1.0, rid=0)
    rec.record("arrive", 0.5, rid=1)       # earlier t, later code order
    assert [e["i"] for e in rec.events] == [0, 1]
    assert all({"i", "kind", "t"} <= set(e) for e in rec.events)
    # sorted_events orders by virtual time; raw order is code order
    assert [e["rid"] for e in rec.sorted_events()] == [1, 0]


def test_shard_view_stamps_and_shares_counter():
    rec = TraceRecorder()
    v0, v1 = rec.shard_view(0), rec.shard_view(1)
    v1.record("drop", 1.0, rid=3)
    v0.record("drop", 2.0, rid=4)
    v1.record("dispatch", 3.0, rid=5, replica=0, shard=7)  # explicit wins
    assert [(e["i"], e["shard"]) for e in rec.events] == \
        [(0, 1), (1, 0), (2, 7)]


def test_null_recorder_is_inert():
    rec = NullRecorder()
    assert not rec.enabled
    rec.record("arrive", 0.0, rid=0)
    rec.sample("queue_depth", 0.0, 1)
    assert rec.shard_view(3) is rec
    assert rec.to_json() == {"events": [], "series": []} or \
        rec.to_json() == {"events": [], "series": {}}


@pytest.mark.parametrize("sharded", [False, True])
def test_recorder_never_steers_the_report(sharded):
    """Tracing observes the serve and never steers it: the default
    engine, an explicit ``NullRecorder`` and a live ``TraceRecorder``
    give the same report, bit for bit (the sharded case records
    through per-shard views)."""
    extra = (dict(n_shards=2, rebalance=True, epoch_s=2.0) if sharded
             else {})
    frames, kw = nvr(n_streams=4, n_frames=16, **extra)
    cls = ShardedDetectionEngine if sharded else DetectionEngine
    default = cls(**kw).serve(frames)
    for rec in (NullRecorder(), TraceRecorder()):
        assert_reports_identical(default,
                                 cls(recorder=rec, **kw).serve(frames))


# ============================================== latency histogram units
def test_histogram_quantile_bounds_and_max():
    h = LatencyHistogram()
    lat = [0.010, 0.020, 0.030, 0.100]
    for x in lat:
        h.add(x)
    for q in (0.5, 0.95, 0.99):
        v = h.quantile(q)
        # quantiles come from bucket upper edges, clamped at the true max
        assert v <= h.max
        assert v >= np.quantile(lat, q) / 2 ** 0.25
    assert h.quantile(0.99) == h.max


def test_histogram_merge_equals_whole():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(-3, 1, 200)
    whole = LatencyHistogram()
    parts = [LatencyHistogram() for _ in range(4)]
    for i, x in enumerate(xs):
        whole.add(float(x))
        parts[i % 4].add(float(x))
    merged = LatencyHistogram()
    for p in parts:
        merged.merge(p)
    assert merged == whole
    assert merged.quantile(0.95) == whole.quantile(0.95)
    d = merge_hist_dicts([p.to_dict() for p in parts])
    assert LatencyHistogram.from_dict(d) == whole
    assert quantile_of_dict(d, 0.99) == whole.quantile(0.99)


def test_histogram_dict_round_trips_json():
    h = LatencyHistogram()
    h.add(0.05), h.add(1.5)
    again = LatencyHistogram.from_dict(
        json.loads(json.dumps(h.to_dict())))   # str keys coerce back
    assert again == h


# ===================================== engine report latency satellites
def test_detection_report_has_latency_keys():
    frames, kw = nvr()
    rep = DetectionEngine(**kw).serve(frames)
    lat = sorted(r.t_done - r.t_start for r in rep["responses"]
                 if not r.interpolated)
    assert rep["p50_latency"] == float(np.median(lat))
    assert rep["p95_latency"] >= rep["p50_latency"]
    assert rep["p99_latency"] >= rep["p95_latency"]
    assert rep["p99_latency"] <= max(lat)
    assert sum(rep["latency_hist"]["counts"].values()) == len(lat)


def test_interpolated_frames_excluded_from_detection_histogram():
    frames, kw = nvr(n_streams=6, n_frames=12)
    kw["service_time"] = 0.2                  # force drops -> interp
    rep = DetectionEngine(**kw).serve(frames)
    n_interp = sum(r.interpolated for r in rep["responses"])
    assert n_interp > 0
    n_det = sum(not r.interpolated for r in rep["responses"])
    assert sum(rep["latency_hist"]["counts"].values()) == n_det
    assert sum(rep["interp_latency"]["counts"].values()) == n_interp


def test_serving_engine_p95_p99_and_empty_trace_keys():
    cfg = get_config("minicpm-2b", preset="smoke")
    eng = ServingEngine(cfg, n_replicas=2, scheduler="fcfs",
                        cache_len=32)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size - 1, 8)
                    .astype(np.int32), 4, i / 50.0) for i in range(6)]
    rep = eng.serve(reqs)
    empty = eng.serve([])
    for k in ("p50_latency", "p95_latency", "p99_latency",
              "latency_hist"):
        assert k in rep and k in empty
    assert rep["p50_latency"] <= rep["p95_latency"] <= rep["p99_latency"]
    assert empty["p95_latency"] == 0.0
    assert sum(empty["latency_hist"]["counts"].values()) == 0


# ==================================== histogram merge == whole-run serve
@pytest.mark.parametrize("n_shards", [1, 4])
def test_sharded_merge_hist_equals_whole_run(n_shards):
    frames, kw = nvr(n_streams=8, n_frames=12)
    rep = ShardedDetectionEngine(n_shards=n_shards, **kw).serve(frames)
    whole = LatencyHistogram()
    for r in rep["responses"]:
        if not r.interpolated:
            whole.add(r.t_done - r.t_start)
    assert LatencyHistogram.from_dict(rep["latency_hist"]) == whole
    lat = sorted(r.t_done - r.t_start for r in rep["responses"]
                 if not r.interpolated)
    assert rep["p50_latency"] == float(np.median(lat))
    assert rep["p95_latency"] == whole.quantile(0.95)
    # per-epoch rollup conserves the same histogram
    per_epoch = rep["per_epoch"]
    assert merge_hist_dicts(
        [e["latency_hist"] for e in per_epoch.values()]) == \
        rep["latency_hist"]


def test_shards1_report_matches_base_engine_bits():
    frames, kw = nvr(n_streams=4, n_frames=10)
    base = DetectionEngine(**kw).serve(frames)
    shard = ShardedDetectionEngine(n_shards=1, **kw).serve(frames)
    for k in ("p50_latency", "p95_latency", "p99_latency",
              "latency_hist", "interp_latency", "latency_by_stream"):
        assert base[k] == shard[k], k


# =============================== event ordering / out-of-order complete
def test_trace_under_out_of_order_completion():
    """A slow replica makes a later-dispatched request finish first;
    the trace must show the inversion, and the audit (including emit
    monotonicity) must still hold."""
    cfg = get_config("minicpm-2b", preset="smoke")
    rec = TraceRecorder()
    eng = ServingEngine(cfg, n_replicas=2, scheduler="rr", cache_len=32,
                        replica_speeds=[8.0, 1.0], recorder=rec)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size - 1, 8)
                    .astype(np.int32), 4, 0.0) for i in range(4)]
    eng.serve(reqs)
    comp = [e for e in rec.events if e["kind"] == "complete"]
    by_dispatch = sorted(comp, key=lambda e: e["t0"])
    done = [e["t"] for e in by_dispatch]
    assert done != sorted(done), "expected out-of-order completion"
    res = audit_events(rec.events)
    assert res.ok, res.violations
    emits = [e["t"] for e in rec.events if e["kind"] == "emit"]
    assert emits == sorted(emits)


def test_detection_trace_frame_conservation():
    frames, kw = nvr(n_streams=6, n_frames=12)
    kw["service_time"] = 0.2                  # force drops
    rec = TraceRecorder()
    rep = DetectionEngine(recorder=rec, **kw).serve(frames)
    res = audit_recorder(rec)
    assert res.ok, res.violations
    assert res.stats["arrive"] == len(frames)
    assert res.stats["emitted"] == len(rep["responses"])


# ======================================= audit catches corrupted traces
def clean_trace():
    frames, kw = nvr(n_streams=4, n_frames=10)
    rec = TraceRecorder()
    DetectionEngine(recorder=rec, **kw).serve(frames)
    assert audit_recorder(rec).ok
    return rec.events


def test_audit_catches_vanished_frame():
    events = [e for e in clean_trace()
              if not (e["kind"] == "emit" and e["rid"] == 0)]
    res = audit_events(events)
    assert not res.ok
    assert any(v["rule"] == "frame_conservation" for v in res.violations)


def test_audit_catches_double_emit():
    events = clean_trace()
    dup = dict(next(e for e in events if e["kind"] == "emit"))
    dup["i"] = len(events)
    res = audit_events(events + [dup])
    assert any(v["rule"] == "frame_conservation" and "terminal" in
               v.get("why", "") for v in res.violations)


def test_audit_catches_emit_time_regression():
    events = clean_trace()
    emits = [e for e in events if e["kind"] in ("emit", "interp_emit")]
    emits[-1]["t"] = emits[0]["t"] - 1.0     # time goes backwards
    res = audit_events(events)
    assert any(v["rule"] == "emit_monotonicity" for v in res.violations)


def test_audit_catches_dead_replica_dispatch():
    events = clean_trace()
    disp = next(e for e in events if e["kind"] == "dispatch")
    mark = {"i": -1, "kind": "health_mark", "t": 0.0,
            "replica": disp["replica"]}
    res = audit_events([mark] + events)
    assert any(v["rule"] == "dead_replica_dispatch"
               for v in res.violations)


def test_audit_catches_unreturned_and_non_lifo_loans():
    base = [{"i": 0, "kind": "loan", "t": 1.0, "lender": 1,
             "borrower": 0, "guest": 2},
            {"i": 1, "kind": "loan", "t": 2.0, "lender": 3,
             "borrower": 0, "guest": 3}]
    res = audit_events(base)                      # never returned
    assert sum(v["rule"] == "loan_lifo" for v in res.violations) == 2
    out_of_order = base + [
        {"i": 2, "kind": "loan_return", "t": 3.0, "lender": 1,
         "borrower": 0, "guest": 2},              # FIFO, not LIFO
        {"i": 3, "kind": "loan_return", "t": 3.0, "lender": 3,
         "borrower": 0, "guest": 3}]
    res = audit_events(out_of_order)
    assert any(v["rule"] == "loan_lifo" and "LIFO" in v["why"]
               for v in res.violations)


# ==================================================== Perfetto export
def test_chrome_export_one_span_per_completed_frame():
    frames, kw = nvr(n_streams=4, n_frames=12)
    rec = TraceRecorder()
    ShardedDetectionEngine(n_shards=2, recorder=rec, **kw).serve(frames)
    doc = to_chrome_trace(rec.events, rec.series)
    json.dumps(doc, default=float)                # valid JSON document
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    completes = [e for e in rec.events if e["kind"] == "complete"]
    assert len(spans) == len(completes) > 0
    # lanes: pid = shard, tid = replica, with metadata naming both
    assert {e["pid"] for e in spans} == \
        {e.get("shard", 0) for e in completes}
    assert any(e["ph"] == "M" for e in doc["traceEvents"])
    # counters exported from the sampled series
    assert any(e["ph"] == "C" for e in doc["traceEvents"])
    # and the raw events survive the round trip
    back = events_from_chrome(doc)
    assert len(back) == len(rec.events)
    assert audit_events(back).ok


# ============================================= chaos-marked audit runs
@pytest.mark.chaos
def test_audit_clean_across_seeded_chaos():
    frames, kw = nvr(n_streams=4, n_frames=16, n_shards=2,
                     rebalance=True, epoch_s=2.0)
    for seed in range(4):
        rec = TraceRecorder()
        sched = FaultSchedule.random(seed=seed, horizon_s=4.0,
                                     n_shards=2, n_replicas=2,
                                     n_shard_events=1)
        ShardedDetectionEngine(faults=sched, supervisor=Watchdog(),
                               recorder=rec, **kw).serve(frames)
        res = audit_recorder(rec)
        assert res.ok, (seed, res.violations)
        assert res.stats["arrive"] == len(frames)


@pytest.mark.chaos
def test_chaos_trace_is_deterministic():
    frames, kw = nvr(n_streams=4, n_frames=12, n_shards=2,
                     rebalance=True, epoch_s=2.0)
    sched = FaultSchedule.random(seed=7, horizon_s=3.0, n_shards=2,
                                 n_replicas=2, n_shard_events=1)

    def run():
        rec = TraceRecorder()
        ShardedDetectionEngine(faults=sched, supervisor=Watchdog(),
                               recorder=rec, **kw).serve(frames)
        return rec.events

    assert run() == run()


@pytest.mark.chaos
def test_lending_trace_loans_lifo():
    """The watchdog lending scenario records loan/loan_return pairs the
    audit accepts (LIFO + all returned)."""

    def stub(images, rids=None):
        b = len(images)
        return (np.zeros((b, 4, 4), np.float32),
                np.zeros((b, 4), np.float32),
                np.zeros((b, 4), np.int32), np.zeros((b, 4), bool))

    events = [(k / 30.0, 0, k) for k in range(120)]
    events += [(k + 0.5, 1, k) for k in range(4)]
    events.sort()
    frames = [FrameRequest(rid, np.zeros((4, 4, 3), np.float32), t,
                           stream_id=s)
              for rid, (t, s, k) in enumerate(events)]
    rec = TraceRecorder()
    rep = ShardedDetectionEngine(
        detect_fn=stub, n_replicas=2, service_time=0.1,
        drop_when_busy=True, micro_batch=1, max_micro_batch=1,
        n_shards=2, rebalance=True, epoch_s=2.0,
        supervisor=Watchdog(idle_backlog_s=0.5),
        recorder=rec).serve(frames)
    assert rep["faults"]["loans"]
    loans = [e for e in rec.events if e["kind"] == "loan"]
    returns = [e for e in rec.events if e["kind"] == "loan_return"]
    assert len(loans) == len(returns) > 0
    res = audit_recorder(rec)
    assert res.ok, res.violations
