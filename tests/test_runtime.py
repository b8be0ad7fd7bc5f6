"""Incremental serving core (``repro.serving.runtime``), event pipeline
(``repro.serving.events``) and daemon (``repro.launch.daemon``).

The load-bearing bar is bit-identity: any chunking of ``ingest`` +
``advance`` must drain to byte-for-byte the one-shot batch ``serve``
report — on the plain engine AND the rebalancing sharded engine under a
seeded fault schedule.  On top of that: the unified ``reset`` semantic
(back-to-back serves independent on every engine), rolling per-epoch
reports that merge exactly (histograms summed bucket-wise, quantiles
recomputed — never averaged), the trace-derived event bus (every
recorded event routed, shard views included, audit-clean), and the
daemon (virtual clock, graceful stop, drained in-flight frames with
frame conservation)."""
import io
import json

import numpy as np
import pytest

from repro.core import proxy_detect_fn_streams
from repro.launch.daemon import ServingDaemon, VirtualClock, WallClock
from repro.obs import audit_recorder
from repro.obs.metrics import LatencyHistogram
from repro.serving import (DetectionEngine, EventBus, FaultSchedule,
                           JsonlSink, ServingRuntime,
                           ShardedDetectionEngine, make_nvr_streams,
                           topic_of)
from test_sharded_serving import assert_reports_identical

CHUNKS = (1, 3, 7, None)          # None = the whole trace in one chunk


def nvr_setup(n_streams=3, n_frames=10, rate=4.0):
    frames, frame_of, videos, dets = make_nvr_streams(
        n_streams, n_frames, rate)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    return sorted(frames, key=lambda f: f.t_arrival), oracle


def det_engine(oracle, **kw):
    return DetectionEngine(detect_fn=oracle, n_replicas=2,
                           service_time=0.3, track_and_interpolate=True,
                           **kw)


def feed_chunked(rt, frames, chunk):
    step = chunk or len(frames)
    for i in range(0, len(frames), step):
        rt.ingest(frames[i:i + step])
        rt.advance()              # watermark advance: nothing future


# ------------------------------------------- chunked == one-shot batch
@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_ingest_matches_one_shot_detection(chunk):
    frames, oracle = nvr_setup()
    base = det_engine(oracle).serve(frames)
    rt = ServingRuntime(det_engine(oracle))
    feed_chunked(rt, frames, chunk)
    out = rt.drain()
    assert set(out) == set(base)
    assert_reports_identical(base, out)


@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_ingest_matches_one_shot_sharded_faults(chunk):
    """The hard configuration: rebalancing epochs + seeded replica AND
    shard faults.  The pending-boundary restructure must reproduce the
    batch epoch loop's action sequence exactly."""
    frames, oracle = nvr_setup(n_streams=4, n_frames=12, rate=2.0)
    kw = dict(detect_fn=oracle, n_shards=2, n_replicas=2,
              service_time=0.3, track_and_interpolate=True,
              rebalance=True, epoch_s=2.0)

    def faults():
        return FaultSchedule.random(
            7, horizon_s=frames[-1].t_arrival, n_shards=2, n_replicas=2,
            n_replica_events=2, n_shard_events=1)

    base = ShardedDetectionEngine(faults=faults(), **kw).serve(frames)
    assert base["faults"]["frames_lost_shard"]   # the chaos actually bit
    rt = ServingRuntime(ShardedDetectionEngine(faults=faults(), **kw),
                        streams=range(4))
    feed_chunked(rt, frames, chunk)
    out = rt.drain()
    assert set(out) == set(base)
    assert_reports_identical(base, out)


@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_ingest_matches_one_shot_sharded_rebalance(chunk):
    """Rebalancing epochs without faults: any chunking drains to the
    batch report."""
    frames, oracle = nvr_setup(n_streams=4, n_frames=12, rate=2.0)
    kw = dict(detect_fn=oracle, n_shards=2, n_replicas=2,
              service_time=0.3, track_and_interpolate=True,
              rebalance=True, epoch_s=2.0)
    base = ShardedDetectionEngine(**kw).serve(frames)
    rt = ServingRuntime(ShardedDetectionEngine(**kw), streams=range(4))
    feed_chunked(rt, frames, chunk)
    out = rt.drain()
    assert set(out) == set(base)
    assert_reports_identical(base, out)


@pytest.mark.parametrize("chunk", (1, 5))
def test_chunked_ingest_matches_one_shot_sharded_static(chunk):
    frames, oracle = nvr_setup(n_streams=4, n_frames=8, rate=2.0)
    kw = dict(detect_fn=oracle, n_shards=2, n_replicas=2,
              service_time=0.3, track_and_interpolate=True)
    base = ShardedDetectionEngine(**kw).serve(frames)
    rt = ServingRuntime(ShardedDetectionEngine(**kw), streams=range(4))
    feed_chunked(rt, frames, chunk)
    out = rt.drain()
    assert set(out) == set(base)
    assert_reports_identical(base, out)


# ------------------------------------------------- unified reset story
def test_unified_reset_back_to_back_detection():
    frames, oracle = nvr_setup()
    eng = det_engine(oracle)
    r1 = eng.serve(frames)
    r2 = eng.serve(frames)                 # serve() resets by default
    assert_reports_identical(r1, r2)
    eng.reset()                            # the documented explicit path
    r3 = eng.serve(frames, reset=False)
    assert_reports_identical(r1, r3)


def test_unified_reset_back_to_back_sharded():
    """``ShardedDetectionEngine.reset`` (new — the class had none) and
    ``ServingRuntime.reset`` both route through ``reset_engines`` and
    leave the engine exactly as serve()'s own reset would."""
    frames, oracle = nvr_setup(n_streams=4, n_frames=8, rate=2.0)
    seng = ShardedDetectionEngine(
        detect_fn=oracle, n_shards=2, n_replicas=2, service_time=0.3,
        track_and_interpolate=True, rebalance=True, epoch_s=2.0)
    r1 = seng.serve(frames)
    seng.reset()
    r2 = seng.serve(frames)
    assert_reports_identical(r1, r2)
    rt = ServingRuntime(seng, streams=range(4))
    rt.ingest(frames)
    out1 = rt.drain()
    rt.reset()                     # fresh watermark + segments + floors
    rt.ingest(frames)
    out2 = rt.drain()
    assert_reports_identical(out1, out2)
    assert_reports_identical(r1, out1)


# ------------------------------------------------ rolling epoch reports
def test_rolling_reports_merge_exactly_to_final():
    frames, oracle = nvr_setup(n_streams=3, n_frames=12, rate=4.0)
    rt = ServingRuntime(det_engine(oracle))
    step = len(frames) // 3
    epochs = []
    for i in range(0, len(frames), step):
        rt.ingest(frames[i:i + step])
        epochs.append(rt.epoch_boundary())
    assert len(rt.report(rolling=True)) == len(epochs)
    final = rt.drain()
    # every response lands in exactly one epoch window
    rids = sorted(r.rid for e in epochs for r in e["responses"])
    assert sorted(r.rid for r in final["responses"]) == rids
    assert sum(len(e["dropped"]) for e in epochs) == len(final["dropped"])
    # merge-never-average: histograms sum bucket-wise...
    merged = LatencyHistogram()
    for e in epochs:
        h = LatencyHistogram()
        h.counts = dict(e["latency_hist"]["counts"])
        h.n, h.max = e["latency_hist"]["n"], e["latency_hist"]["max"]
        merged.merge(h)
    assert final["latency_hist"]["counts"] == merged.counts
    assert final["latency_hist"]["n"] == merged.n
    # ...and quantiles recompute from the merged buckets
    assert final["p95_latency"] == merged.quantile(0.95)
    assert final["p99_latency"] == merged.quantile(0.99)
    # p50 is the exact median over the merged detections
    lat = [r.t_done - r.t_start for r in final["responses"]
           if not r.interpolated]
    assert final["p50_latency"] == pytest.approx(float(np.median(lat)))
    # per-stream frame totals conserve across the windows
    for sid in final["per_stream"]:
        assert final["per_stream"][sid]["frames"] == sum(
            e["per_stream"].get(sid, {"frames": 0})["frames"]
            for e in epochs)


def test_mid_serve_report_is_non_destructive():
    """A rolling peek must not perturb the final report: two identical
    runtimes, one peeked mid-serve, drain bit-identically."""
    frames, oracle = nvr_setup()
    ra = ServingRuntime(det_engine(oracle))
    rb = ServingRuntime(det_engine(oracle))
    half = len(frames) // 2
    for rt in (ra, rb):
        rt.ingest(frames[:half])
        rt.advance()
    peek = ra.report(rolling=False)
    assert peek["partial"] is True
    assert peek["responses"]             # something already completed
    for rt in (ra, rb):
        rt.ingest(frames[half:])
    assert_reports_identical(rb.drain(), ra.drain())


def test_sharded_rolling_rollups():
    frames, oracle = nvr_setup(n_streams=4, n_frames=12, rate=2.0)
    seng = ShardedDetectionEngine(
        detect_fn=oracle, n_shards=2, n_replicas=2, service_time=0.3,
        track_and_interpolate=True, rebalance=True, epoch_s=2.0)
    rt = ServingRuntime(seng, streams=range(4))
    feed_chunked(rt, frames, 3)
    final = rt.drain()
    per_epoch = rt.report(rolling=True)
    # the rolling rollups ARE the final report's per_epoch entries
    assert per_epoch == [final["per_epoch"][e]
                         for e in sorted(final["per_epoch"])]
    # fault-free + blocking mode: every frame ends up in some window
    assert sum(e["responses"] for e in per_epoch) == len(frames)
    assert sum(e["dropped"] for e in per_epoch) == 0


# ------------------------------------------- resident track table
def rows_every_boundary(rt):
    """Make ``rt`` carry its track table as host rows across every
    boundary: the row round trip the resident table replaces."""
    core = rt._core
    boundary = core.epoch_boundary

    def epoch_boundary():
        rep = boundary()
        if core._resident is not None:
            core._tracks0.update(core._resident)
            core._resident = None
        return rep

    core.epoch_boundary = epoch_boundary
    return rt


def serve_segments(rt, segments, peek=False):
    """One ``epoch_boundary`` per segment, then ``drain``; with
    ``peek`` a ``report()`` halfway into every segment, once every
    camera has a processed frame (so its tracker serves the resident
    table's streams)."""
    reps = []
    for seg in segments:
        half = len(seg) // 2 + 1 if peek else len(seg)
        rt.ingest(seg[:half])
        if peek:
            rt.advance()
            part = rt.report()[-1]
            assert part["partial"]
            assert all(v["frames"] for v in part["per_stream"].values())
            assert rt._core._resident is None or \
                not rt._core._resident.taken
            rt.ingest(seg[half:])
        reps.append(rt.epoch_boundary())
    return reps, rt.drain()


def without_counter(rep):
    return {k: v for k, v in rep.items() if k != "track_table_resident"}


def assert_segments_identical(a, b):
    (ra, fa), (rb, fb) = a, b
    assert len(ra) == len(rb)
    for x, y in zip(ra, rb):
        assert_reports_identical(without_counter(x), without_counter(y))
    assert_reports_identical(without_counter(fa), without_counter(fb))


def assert_rows_identical(a, b):
    assert list(a) == list(b)
    for sid in a:
        for f, v in a[sid].items():
            assert np.array_equal(v, b[sid][f]), (sid, f)


def segments_of(frames, n_streams, per_seg):
    """Frames (arrival order) cut into segments of ``per_seg`` frames
    per camera: every segment serves every camera."""
    step = per_seg * n_streams
    return [frames[i:i + step] for i in range(0, len(frames), step)]


@pytest.mark.parametrize("fused", (False, True))
@pytest.mark.parametrize("rate", (1.0, 4.0), ids=("steady", "drops"))
def test_resident_track_table_matches_row_round_trip(fused, rate):
    frames, oracle = nvr_setup(n_streams=3, n_frames=12, rate=rate)
    segments = segments_of(frames, 3, 2)
    assert len(segments) >= 5
    got_eng = det_engine(oracle, fused_tick=fused)
    ref_eng = det_engine(oracle, fused_tick=fused)
    got = serve_segments(ServingRuntime(got_eng), segments)
    ref = serve_segments(rows_every_boundary(ServingRuntime(ref_eng)),
                         segments)
    assert_segments_identical(got, ref)
    assert [r["track_table_resident"] for r in got[0]] == \
        [0] + [1] * (len(segments) - 1)
    assert [r["track_table_resident"] for r in ref[0]] == \
        [0] * len(segments)
    assert got[1]["track_table_resident"] == len(segments) - 1
    interpolated = sum(r["interpolated"] for r in got[0])
    assert (interpolated > 0) == (rate == 4.0)   # drops coast the table
    assert any(np.any(np.asarray(r.track_ids) >= 0)
               for r in got[1]["responses"] if r.interpolated is False)
    assert_rows_identical(got_eng._exported_tracks,
                          ref_eng._exported_tracks)


class SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each
    span's name and args."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **args):
        log = self

        class Span:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                log.spans.append((name, args))

            def set_metadata(self, **kw):
                args.update(kw)

        return Span()

    def args(self, name):
        return [a for n, a in self.spans if n == name]


@pytest.mark.parametrize("fused", (False, True))
def test_resident_track_table_falls_back_to_rows(fused, monkeypatch):
    """A stream that has no frame in a segment (leaves), comes back,
    or is new rebuilds the table from rows, bit-identically; the same
    stream set takes the device table."""
    from repro.serving import pipeline
    frames, oracle = nvr_setup(n_streams=4, n_frames=14, rate=4.0)
    plan = [(0, 1, 2), (0, 1, 2), (0, 1), (0, 1), (0, 1, 2),
            (0, 1, 2, 3), (0, 1, 2, 3)]
    segments = []
    for j, sids in enumerate(plan):
        seg = frames[j * 2 * 4:(j + 1) * 2 * 4]
        segments.append([f for f in seg if f.stream_id in sids])
    log = SpanLog()
    monkeypatch.setattr(pipeline, "span", log)
    got_eng = det_engine(oracle, fused_tick=fused)
    got = serve_segments(ServingRuntime(got_eng), segments)
    seeds = log.args("serve.track.seed")
    exports = log.args("serve.track.export")
    ref_eng = det_engine(oracle, fused_tick=fused)
    ref = serve_segments(rows_every_boundary(ServingRuntime(ref_eng)),
                         segments)
    assert_segments_identical(got, ref)
    assert_rows_identical(got_eng._exported_tracks,
                          ref_eng._exported_tracks)
    resident = [0, 1, 0, 1, 0, 0, 1]
    assert [r["track_table_resident"] for r in got[0]] == resident
    assert [a["resident"] for a in seeds] == resident
    assert all(a["h2d_bytes"] == 0 for a in seeds if a["resident"])
    assert all(a["h2d_bytes"] > 0 for a in seeds[1:] if not a["resident"])
    # every segment hands its table on without a pull; only the three
    # rebuilds that follow a device table read its rows
    assert [a["d2h_bytes"] > 0 for a in exports].count(False) == len(plan)
    assert [a["d2h_bytes"] > 0 for a in exports].count(True) == 3


def test_peek_leaves_resident_table_to_the_boundary():
    """``report()`` mid-segment (the non-destructive peek) under the
    donating fused tick neither takes nor replaces the device table:
    the boundaries still start from it, and everything (final rows
    included) matches the run without peeks."""
    frames, oracle = nvr_setup(n_streams=3, n_frames=12, rate=1.0)
    segments = segments_of(frames, 3, 2)
    peek_eng = det_engine(oracle, fused_tick=True)
    plain_eng = det_engine(oracle, fused_tick=True)
    peeked = serve_segments(ServingRuntime(peek_eng), segments, peek=True)
    plain = serve_segments(ServingRuntime(plain_eng), segments)
    assert_segments_identical(peeked, plain)
    assert [r["track_table_resident"] for r in peeked[0]] == \
        [0] + [1] * (len(segments) - 1)
    ref_eng = det_engine(oracle, fused_tick=True)
    serve_segments(rows_every_boundary(ServingRuntime(ref_eng)), segments)
    assert_rows_identical(peek_eng._exported_tracks,
                          ref_eng._exported_tracks)


# ------------------------------------------------- contract violations
def test_watermark_violation_raises():
    frames, oracle = nvr_setup()
    rt = ServingRuntime(det_engine(oracle))
    rt.ingest(frames[5:])
    with pytest.raises(ValueError, match="watermark"):
        rt.ingest(frames[:5])


def test_incremental_sharded_requires_streams():
    frames, oracle = nvr_setup(n_streams=4, n_frames=6, rate=2.0)
    kw = dict(detect_fn=oracle, n_shards=2, n_replicas=2,
              service_time=0.3, track_and_interpolate=True)
    rt = ServingRuntime(ShardedDetectionEngine(**kw))   # no streams=
    rt.ingest(frames)
    with pytest.raises(RuntimeError, match="streams"):
        rt.epoch_boundary()
    base = ShardedDetectionEngine(**kw).serve(frames)
    out = rt.drain()                  # lazy batch replay is still exact
    assert_reports_identical(base, out)


def test_runtime_rejects_bad_engines_and_hooks():
    frames, oracle = nvr_setup(n_streams=2, n_frames=2, rate=2.0)
    seng = ShardedDetectionEngine(detect_fn=oracle, n_shards=2,
                                  n_replicas=2, service_time=0.3)
    with pytest.raises(ValueError, match="warm-start"):
        ServingRuntime(seng, stream_seq0={0: 1})
    with pytest.raises(TypeError):
        ServingRuntime(object())


# ------------------------------------------------------- event pipeline
def test_event_bus_taps_every_trace_event():
    frames, oracle = nvr_setup(n_streams=4, n_frames=8, rate=2.0)
    bus = EventBus()
    got = []
    h = bus.subscribe(lambda t, e: got.append((t, e["kind"])),
                      topics=("detection", "drop"))
    buf = io.StringIO()
    sink = JsonlSink(buf)
    bus.subscribe(sink)
    rec = bus.recorder()
    seng = ShardedDetectionEngine(
        detect_fn=oracle, n_shards=2, n_replicas=2, service_time=0.3,
        track_and_interpolate=True, recorder=rec)
    seng.serve(frames)
    # every recorded event was published exactly once (shard views
    # append to the parent log directly — the tap must cover them too)
    assert sum(bus.counts.values()) == len(rec.events) == sink.n_written
    assert any("shard" in e for e in rec.events)
    assert got and all(t in ("detection", "drop") for t, _ in got)
    lines = [json.loads(s) for s in buf.getvalue().splitlines()]
    assert len(lines) == len(rec.events)
    assert {ln["kind"] for ln in lines} == {e["kind"] for e in rec.events}
    assert all(ln["topic"] == topic_of(ln["kind"]) for ln in lines)
    assert audit_recorder(rec).ok     # the tapped log is still the log
    bus.unsubscribe(h)
    n = len(got)
    bus.publish({"kind": "complete", "t": 0.0})
    assert len(got) == n              # unsubscribed
    with pytest.raises(ValueError, match="unknown topics"):
        bus.subscribe(lambda *a: None, topics=("nope",))
    assert topic_of("some_future_kind") == "lifecycle"


# --------------------------------------------------------------- daemon
def test_daemon_virtual_clock_matches_batch_and_audits():
    frames, oracle = nvr_setup(n_streams=4, n_frames=8, rate=2.0)
    kw = dict(detect_fn=oracle, n_shards=2, n_replicas=2,
              service_time=0.3, track_and_interpolate=True)
    base = ShardedDetectionEngine(**kw).serve(frames)
    bus = EventBus()
    rec = bus.recorder()
    eng = ShardedDetectionEngine(recorder=rec, **kw)
    daemon = ServingDaemon(ServingRuntime(eng, streams=range(4)),
                           clock=VirtualClock(), chunk=3)
    out = daemon.run(frames)
    assert daemon.frames_ingested == len(frames)
    assert daemon.runtime.frames_pending == 0
    assert_reports_identical(base, out)
    res = audit_recorder(rec)         # frame conservation et al.
    assert res.ok, res.violations[:3]
    assert bus.counts.get("detection", 0) > 0


def test_daemon_drains_rebalancing_serve_through_the_bus():
    """The daemon on the rebalancing sharded engine: every frame is
    ingested and drained, the bus publishes every recorded event, and
    the tapped trace is audit-clean."""
    frames, oracle = nvr_setup(n_streams=4, n_frames=12, rate=2.0)
    bus = EventBus()
    rec = bus.recorder()
    eng = ShardedDetectionEngine(
        detect_fn=oracle, n_shards=2, n_replicas=2, service_time=0.3,
        track_and_interpolate=True, rebalance=True, epoch_s=2.0,
        recorder=rec)
    daemon = ServingDaemon(ServingRuntime(eng, streams=range(4)),
                           clock=VirtualClock(), chunk=4)
    daemon.run(frames)
    assert daemon.frames_ingested == len(frames)
    assert daemon.runtime.frames_pending == 0
    assert sum(bus.counts.values()) == len(rec.events)
    res = audit_recorder(rec)
    assert res.ok, res.violations[:3]


def test_daemon_graceful_stop_drains_ingested_frames():
    frames, oracle = nvr_setup(n_streams=3, n_frames=8, rate=4.0)
    rt = ServingRuntime(det_engine(oracle))
    daemon = ServingDaemon(rt, clock=VirtualClock(), chunk=2)

    def feed():
        for k, f in enumerate(frames):
            if k == 10:
                daemon.request_stop()
            yield f

    out = daemon.run(feed())
    n = daemon.frames_ingested
    assert 0 < n <= 10
    assert rt.frames_pending == 0     # in-flight frames were drained
    accounted = {r.rid for r in out["responses"]} | set(out["dropped"])
    assert accounted == {f.rid for f in frames[:n]}


def test_clocks():
    c = VirtualClock()
    assert c.now() == 0.0
    c.sleep_until(2.5)
    c.sleep_until(1.0)                # never goes backwards
    assert c.now() == 2.5
    w = WallClock()
    t0 = w.now()
    w.sleep_until(t0 - 1.0)           # already past: returns immediately
    assert w.now() >= t0
    with pytest.raises(ValueError):
        ServingDaemon(ServingRuntime(det_engine(nvr_setup()[1])),
                      chunk=0)


def test_daemon_cli_smoke(tmp_path, capsys):
    from repro.launch import daemon as dmod
    ev = tmp_path / "ev.jsonl"
    dmod.main(["--cameras", "3", "--frames", "6", "--shards", "2",
               "--clock", "virtual", "--events", str(ev), "--chunk", "2"])
    out = capsys.readouterr().out
    assert "audit=ok" in out and "pending=0" in out
    lines = [json.loads(s) for s in ev.read_text().splitlines()]
    assert lines and all("topic" in ln and "kind" in ln for ln in lines)
