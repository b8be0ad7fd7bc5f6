"""The program's spans in a trace: hand-built traces with nested
``serve.*`` spans and args, and a trace the profiler records on this
CPU while frames are served."""
import numpy as np
import pytest

from bench import span_reduce as sr
from bench import trace_reduce as tr


def _spans():
    # one emit boundary 6..9 s with the program's spans nested inside
    return [("bench.window", 0.0, 10.0, {}),
            ("bench.advance", 0.5, 4.5, {}),
            ("serve.batch", 0.6, 4.4, {"frames": 3, "dropped": 1}),
            ("serve.detect", 0.7, 4.3, {"frames": 2, "padded": 0}),
            ("serve.detect.run", 1.0, 4.0, {}),
            ("bench.wait", 4.5, 6.0, {}),
            ("bench.boundary", 6.0, 9.0, {}),
            ("serve.flush", 6.0, 6.2, {}),
            ("serve.track", 6.2, 7.6, {"ticks": 1, "streams": 2}),
            ("serve.track.tick", 6.3, 6.9, {"h2d_bytes": 100}),
            ("serve.track.export", 6.9, 7.5, {"d2h_bytes": 40}),
            ("serve.report", 7.6, 8.9, {}),
            ("serve.report.latency", 8.0, 8.8, {})]


def _trace():
    t = tr.Trace()
    t.ops["/device:TPU:0"] = [("conv", 1.0, 3.0), ("nms", 2.5, 4.0),
                              ("add", 6.0, 7.0), ("conv", 9.5, 11.0)]
    t.ops["/device:TPU:1"] = [("conv", 1.0, 2.0)]
    return t


def test_gap_named_by_innermost_covering_span():
    sp = _spans()
    # 7.0..7.5 lies inside bench.boundary, serve.track and
    # serve.track.export: the shortest of them names it
    assert sr.name_gap(7.0, 7.5, sp) == "serve.track.export"
    # 7.6..8.9: serve.report covers it whole, its latency block 0.8 s
    # of 1.3 (more than half), so the latency block names it
    assert sr.name_gap(7.6, 8.9, sp) == "serve.report.latency"
    # 4.0..6.0: bench.wait covers 1.5 of the 2 s
    assert sr.name_gap(4.0, 6.0, sp) == "bench.wait"
    # 2..10 (8 s): none covers half; the most overlap wins
    assert sr.name_gap(2.0, 10.0, sp[:2] + sp[5:7]) == "bench.boundary"
    assert sr.name_gap(11.0, 12.0, sp) == "none"


def test_harness_gaps_keep_their_names():
    # the hand-built trace of test_bench_trace_reduce, under both rules
    t = _trace()
    t.spans = [("bench.window", 0.0, 10.0), ("bench.advance", 0.5, 4.5),
               ("bench.wait", 4.5, 6.0), ("bench.boundary", 6.0, 9.0)]
    spans = [(n, a, b, {}) for n, a, b in t.spans]
    assert (sr.idle_gaps(t, spans, 0.0, 10.0, k=3)
            == tr.idle_gaps(t, 0.0, 10.0, k=3))


def test_idle_gaps_named_by_program_spans():
    t = _trace()
    gaps = sr.idle_gaps(t, _spans(), 0.0, 10.0, k=3)
    # device 1 idles 2..10: nothing covers half of 8 s, boundary has
    # the most overlap; device 0 idles 7..9.5, of which serve.report
    # (7.6..8.9) covers 1.3 of 2.5 s, and 4..6, which bench.wait takes
    assert gaps == [["bench.boundary", pytest.approx(8.0)],
                    ["serve.report", pytest.approx(2.5)],
                    ["bench.wait", pytest.approx(2.0)]]


def test_totals_count_clip_and_sum_args():
    sp = _spans() + [("serve.detect", 9.5, 10.5,
                      {"frames": 1, "padded": 1})]
    tot = sr.totals(sp, 0.0, 10.0)
    d = tot["serve.detect"]
    assert d.count == 2
    assert d.seconds == pytest.approx(3.6 + 0.5)     # clipped at 10
    assert d.args == {"frames": 3, "padded": 1}
    assert tot["serve.batch"].args == {"frames": 3, "dropped": 1}
    assert tot["serve.track.tick"].args == {"h2d_bytes": 100}
    # spans wholly outside the window count for nothing
    assert sr.totals(sp, 11.0, 12.0) == {}
    assert set(sr.totals(sp, 6.5, 7.0)) == {
        "bench.window", "bench.boundary", "serve.track",
        "serve.track.tick", "serve.track.export"}


def test_inside_reads_coverage():
    assert sr.inside([(1.0, 4.0)], [(0.7, 4.3)]) == pytest.approx(3.0)
    assert sr.inside([(1.0, 2.0), (3.0, 5.0)],
                     [(0.0, 1.5), (1.8, 3.5), (4.5, 9.0)]) \
        == pytest.approx(0.5 + 0.2 + 0.5 + 0.5)
    assert sr.inside([(1.0, 2.0)], []) == 0.0
    # the boundary 6..9 is covered by flush, track and report alone
    sp = _spans()
    inner = [(a, b) for n, a, b, _ in sp
             if n in ("serve.flush", "serve.track", "serve.report")]
    assert sr.inside([(6.0, 9.0)], inner) == pytest.approx(2.9)


def test_per_layer_numbers():
    sp = _spans() + [("serve.detect", 9.0, 9.4,
                      {"frames": 2, "padded": 0}),
                     ("serve.detect.put", 9.0, 9.1, {"h2d_bytes": 300}),
                     ("serve.detect.pull", 9.3, 9.4, {"d2h_bytes": 8})]
    m = sr.per_layer(sr.totals(sp, 0.0, 10.0))
    assert m["detect_call_ms_per_frame"] == pytest.approx(4000 / 4)
    assert m["detect_put_ms_per_frame"] == pytest.approx(100 / 4)
    assert m["detect_pull_ms_per_frame"] == pytest.approx(100 / 4)
    assert m["frames_per_detect_call"] == pytest.approx(2.0)
    assert m["h2d_bytes_per_frame"] == pytest.approx((300 + 100) / 4)
    assert m["track_host_ms_per_boundary"] == pytest.approx(1400)
    assert m["report_ms_per_boundary"] == pytest.approx(1300)


def test_per_layer_none_without_spans():
    # the harness's spans alone, as a trace of a program without spans
    sp = [s for s in _spans() if s[0].startswith("bench.")]
    m = sr.per_layer(sr.totals(sp, 0.0, 10.0))
    assert set(m) == {"detect_call_ms_per_frame", "detect_put_ms_per_frame",
                      "detect_pull_ms_per_frame", "frames_per_detect_call",
                      "h2d_bytes_per_frame", "track_host_ms_per_boundary",
                      "report_ms_per_boundary"}
    assert all(v is None for v in m.values())


def test_served_frames_leave_every_span_with_its_args(tmp_path):
    import jax
    from repro.obs.spans import SPANS
    from repro.serving import DetectionEngine, FrameRequest, ServingRuntime
    eng = DetectionEngine(track_and_interpolate=True, max_micro_batch=4)
    rng = np.random.default_rng(0)
    n, cams = 12, 3
    frames = [FrameRequest(i, rng.random((64, 64, 3), np.float32),
                           i * 0.01, stream_id=i % cams) for i in range(n)]
    rt = ServingRuntime(eng)
    rt.ingest(frames[:2])          # compile outside the trace
    rt.advance()
    rt.epoch_boundary()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for half in (frames[2:7], frames[7:]):
            rt.ingest(half)
            rt.advance()
            rt.epoch_boundary()
    jax.profiler.stop_trace()
    spans = sr.load(str(tmp_path))
    names = {s[0] for s in spans}
    assert set(SPANS) <= names
    tot = sr.totals(spans, *tr.window(tr.load(str(tmp_path))))
    det, batch = tot["serve.detect"], tot["serve.batch"]
    assert batch.args["frames"] == n - 2
    assert det.args["frames"] == n - 2 - batch.args["dropped"]
    slots = det.args["frames"] + det.args["padded"]
    assert (tot["serve.detect.put"].args["h2d_bytes"]
            == slots * 64 * 64 * 3 * 4)
    assert tot["serve.detect.pull"].args["d2h_bytes"] > 0
    assert tot["serve.track"].count == 2
    assert tot["serve.track"].args["streams"] == 2 * cams
    assert tot["serve.track.tick"].count == tot["serve.track"].args["ticks"]
    assert tot["serve.track.tick"].args["h2d_bytes"] > 0
    # both segments seed from the rows the one before exported
    assert tot["serve.track.seed"].args["h2d_bytes"] > 0
    assert tot["serve.track.export"].args["d2h_bytes"] > 0
    for name in ("serve.ingest", "serve.advance", "serve.boundary",
                 "serve.flush", "serve.report", "serve.report.latency"):
        assert tot[name].count >= 2, name
    m = sr.per_layer(tot)
    assert all(v is not None and v > 0 for v in m.values()), m
