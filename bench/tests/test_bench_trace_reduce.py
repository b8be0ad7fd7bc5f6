"""Trace reduction on hand-built traces, and on a trace the profiler
records on this CPU."""
import pytest

from bench import trace_reduce as tr

TABLE = {"_doc": "x", "detector program": ["^jit__lambda"],
         "tracker tick": ["^jit_step", "^jit_output"]}


def _trace():
    t = tr.Trace()
    # two devices; window 0..10 s
    t.ops["/device:TPU:0"] = [("conv", 1.0, 3.0), ("nms", 2.5, 4.0),
                              ("add", 6.0, 7.0), ("conv", 9.5, 11.0)]
    t.ops["/device:TPU:1"] = [("conv", 1.0, 2.0)]
    t.modules["/device:TPU:0"] = [("jit__lambda_(12)", 1.0, 4.0),
                                  ("jit_step(3)", 6.0, 7.0),
                                  ("jit_other", 9.5, 11.0)]
    t.modules["/device:TPU:1"] = [("jit__lambda_(12)", 1.0, 2.0)]
    t.spans = [("bench.window", 0.0, 10.0), ("bench.advance", 0.5, 4.5),
               ("bench.wait", 4.5, 6.0), ("bench.boundary", 6.0, 9.0)]
    return t


def test_window_busy_and_union():
    t = _trace()
    assert tr.window(t) == (0.0, 10.0)
    assert tr.union([(3, 4), (1, 2), (1.5, 3.5)]) == [[1, 4]]
    # device 0: [1, 4] + [6, 7] + [9.5, 10] = 4.5 s; device 1: 1 s
    assert tr.busy_seconds(t, 0.0, 10.0) == pytest.approx((4.5 + 1.0) / 2)


def test_layer_seconds_by_name_table():
    lay = tr.layer_seconds(_trace(), TABLE, 0.0, 10.0)
    assert lay == pytest.approx({"detector program": (3.0 + 1.0) / 2,
                                 "tracker tick": 1.0 / 2})
    assert tr.layer_of("jit_other", TABLE) is None


def test_top_ops_and_idle_gaps_attributed_to_spans():
    t = _trace()
    top = tr.top_ops(t, 0.0, 10.0)
    assert top[0][0] == "conv"
    assert top[0][1] == pytest.approx((2.0 + 0.5 + 1.0) / 2)
    gaps = tr.idle_gaps(t, 0.0, 10.0, k=3)
    # device 1 idles 2..10 (8 s) mostly inside bench.boundary (3 s) and
    # bench.advance (2.5 s); device 0 idles 7..9.5 inside bench.boundary
    assert gaps[0] == ["bench.boundary", pytest.approx(8.0)]
    assert gaps[1] == ["bench.boundary", pytest.approx(2.5)]
    assert gaps[2] == ["bench.wait", pytest.approx(2.0)]


def test_summary_none_without_window():
    t = _trace()
    t.spans = t.spans[1:]
    assert tr.summarize(t, TABLE) is None
    s = tr.summarize(_trace(), TABLE)
    assert s.window_s == 10.0 and s.busy_s == pytest.approx(2.75)


def test_load_reads_harness_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.advance"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = {e[0] for e in t.spans}
    assert {"bench.window", "bench.advance"} <= names
    lo, hi = tr.window(t)
    adv = [e for e in t.spans if e[0] == "bench.advance"][0]
    assert lo <= adv[1] <= adv[2] <= hi
    # this CPU has no device plane: nothing to call busy
    assert tr.busy_seconds(t, lo, hi) is None
