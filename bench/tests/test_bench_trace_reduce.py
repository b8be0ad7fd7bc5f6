"""Trace reduction on hand-built traces, and on a trace the profiler
records on this CPU."""
import pytest

from bench import run
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
    lay = tr.layer_seconds(tr.program_runs(_trace(), 0.0, 10.0), TABLE)
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


# A detect program whose ``nms`` scope runs a while loop with a cond in
# its body; the loop's own ops and the cond's branch carry relative
# op_names or none, as XLA leaves them, and so do two ops XLA made
# outside the loop: one feeds it, one copies the program's output.
HLO = """HloModule jit_infer, entry_computation_layout={()->f32[]}

%body (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  %fusion.3 = f32[] fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="while/body/add"}
  %cond.4 = f32[] conditional(%p, %p, %p), branch_computations={%br0, %br1}
  ROOT %fusion.6 = f32[] fusion(%cond.4), kind=kLoop, calls=%fc, metadata={op_name="jit(infer)/nms/sub"}
}

%br0 (q: f32[]) -> f32[] {
  ROOT %fusion.5 = f32[] fusion(%q), kind=kLoop, calls=%fc, metadata={op_name="cond/branch_0_fun/mul"}
}

ENTRY %main.9 () -> f32[] {
  %fusion.1 = f32[] fusion(), kind=kLoop, calls=%fc, metadata={op_name="jit(infer)/backbone/conv_general_dilated"}
  %rw.10 = f32[] reduce-window(%fusion.1), window={size=1}, to_apply=%sum
  %while.2 = f32[] while(%rw.10), condition=%cnd, body=%body, metadata={op_name="jit(infer)/nms/while"}
  %add.8 = f32[] add(%while.2, %while.2), metadata={op_name="jit(infer)/add"}
  %copy.7 = f32[] copy(%add.8), metadata={op_name="jit(infer)/decode/copy"}
  ROOT %copy.11 = f32[] copy(%copy.7)
}
"""


def _loop_trace():
    t = tr.Trace()
    t.modules["/device:TPU:0"] = [("jit_infer(7)", 1.0, 4.0),
                                  ("jit_step(3)", 6.0, 7.0)]
    t.ops["/device:TPU:0"] = [
        ("%fusion.1", 1.0, 1.4), ("%rw.10", 1.4, 1.5),
        ("%while.2", 1.5, 3.5),
        ("%fusion.3", 1.6, 2.0), ("%cond.4", 2.0, 3.0),
        ("%fusion.5", 2.1, 2.9), ("%fusion.6", 3.0, 3.4),
        ("%copy.7", 3.5, 4.2), ("%fusion.1", 6.0, 7.0)]
    t.modules["/device:TPU:1"] = [("jit_infer(7)", 1.0, 2.0)]
    t.ops["/device:TPU:1"] = [("%fusion.1", 1.0, 2.0)]
    t.spans = [("bench.window", 0.0, 10.0)]
    return t


def test_parse_hlo_names_each_op_by_its_top_scope():
    h = tr.parse_hlo(HLO)
    assert h.module == "jit_infer"
    # the loop body and the cond branch inherit the scope of the op that
    # runs them; an op outside every scope takes that of the op that
    # uses its result (rw.10, add.8), or failing that of the op that
    # makes its operand (copy.11)
    assert h.scope == {"p": "nms", "fusion.3": "nms", "cond.4": "nms",
                       "fusion.6": "nms", "fusion.5": "nms",
                       "fusion.1": "backbone", "rw.10": "nms",
                       "while.2": "nms", "add.8": "decode",
                       "copy.7": "decode", "copy.11": "decode"}


def test_exclusive_gives_each_op_its_own_time():
    ops = _loop_trace().ops["/device:TPU:0"]
    got = [(n, pytest.approx(a), pytest.approx(b))
           for n, a, b in tr.exclusive(ops)]
    # the while and the cond keep the time between the ops they hold;
    # nothing overlaps and the pieces cover what the ops cover
    assert got == [
        ("%fusion.1", 1.0, 1.4), ("%rw.10", 1.4, 1.5),
        ("%while.2", 1.5, 1.6), ("%fusion.3", 1.6, 2.0),
        ("%cond.4", 2.0, 2.1), ("%fusion.5", 2.1, 2.9),
        ("%cond.4", 2.9, 3.0), ("%fusion.6", 3.0, 3.4),
        ("%while.2", 3.4, 3.5), ("%copy.7", 3.5, 4.2),
        ("%fusion.1", 6.0, 7.0)]


def test_exclusive_cuts_an_op_that_outlasts_its_parent():
    # clock rounding: the inner op ends after the loop that holds it
    got = tr.exclusive([("w", 0.0, 1.0), ("a", 0.2, 1.1), ("b", 1.1, 1.5)])
    assert got == [("w", 0.0, 0.2), ("a", 0.2, 1.0), ("b", 1.1, 1.5)]


def test_scopes_count_no_op_twice():
    t = _loop_trace()
    s = tr.summarize(t, TABLE, [HLO])
    assert s.programs == {"jit_infer": (pytest.approx((3.0 + 1.0) / 2), 2),
                          "jit_step": (pytest.approx(0.5), 1)}
    # the while (2 s) and the cond (1 s) count only the 0.2 s each that
    # the body's ops leave; rw.10 counts to the loop it feeds; copy.7 is
    # clipped to its execution, which ends at 4 s; the jit_step op is in
    # no program whose text was given
    assert s.scopes == pytest.approx({
        "backbone": (0.4 + 1.0) / 2,
        "nms": (0.1 + 0.2 + 0.4 + 0.2 + 0.8 + 0.4) / 2,
        "decode": 0.5 / 2})
    # the ops cover each execution, so the scopes sum to the program
    assert sum(s.scopes.values()) == pytest.approx(
        s.programs["jit_infer"].seconds)
    # without the program's text there is nothing to name a scope by
    assert tr.summarize(t, TABLE).scopes == {}


def test_scopes_clip_to_the_window():
    s = tr.scope_seconds(_loop_trace(), [tr.parse_hlo(HLO)], 1.8, 3.2)
    # device 0: fusion.3 from 1.8, the cond's own 0.2 s, fusion.5,
    # fusion.6 to 3.2; device 1: its backbone op from 1.8 to 2.0
    assert s == pytest.approx({"nms": (0.2 + 0.2 + 0.8 + 0.2) / 2,
                               "backbone": 0.2 / 2})
    runs = tr.program_runs(_loop_trace(), 1.8, 3.2)
    assert runs == {"jit_infer": (pytest.approx((1.4 + 0.2) / 2), 2)}
    assert sum(s.values()) == pytest.approx(runs["jit_infer"].seconds)


def test_each_execution_read_against_the_text_that_names_its_ops():
    # a second compile of the program (another batch size) reuses the
    # name fusion.1 for an op of another scope, and has an op the first
    # lacks
    other = HLO.replace("jit(infer)/backbone/conv_general_dilated",
                        "jit(infer)/decode/exp").replace(
        "%copy.7 = f32[] copy(%add.8)", "%fusion.99 = f32[] copy(%add.8)")
    t = tr.Trace()
    t.modules["/device:TPU:0"] = [("jit_infer(7)", 0.0, 1.0),
                                  ("jit_infer(8)", 2.0, 3.0)]
    t.ops["/device:TPU:0"] = [("%fusion.1", 0.0, 0.5), ("%copy.7", 0.5, 1.0),
                              ("%fusion.1", 2.0, 2.5),
                              ("%fusion.99", 2.5, 3.0)]
    s = tr.scope_seconds(t, [tr.parse_hlo(HLO), tr.parse_hlo(other)],
                         0.0, 10.0)
    assert s == pytest.approx({"backbone": 0.5, "decode": 0.5 + 0.5 + 0.5})


def test_parse_hlo_of_a_compiled_program_with_a_loop():
    import jax
    import jax.numpy as jnp

    def infer(x):
        with jax.named_scope("backbone"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("nms"):
            y = jax.lax.fori_loop(0, 3, lambda i, c: jax.lax.cond(
                c.sum() > 0, jnp.sin, jnp.cos, c) * 1.01, y)
        return y.sum()

    text = jax.jit(infer).lower(jnp.ones((8, 8))).compile().as_text()
    h = tr.parse_hlo(text)
    assert h.module == "jit_infer"
    for line in text.splitlines():
        name = line.split(" = ")[0].split()[-1].lstrip("%") \
            if " = " in line else None
        if " sine(" in line or " cosine(" in line:
            assert h.scope[name] == "nms", line
        if " dot(" in line or " tanh(" in line:
            assert h.scope[name] == "backbone", line


def _ctx(summary, **kw):
    fam = run.load_module("families", "ssd")
    cfg = run.Cell("minissd64-eth14-steady").config
    ctx = {"trace": summary, "trace_detected": 40, "family": fam,
           "config": cfg, "chips": 1, "device_kind": "TPU v5 lite",
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "notes": {}}
    ctx.update(kw)
    return ctx


def test_scope_metrics_read_the_summary():
    s = tr.Summary(3.0, 0.1, {}, [], [], {"jit_infer": tr.Runs(0.02, 20)},
                   {"backbone": 0.004, "nms": 0.012})
    ctx = _ctx(s)
    fam, cfg = ctx["family"], ctx["config"]
    assert run.read_metric("nms_device_ms_per_frame", ctx) == \
        pytest.approx(12.0 / 40)
    # 40 frames in 20 calls: 2 frames a call, the weights read 20 times
    t_flops = 40 * fam.flops_by_scope(cfg)["backbone"] / 197e12
    t_bytes = 20 * fam.bytes_by_scope(cfg, 2.0)["backbone"] / 819e9
    assert t_bytes > t_flops
    assert run.read_metric("backbone_roofline", ctx) == \
        pytest.approx(100 * t_bytes / 0.004)
    assert ctx["notes"]["backbone_roofline"].startswith("bound by HBM bytes")


def test_scope_metrics_absent_without_their_scope():
    empty = tr.Summary(3.0, 0.1, {}, [], [], {}, {})
    for name in ("nms_device_ms_per_frame", "backbone_roofline"):
        assert run.read_metric(name, _ctx(None)) is None
        assert run.read_metric(name, _ctx(empty)) is None
        assert run.read_metric(name, _ctx(empty, trace_detected=0)) is None
