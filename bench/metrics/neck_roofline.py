"""Detector program, its ``neck`` scope: ``backbone_roofline``'s
formula over the ``neck`` scope's device seconds -- the larger of the
neck's operations (its convs and the heads, padding taps left out) over
the bf16 peak and its bytes (its weights, once per detect call) over the
HBM bandwidth, for the frames detected in the traced window, over the
seconds of the ops under the ``neck`` named scope (``Summary.scopes``),
in %.  Counts from the family's ``flops_by_scope`` and
``bytes_by_scope``, each a lower bound.  The convs run in float32 at
``Precision.HIGHEST``, so this reads low by design.  Which bound binds
goes into the result line's ``notes``.  A program without a ``neck``
scope reads nothing."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_detected"]:
        return None
    seconds = tr.scopes.get("neck")
    runs = tr.programs.get("jit_infer")
    fam, cfg = ctx["family"], ctx["config"]
    flops = fam.flops_by_scope(cfg).get("neck")
    if not seconds or runs is None or flops is None:
        return None
    peaks = ctx["peaks"]
    if peaks is None:
        raise KeyError(f"no peaks for {ctx['device_kind']!r} in "
                       "bench/peaks.json")
    frames = ctx["trace_detected"]
    chips = ctx["chips"]
    t_flops = frames * flops / (chips * peaks["bf16_flops_per_s"])
    t_bytes = (runs.count * fam.bytes_by_scope(cfg, frames / runs.count)
               ["neck"] / (chips * peaks["hbm_bytes_per_s"]))
    bound = "operations" if t_flops >= t_bytes else "HBM bytes"
    ctx["notes"]["neck_roofline"] = (
        f"bound by {bound}: {t_flops} s of operations, {t_bytes} s of "
        f"bytes, {seconds} s on the device")
    return 100.0 * max(t_flops, t_bytes) / seconds
