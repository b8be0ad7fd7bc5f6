"""Detector program, its ``backbone`` scope: the least time the chip
could take for the backbone of the frames detected in the traced window
-- the larger of their operations over the bf16 peak and their bytes
over the HBM bandwidth (``bench/peaks.json``) -- over the device seconds
of the ``backbone`` scope (``Summary.scopes``), in %.  Operations and
bytes come from the configuration's shapes (the family's
``flops_by_scope`` and ``bytes_by_scope``), each a lower bound of what
the chip must do; bytes at the window's mean frames per detect call,
detected frames over ``jit_infer`` executions.  The convs run in float32
at ``Precision.HIGHEST``, so this reads low by design.  Which bound
binds goes into the result line's ``notes``."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_detected"]:
        return None
    seconds = tr.scopes.get("backbone")
    runs = tr.programs.get("jit_infer")
    fam, cfg = ctx["family"], ctx["config"]
    flops = fam.flops_by_scope(cfg).get("backbone")
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
               ["backbone"] / (chips * peaks["hbm_bytes_per_s"]))
    bound = "operations" if t_flops >= t_bytes else "HBM bytes"
    ctx["notes"]["backbone_roofline"] = (
        f"bound by {bound}: {t_flops} s of operations, {t_bytes} s of "
        f"bytes, {seconds} s on the device")
    return 100.0 * max(t_flops, t_bytes) / seconds
