"""Whole step: analytic FLOPs per frame (the family's
``flops_per_frame``) times the detected frame rate, over the chips' bf16
peak (``bench/peaks.json``), in %.  The convs run in float32 at ``Precision.HIGHEST``, so this reads
low by design.  A device missing from the peak table is an error."""


def read(ctx):
    peaks = ctx["peaks"]
    if peaks is None:
        raise KeyError(f"no peaks for {ctx['device_kind']!r} in "
                       "bench/peaks.json")
    return (100.0 * ctx["flops_per_frame"] * ctx["detected_fps"]
            / (ctx["chips"] * peaks["bf16_flops_per_s"]))
