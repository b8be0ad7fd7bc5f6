"""Detector program: device seconds of the detect program (layer
``detector program`` of ``bench/layers.json``) in the traced window, in
ms per frame detected there (device trace)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_detected"]:
        return None
    s = tr.layers.get("detector program")
    if not s:
        return None
    return s * 1e3 / ctx["trace_detected"]
