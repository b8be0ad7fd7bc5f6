"""Detector program, its ``nms`` scope: device seconds of the ops under
the ``nms`` named scope of the detect program, each its own time
(``Summary.scopes``), in the traced window, in ms per frame detected
there (device trace)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_detected"]:
        return None
    s = tr.scopes.get("nms")
    if not s:
        return None
    return s * 1e3 / ctx["trace_detected"]
