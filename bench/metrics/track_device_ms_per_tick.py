"""Tracker tick: device seconds of the tracker programs (layer
``tracker tick`` of ``bench/layers.json``) in the traced window, in ms
per emit boundary there; every boundary runs one tick (device trace)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_boundaries"]:
        return None
    s = tr.layers.get("tracker tick")
    if not s:
        return None
    return s * 1e3 / ctx["trace_boundaries"]
