"""Emit latency below the knee, its tail: 99th percentile of (emit -
due) over every frame of the window, detected and interpolated alike
(host clock), in ms."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx["emit_ms"], 99)
