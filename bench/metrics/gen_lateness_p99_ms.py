"""Load generator: 99th percentile of how late frames were ingested
past their due time, over every frame of the window (host clock)."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx["lateness_ms"], 99)
