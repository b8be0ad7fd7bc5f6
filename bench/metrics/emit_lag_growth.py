"""Emit and tracker path, above the knee: how fast the emits fall behind
the frames' due times, in ms of emit latency gained per second of the
window (host clock).  Frame ``i`` of the due-ordered window is due at
``i / (cameras * fps)`` (``generator.due_times``); the reading is the
median emit latency of the last third of the frames less that of the
first third, over the due seconds between the thirds' medians.  On a
traced run only the frames emitted before the trace began count, so the
profiler's own cost stays out.  A host loop that keeps up reads about 0;
one that does more work per emit period than the period lasts reads the
share by which it overruns the wall clock, in ms per s, and stretches
the span over which ``detected_fps`` counts.  Nothing is read where a
frame went unaccounted (``failed``) or too few frames remain."""
import numpy as np

from bench.stats import percentile


def read(ctx):
    lat = np.asarray(ctx["emit_ms"], np.float64)
    if len(lat) != ctx["frames"]:
        return None
    mix = ctx["mix"]
    due = np.arange(len(lat)) * 1e3 / (mix.cameras * mix.fps)
    tr = ctx["trace"]
    if tr is not None:
        keep = due + lat < (ctx["seconds"] - tr.window_s) * 1e3
        lat, due = lat[keep], due[keep]
    if len(lat) < 3:
        return None
    first, _, last = np.array_split(np.arange(len(lat)), 3)
    dt_s = (np.median(due[last]) - np.median(due[first])) / 1e3
    if dt_s <= 0:
        return None
    return (percentile(lat[last], 50) - percentile(lat[first], 50)) / dt_s
