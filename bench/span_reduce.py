"""The program's own spans in a profiler trace, beside the harness's.

The serving path opens ``serve.*`` spans (``repro.obs.spans``) on the
profiler's host plane, each with integer args (frames, padding, drops,
bytes).  ``trace_reduce`` keeps only the harness's ``bench.*`` spans,
as plain events; this module reads both kinds with their args and
reduces them:

* ``totals``: per span name inside a window, the count, the clipped
  seconds and the sum of each numeric arg;
* ``name_gap`` / ``idle_gaps``: a device's idle gap named by its
  innermost covering span, the shortest one that covers at least half
  of the gap (where none does, the name with the most overlap, which
  is ``trace_reduce.idle_gaps``'s rule);
* ``inside``: how much of a set of events lies inside a set of spans
  (are the spans and the device ops on one clock; do the inner spans
  account for an outer one);
* ``per_layer``: the host-path numbers these spans give, each
  normalised by its own spans' args.

``run.py`` does not call this module: its result line reads
``trace_reduce.summarize`` alone.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

Span = Tuple[str, float, float, Dict[str, int]]  # name, start_s, end_s, args

PREFIXES = ("serve.", "bench.")


def load(logdir: str) -> List[Span]:
    """The ``serve.*`` and ``bench.*`` host spans of the newest xplane
    file under ``logdir``, with their integer stats as args."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: List[Span] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    args = {k: int(v) for k, v in e.stats
                            if isinstance(v, int)}
                    a = e.start_ns * 1e-9
                    out.append((e.name, a, a + e.duration_ns * 1e-9, args))
    return out


@dataclass
class Total:
    count: int = 0
    seconds: float = 0.0
    args: Dict[str, int] = field(default_factory=dict)


def totals(spans: Sequence[Span], lo: float, hi: float) -> Dict[str, Total]:
    """Per span name: the spans that overlap ``[lo, hi]``, their seconds
    clipped to it and the sums of their args."""
    out: Dict[str, Total] = {}
    for name, a, b, args in spans:
        if b <= lo or a >= hi:
            continue
        t = out.setdefault(name, Total())
        t.count += 1
        t.seconds += min(b, hi) - max(a, lo)
        for k, v in args.items():
            t.args[k] = t.args.get(k, 0) + v
    return out


def name_gap(a: float, b: float, spans: Sequence[Span]) -> str:
    """The innermost span over the gap ``[a, b]``: the shortest one
    that covers at least half of it; else the name with the most
    overlap; ``"none"`` where no span overlaps."""
    half = (b - a) / 2
    best, best_len = None, float("inf")
    cover: Dict[str, float] = {}
    for name, sa, sb, _ in spans:
        if name == trace_reduce.WINDOW_SPAN:
            continue
        ov = min(b, sb) - max(a, sa)
        if ov <= 0:
            continue
        cover[name] = cover.get(name, 0.0) + ov
        if ov >= half and sb - sa < best_len:
            best, best_len = name, sb - sa
    if best is not None:
        return best
    return max(cover, key=cover.get) if cover else "none"


def gaps(tr: trace_reduce.Trace, lo: float, hi: float):
    """Every idle gap ``(start, end)`` of every device in ``[lo, hi]``."""
    out = []
    for evs in tr.ops.values():
        t = lo
        for a, b in trace_reduce.union(
                [(a, b) for _, a, b in trace_reduce.clip(evs, lo, hi)]):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if hi > t:
            out.append((t, hi))
    return out


def idle_gaps(tr: trace_reduce.Trace, spans: Sequence[Span], lo: float,
              hi: float, k: int = 10):
    """The ``k`` longest idle gaps, ``[span, seconds]``, each named by
    ``name_gap``."""
    longest = sorted(gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [[name_gap(a, b, spans), b - a] for a, b in longest]


def inside(events: Sequence[Tuple[float, float]],
           spans: Sequence[Tuple[float, float]]) -> float:
    """Seconds of the union of ``events`` that lie inside the union of
    ``spans``."""
    ev = trace_reduce.union(events)
    sp = trace_reduce.union(spans)
    s, j = 0.0, 0
    for a, b in ev:
        while j < len(sp) and sp[j][1] <= a:
            j += 1
        i = j
        while i < len(sp) and sp[i][0] < b:
            s += min(b, sp[i][1]) - max(a, sp[i][0])
            i += 1
    return s


def per_layer(tot: Dict[str, Total]) -> Dict[str, Optional[float]]:
    """The host-path numbers of a window's span totals; a number whose
    spans are absent reads None.  Per frame means per frame that the
    ``serve.detect`` spans count; per boundary, per span counted."""
    det = tot.get("serve.detect")
    frames = det.args.get("frames", 0) if det else 0

    def per_frame(name):
        t = tot.get(name)
        return t.seconds * 1e3 / frames if t and frames else None

    def per_call(name):
        t = tot.get(name)
        return t.seconds * 1e3 / t.count if t and t.count else None

    h2d = sum(t.args.get("h2d_bytes", 0) for n, t in tot.items()
              if n.startswith("serve."))
    return {
        "detect_call_ms_per_frame": per_frame("serve.detect"),
        "detect_put_ms_per_frame": per_frame("serve.detect.put"),
        "detect_pull_ms_per_frame": per_frame("serve.detect.pull"),
        "frames_per_detect_call": (frames / det.count
                                   if det and det.count else None),
        "h2d_bytes_per_frame": h2d / frames if frames else None,
        "track_host_ms_per_boundary": per_call("serve.track"),
        "report_ms_per_boundary": per_call("serve.report"),
    }
