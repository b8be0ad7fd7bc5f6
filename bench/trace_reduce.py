"""From a profiler trace to device busy time, per-layer device time
and idle gaps attributed to the harness's host spans.

``load(logdir)`` reads the newest ``*.xplane.pb`` under a
``jax.profiler`` log directory into plain tuples; everything after that
works on those tuples, so the reduction is tested on hand-built traces.

* Device events are the ``XLA Ops`` line of every ``/device:`` plane:
  busy time is the union of their intervals inside the traced window,
  averaged over the devices.
* Program events are the ``XLA Modules`` line: their durations, summed
  by the layer whose name pattern (``bench/layers.json``) matches.
* Host spans are the harness's ``TraceAnnotation`` events (names that
  start with ``bench.``) on the host plane; ``bench.window`` bounds the
  traced window.  Every idle gap of a device is named by the span that
  covers most of it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_s, end_s)

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # device
    spans: List[Event] = field(default_factory=list)               # host


def load(logdir: str) -> Trace:
    """The newest xplane file under ``logdir``, as a ``Trace``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {"XLA Ops": tr.ops,
                        "XLA Modules": tr.modules}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        _events(line.events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend(e for e in _events(line.events)
                                if e[0].startswith(SPAN_PREFIX))
    return tr


def _events(events) -> List[Event]:
    # an XLA op's event name is its whole HLO line: keep the op's name
    return [(e.name.split(" = ")[0], e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in events]


def window(tr: Trace) -> Optional[Tuple[float, float]]:
    """Start and end of the traced window (the ``bench.window`` span)."""
    w = [e for e in tr.spans if e[0] == WINDOW_SPAN]
    if not w:
        return None
    return min(e[1] for e in w), max(e[2] for e in w)


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def busy_seconds(tr: Trace, lo: float, hi: float) -> Optional[float]:
    """Union of device-op intervals in ``[lo, hi]``, averaged over the
    devices; None when the trace holds no device."""
    if not tr.ops:
        return None
    per = [sum(b - a for a, b in union([(a, b) for _, a, b in
                                        clip(evs, lo, hi)]))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def layer_of(name: str, table: Dict[str, List[str]]) -> Optional[str]:
    for layer, patterns in table.items():
        if layer.startswith("_"):
            continue
        if any(re.search(p, name) for p in patterns):
            return layer
    return None


def layer_seconds(tr: Trace, table: Dict[str, List[str]], lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds of the programs of each layer in ``[lo, hi]``,
    averaged over the devices; layers with no program are absent."""
    out: Dict[str, float] = {}
    n = max(len(tr.modules), 1)
    for evs in tr.modules.values():
        for name, a, b in clip(evs, lo, hi):
            layer = layer_of(name, table)
            if layer is not None:
                out[layer] = out.get(layer, 0.0) + (b - a) / n
    return out


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10):
    """The ``k`` device ops with the most time, ``[[name, seconds]]``,
    seconds averaged over the devices."""
    tot: Dict[str, float] = {}
    n = max(len(tr.ops), 1)
    for evs in tr.ops.values():
        for name, a, b in clip(evs, lo, hi):
            tot[name] = tot.get(name, 0.0) + (b - a) / n
    return [[name, s] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, lo: float, hi: float, k: int = 10):
    """The ``k`` longest idle gaps of any device in ``[lo, hi]``, each
    ``[span, seconds]`` named by the innermost harness span that covers
    most of the gap (``"none"`` where no span covers it)."""
    gaps = []
    for evs in tr.ops.values():
        t = lo
        for a, b in union([(a, b) for _, a, b in clip(evs, lo, hi)]):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
    spans = [e for e in tr.spans if e[0] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        cover = {}
        for name, sa, sb in spans:
            ov = min(b, sb) - max(a, sa)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        out.append([max(cover, key=cover.get) if cover else "none", b - a])
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: Optional[float]
    layers: Dict[str, float]
    device_ops: list
    idle_gaps: list


def summarize(tr: Trace, table: Dict[str, List[str]]) -> Optional[Summary]:
    w = window(tr)
    if w is None:
        return None
    lo, hi = w
    return Summary(hi - lo, busy_seconds(tr, lo, hi),
                   layer_seconds(tr, table, lo, hi), top_ops(tr, lo, hi),
                   idle_gaps(tr, lo, hi))
