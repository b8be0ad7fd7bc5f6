"""From a profiler trace to device busy time, per-layer device time
and idle gaps attributed to the harness's host spans.

``load(logdir)`` reads the newest ``*.xplane.pb`` under a
``jax.profiler`` log directory into plain tuples; everything after that
works on those tuples, so the reduction is tested on hand-built traces.

* Device events are the ``XLA Ops`` line of every ``/device:`` plane:
  busy time is the union of their intervals inside the traced window,
  averaged over the devices.
* Program events are the ``XLA Modules`` line: their durations and
  execution count per XLA module name (``programs``), and summed by the
  layer whose name pattern (``bench/layers.json``) matches (``layers``).
* Scopes: the device time of a program's ops by the top-level named
  scope of each op.  Ops nest (a ``while`` or ``cond`` holds the ops of
  its body), so each op counts only its own time, the part of its
  interval that no op nested in it covers (``exclusive``): no time is
  counted twice, and the loop's own control counts to the loop's scope.
  An op event carries only its HLO name, so the scope comes from the
  compiled program's text (``parse_hlo``): its ``op_name`` metadata, or
  that of the ``while``, ``cond`` or call that runs its computation, or
  where neither names one (an op XLA made without metadata) that of the
  ops that use its result or, failing them, that make its operands.
  Where a program was compiled at several shapes, each execution is
  read against the text that names the most of its ops.
* Host spans are the harness's ``TraceAnnotation`` events (names that
  start with ``bench.``) on the host plane; ``bench.window`` bounds the
  traced window.  Every idle gap of a device is named by the span that
  covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

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


class Runs(NamedTuple):
    seconds: float      # device seconds, averaged over the devices
    count: int          # executions, summed over the devices


def module_name(event: str) -> str:
    """An ``XLA Modules`` event's name without its ``(program id)``."""
    return re.sub(r"\(\d+\)$", "", event)


def program_runs(tr: Trace, lo: float, hi: float) -> Dict[str, Runs]:
    """Device seconds and executions of each XLA module in ``[lo, hi]``."""
    n = max(len(tr.modules), 1)
    sec: Dict[str, float] = {}
    cnt: Dict[str, int] = {}
    for evs in tr.modules.values():
        for name, a, b in clip(evs, lo, hi):
            m = module_name(name)
            sec[m] = sec.get(m, 0.0) + (b - a) / n
            cnt[m] = cnt.get(m, 0) + 1
    return {m: Runs(sec[m], cnt[m]) for m in sec}


def layer_of(name: str, table: Dict[str, List[str]]) -> Optional[str]:
    for layer, patterns in table.items():
        if layer.startswith("_"):
            continue
        if any(re.search(p, name) for p in patterns):
            return layer
    return None


def layer_seconds(programs: Dict[str, Runs],
                  table: Dict[str, List[str]]) -> Dict[str, float]:
    """Device seconds of each layer's programs (``program_runs``);
    layers with no program are absent."""
    out: Dict[str, float] = {}
    for name, runs in programs.items():
        layer = layer_of(name, table)
        if layer is not None:
            out[layer] = out.get(layer, 0.0) + runs.seconds
    return out


@dataclass
class Hlo:
    """One compiled program: its module name and the top-level named
    scope of each instruction (None outside every scope)."""
    module: str
    scope: Dict[str, Optional[str]]


_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_INHERIT = object()


def _own_scope(line: str):
    """The scope an instruction's ``op_name`` names: the first part after
    the leading ``jit(...)`` parts, where an op follows it.  An op_name
    that does not start at a ``jit(...)`` is relative to the op that
    runs its computation, as is an instruction with none."""
    m = _OP_NAME.search(line)
    if m is None or not m.group(1).startswith("jit("):
        return _INHERIT
    parts = m.group(1).split("/")
    while parts and parts[0].startswith("jit("):
        parts.pop(0)
    return parts[0] if len(parts) > 1 else None


_REF = re.compile(r"%([\w.\-]+)")


def parse_hlo(text: str) -> Hlo:
    """Instruction name -> top-level named scope, from a compiled
    program's HLO text (``compiled.as_text()``)."""
    module, comp = "", None
    own: Dict[str, object] = {}
    comp_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    refs: Dict[str, List[str]] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line.endswith("{") and not line[:1].isspace():
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
        else:
            m = _INSTR.match(line)
            if m is None or comp is None:
                continue
            name = m.group(1)
            own[name] = _own_scope(line)
            comp_of[name] = comp
            refs[name] = _REF.findall(line[m.end():])
            called = _CALLS.findall(line)
            for br in _BRANCHES.findall(line):
                called += [c.strip().lstrip("%") for c in br.split(",")]
            for c in called:
                caller.setdefault(c, name)
    scope: Dict[str, Optional[str]] = {}

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        if name not in scope:
            s = own[name]
            if s is _INHERIT:
                up = caller.get(comp_of[name])
                s = resolve(up, depth + 1) if up and depth < 64 else None
            scope[name] = s
        return scope[name]

    for name in own:
        resolve(name)
    # an op outside every scope by its metadata takes the scope of the
    # nearest op that uses its result, else of one that makes an operand
    operands = {n: [r for r in rs if comp_of.get(r) == comp_of[n]]
                for n, rs in refs.items()}
    users: Dict[str, List[str]] = {}
    for n, rs in operands.items():
        for r in rs:
            users.setdefault(r, []).append(n)
    for edges in (users, operands):
        todo = [n for n in own if scope[n] is None]
        while todo:
            left = []
            for n in todo:
                got = [scope[m] for m in edges.get(n, ()) if scope[m]]
                if got:
                    scope[n] = got[0]
                else:
                    left.append(n)
            if len(left) == len(todo):
                break
            todo = left
    return Hlo(module, scope)


def exclusive(events: Sequence[Event]) -> List[Event]:
    """Where events nest (the ops of a loop's body inside the
    ``while``), each event's own time: the pieces of its interval that
    no event nested in it covers, under its name.  The pieces never
    overlap and cover what the events cover.  An event that outlasts
    the one it starts in is cut at that one's end."""
    out: List[Event] = []
    stack: List[list] = []          # [name, covered up to, end]

    def close():
        name, t, end = stack.pop()
        if end > t:
            out.append((name, t, end))
        if stack:
            stack[-1][1] = max(stack[-1][1], end)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            top = stack[-1]
            if s > top[1]:
                out.append((top[0], top[1], s))
            top[1] = max(top[1], s)
            e = min(e, top[2])
        stack.append([name, s, e])
    while stack:
        close()
    return sorted(out, key=lambda ev: ev[1])


def scope_seconds(tr: Trace, programs: Sequence[Hlo], lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds of the ops of ``programs`` in ``[lo, hi]``, each
    its own time (``exclusive``), by top-level named scope, averaged
    over the devices.  An op's piece belongs to the execution that holds
    its midpoint and is clipped to it, so the scopes of a program never
    sum to more than its own device time."""
    out: Dict[str, float] = {}
    n = max(len(tr.modules), 1)
    for dev, evs in tr.modules.items():
        mods = sorted(evs, key=lambda e: e[1])
        starts = [e[1] for e in mods]
        runs: Dict[int, List[Event]] = {}
        for op, s, e in exclusive(tr.ops.get(dev, [])):
            i = bisect.bisect_right(starts, (s + e) / 2) - 1
            if i >= 0 and (s + e) / 2 <= mods[i][2]:
                runs.setdefault(i, []).append((op.lstrip("%"), s, e))
        for i, ops in runs.items():
            name, a, b = mods[i]
            texts = [h for h in programs if h.module == module_name(name)]
            a, b = max(a, lo), min(b, hi)
            if not texts or b <= a:
                continue
            names = {op for op, _, _ in ops}
            h = max(texts, key=lambda h: len(names & h.scope.keys()))
            for op, s, e in ops:
                sc = h.scope.get(op)
                s, e = max(s, a), min(e, b)
                if sc is not None and e > s:
                    out[sc] = out.get(sc, 0.0) + (e - s) / n
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: Optional[float]
    layers: Dict[str, float]
    device_ops: list
    idle_gaps: list
    programs: Dict[str, Runs]
    scopes: Dict[str, float]


def summarize(tr: Trace, table: Dict[str, List[str]],
              hlo: Sequence[str] = ()) -> Optional[Summary]:
    """The traced window's numbers; ``hlo``: compiled texts of the
    programs whose scopes to count."""
    w = window(tr)
    if w is None:
        return None
    lo, hi = w
    programs = program_runs(tr, lo, hi)
    return Summary(hi - lo, busy_seconds(tr, lo, hi),
                   layer_seconds(programs, table), top_ops(tr, lo, hi),
                   idle_gaps(tr, lo, hi), programs,
                   scope_seconds(tr, [parse_hlo(t) for t in hlo], lo, hi))
