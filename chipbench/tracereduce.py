"""From a profiler trace to device times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; everything after works on those, so a test can feed a small
recorded trace (``tests/data/trace_small.json``).  Device planes are the
``/device:TPU:<n>`` ones; on them the ``XLA Ops`` line holds every
operation that ran and the ``XLA Modules`` line one event per execution of
a compiled program (``jit_<name>(<id>)``).  Host planes (``/host:CPU``)
hold what the host threads were doing.

Busy time is the union of the operation intervals, so overlapping
operations count once; the idle share is one minus busy over the window.
The window runs from the first to the last device event of the trace:
the device's recording starts some tens of milliseconds after the host
asks for it.  An operation is named ``<program>/<instruction>`` after the
program execution it falls in; its full HLO text stays searchable.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_NAME = re.compile(r"^(?:jit_)?(.+?)(?:\(\d+\))?$")
_CONTAINER = re.compile(r"^\S+ = .*? (?:while|conditional|call)\(")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:") or
                plane.name.startswith("/host:CPU")):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith("/device:") and e.line == OPS_LINE})


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(ivs, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over device planes
    modules: Dict[str, Tuple[int, float]]   # program -> (count, device s)
    ops: Dict[str, float]                   # program/instruction -> device s
    text: Dict[str, str]                    # program/instruction -> HLO text
    gaps: List[Tuple[str, float]]           # longest idle gaps, host cause

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module(self, name: str) -> Tuple[int, float]:
        return self.modules.get(name, (0, 0.0))

    def op_seconds(self, pattern: str, program: Optional[str] = None) -> float:
        """Device seconds of the operations whose HLO text matches
        ``pattern``, within executions of ``program`` if given."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.ops.items()
                   if (program is None or n.split("/")[0] == program)
                   and rx.search(self.text[n]))


def module_name(event_name: str) -> str:
    """``jit__prefill_fn(123)`` -> ``_prefill_fn``."""
    return _MODULE_NAME.match(event_name).group(1)


def op_label(program: str, event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` in ``_decode_fn`` ->
    ``_decode_fn/fusion.12``."""
    return f"{program}/{event_name.split(' = ')[0].lstrip('%')}"


def reduce(events: List[Event], window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Reduced:
    """``window`` is (start, end) in the trace's nanoseconds; by default it
    spans the device events."""
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace has no device operations")
    dev = [e for e in events if e.plane in planes]
    if window is None:
        window = (min(e.start_ns for e in dev), max(e.end_ns for e in dev))
    lo, hi = window
    busy, ops_s, text, modules = 0.0, {}, {}, {}
    first_busy = None
    for plane in planes:
        ivs = union(_clip([(e.start_ns, e.end_ns) for e in dev
                           if e.plane == plane and e.line == OPS_LINE],
                          lo, hi))
        busy += sum(b - a for a, b in ivs)
        if first_busy is None:
            first_busy = ivs
    spans = {p: sorted((e.start_ns, e.end_ns, module_name(e.name))
                       for e in dev if e.plane == p and e.line == MODULES_LINE)
             for p in planes}
    for e in sorted(dev, key=lambda e: e.start_ns):
        if not (lo <= e.start_ns < hi):
            continue
        if e.line == OPS_LINE:
            if _CONTAINER.search(e.name):
                continue            # its body's operations are listed too
            label = op_label(_program_at(spans[e.plane], e.start_ns), e.name)
            ops_s[label] = ops_s.get(label, 0.0) + e.dur_ns * 1e-9
            text.setdefault(label, e.name)
        elif e.line == MODULES_LINE:
            n, s = modules.get(module_name(e.name), (0, 0.0))
            modules[module_name(e.name)] = (n + 1, s + e.dur_ns * 1e-9)
    gaps = _gaps(first_busy, lo, hi,
                 [e for e in events if e.plane.startswith("/host:")], top)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / len(planes),
                   modules=modules, ops=ops_s, text=text, gaps=gaps)


def _program_at(spans, t: float) -> str:
    """The program whose execution covers time t ("" if none)."""
    import bisect
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][0] <= t < spans[i][1]:
        return spans[i][2]
    return ""


def _gaps(busy, lo, hi, host: List[Event], top: int):
    """The longest idle stretches of the first device, each named after the
    host event that overlaps it most (the innermost on a tie)."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    holes.sort(key=lambda h: h[0] - h[1])
    out = []
    for a, b in holes[:top]:
        best, best_overlap, best_dur = "no host event", 0.0, float("inf")
        for e in host:
            ov = min(b, e.end_ns) - max(a, e.start_ns)
            if ov > best_overlap or (ov == best_overlap and ov > 0
                                     and e.dur_ns < best_dur):
                best, best_overlap, best_dur = e.name, ov, e.dur_ns
        out.append((best, (b - a) * 1e-9))
    return out


def breakdown(r: Reduced, top: int = 10) -> dict:
    ops = sorted(r.ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in r.gaps[:top]]}
