"""The reduction from a JAX profiler trace to device intervals.

A traced run writes one ``.xplane.pb``. Its device planes are named
``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event per
operation that ran (start and duration in ns on the profiler's clock) and
the line ``XLA Modules`` one event per program. The host plane holds the
benchmark's own ``jax.profiler.TraceAnnotation`` events (names starting with
``bench.``), which mark the measured window and each campaign in it.

Everything a per-layer metric reads from the device comes through here:
busy time (the union of op intervals), time per op or module name, and the
idle gaps between busy intervals.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]                 # [start, end) in ns
Event = Tuple[int, int, str]               # start, end, name

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."


@dataclass
class Trace:
    """The events of one trace that the benchmark reads."""
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    annotations: List[Event] = field(default_factory=list)

    @property
    def devices(self) -> List[int]:
        return sorted(set(self.ops) | set(self.modules))

    def annotation(self, name: str) -> Optional[Event]:
        """The first annotation of that name (the window is traced once)."""
        for event in self.annotations:
            if event[2] == name:
                return event
        return None


def op_name(event_name: str) -> str:
    """An op event of a TPU trace is named by its whole HLO instruction
    (``%fusion.3 = f32[...] fusion(...)``); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n) -> List[Event]:
    return sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                   name(e.name)) for e in line.events)


def load(path: str) -> Trace:
    """Read the device ops and modules and the benchmark's annotations."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if match and line.name == OPS_LINE:
                trace.ops[int(match.group(1))] = _events(line, op_name)
            elif match and line.name == MODULES_LINE:
                trace.modules[int(match.group(1))] = _events(line)
            elif not match:
                trace.annotations += [
                    e for e in _events(line)
                    if e[2].startswith(ANNOTATION_PREFIX)]
    trace.annotations.sort()
    return trace


def union(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The sorted, disjoint union of ``intervals`` clipped to [lo, hi)."""
    out: List[List[int]] = []
    for start, end in sorted((max(s, lo), min(e, hi))
                             for s, e in intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def device_intervals(trace: Trace, device: int) -> List[Interval]:
    """What ran on one device: its ops, or its modules where the trace
    has no op line."""
    events = trace.ops.get(device) or trace.modules.get(device) or []
    return [(s, e) for s, e, _ in events]


def busy_ns(trace: Trace, device: int, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(device_intervals(trace, device),
                                       lo, hi))


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) around a disjoint sorted ``busy``."""
    out, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def time_ns(events: Sequence[Event], match: Callable[[str], bool],
            lo: int, hi: int) -> int:
    """Summed duration, clipped to [lo, hi), of the events whose name
    ``match`` accepts (events of one kind do not overlap on a device)."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e, name in events
               if match(name))


def enclosing(events: Sequence[Event], t: int) -> List[str]:
    """Names of the events that contain instant ``t``, outermost first."""
    hits = [(s, -(e - s), name) for s, e, name in events if s <= t < e]
    return [name for _, _, name in sorted(hits)]
