"""What a metric reads: the measured window of one run.

Each metric is a reader of its own, ``bench/end_to_end/<name>.py`` or
``bench/layer_metrics/<name>.py``, whose ``read(window)`` returns one
number, or None where the window holds nothing for it to read; the harness
then leaves the metric out. The helpers below are the arithmetic the
readers share.
"""

from __future__ import annotations

import bisect
import importlib.util
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import trace as tr

HERE = Path(__file__).resolve().parent
READERS = {"end_to_end": HERE / "end_to_end",
           "per_layer": HERE / "layer_metrics"}


@dataclass
class Window:
    """The measured window of one run, as the readers see it."""
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    setup_s: float                 # host clock, process start to window
    seconds: float                 # host clock, start to last completion
    campaigns: int
    rounds: int                    # campaigns × rounds per campaign
    compiles: int                  # XLA compiles inside the window
    memory_peak_bytes: Optional[int] = None
    spans: List[Dict[str, Any]] = field(default_factory=list)
    trace: Optional[tr.Trace] = None
    devices: List[int] = field(default_factory=list)
    peak: Optional[Dict[str, float]] = None

    @property
    def bounds(self):
        """The window on the profiler's clock, or None without a trace."""
        if self.trace is None:
            return None
        event = self.trace.annotation("bench.window")
        return None if event is None else (event[0], event[1])


def read(kind: str, name: str, window: Window) -> Optional[float]:
    """Run the reader of the ``kind`` metric ``name`` on ``window``."""
    path = READERS[kind] / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(window)
    return None if value is None else float(value)


# ---- device time --------------------------------------------------------- #

def busy_ns(window: Window) -> Optional[List[int]]:
    """Busy ns of each device the run used, inside the window."""
    if window.bounds is None:
        return None
    lo, hi = window.bounds
    busy = [tr.busy_ns(window.trace, d, lo, hi) for d in window.devices
            if d in window.trace.devices]
    return busy if any(busy) else None


def op_ns(window: Window, match: Callable[[str], bool],
          modules: bool = False) -> Optional[int]:
    """Device ns, over every device used, of the ops (or programs) whose
    name ``match`` accepts; None where none ran."""
    if window.bounds is None:
        return None
    lo, hi = window.bounds
    lines = window.trace.modules if modules else window.trace.ops
    total = sum(tr.time_ns(lines.get(d, []), match, lo, hi)
                for d in window.devices)
    return total or None


def program_ns(window: Window, match: Callable[[str], bool]
               ) -> Optional[int]:
    """Device ns, over every device used, of the programs in which an op
    whose name ``match`` accepts ran: a kernel with the ops that stage its
    operands (a Pallas kernel may read them from fast on-chip memory that
    an op before it filled from HBM)."""
    if window.bounds is None:
        return None
    lo, hi = window.bounds
    total = 0
    for d in window.devices:
        ops = [s for s, _, name in window.trace.ops.get(d, [])
               if match(name)]
        for start, end, _ in window.trace.modules.get(d, []):
            i = bisect.bisect_left(ops, start)
            if i < len(ops) and ops[i] < end:
                total += max(0, min(end, hi) - max(start, lo))
    return total or None


def roofline_percent(least_s: float, device_ns: Optional[int]
                     ) -> Optional[float]:
    if not device_ns or least_s <= 0:
        return None
    return 100.0 * least_s / (device_ns * 1e-9)


# ---- program spans ------------------------------------------------------- #

def span_durations_ns(window: Window, name: str) -> List[int]:
    return [s["dur"] for s in window.spans if s.get("name") == name
            and s.get("ph") == "X"]


def self_ns(window: Window, names: Sequence[str]) -> Optional[int]:
    """Summed self time of the spans named ``names``: each one's duration
    less the part its direct children (same thread, one level deeper)
    cover."""
    by_thread: Dict[int, List[Dict[str, Any]]] = defaultdict(list)
    for s in window.spans:
        if s.get("ph") == "X":
            by_thread[s["tid"]].append(s)
    total, found = 0, False
    for spans in by_thread.values():
        for s in spans:
            if s["name"] not in names:
                continue
            found = True
            end = s["ts"] + s["dur"]
            children = sum(c["dur"] for c in spans
                           if c["depth"] == s["depth"] + 1
                           and s["ts"] <= c["ts"] and c["ts"] + c["dur"] <= end)
            total += s["dur"] - children
    return total if found else None


# ---- the breakdown of a traced run --------------------------------------- #

TOP = 10
LABELLED = 200      # the longest gaps labelled one by one


def _module_of_ops(modules: List[tr.Event], ops: List[tr.Event]
                   ) -> List[str]:
    """For each op (sorted by start), the name of the program it ran in."""
    names, i = [], 0
    for start, _, op in ops:
        while i < len(modules) and modules[i][1] <= start:
            i += 1
        inside = i < len(modules) and modules[i][0] <= start
        module = re.sub(r"\(\d+\)$", "", modules[i][2]) if inside else "?"
        names.append(f"{module}/{op}")
    return names


def breakdown(window: Window, mono_at_lo: int) -> Optional[Dict[str, Any]]:
    """The device ops that took the most time (over the devices used), and
    the idle time of the first device used, by what the host was doing: the
    program's spans open at each gap's middle, else the benchmark's own
    annotation around it. ``mono_at_lo`` is the program spans' clock
    (``time.monotonic_ns``) at the window's start."""
    if window.bounds is None:
        return None
    lo, hi = window.bounds
    op_time: Counter = Counter()
    for d in window.devices:
        ops = window.trace.ops.get(d, [])
        names = _module_of_ops(window.trace.modules.get(d, []), ops)
        for (s, e, _), name in zip(ops, names):
            op_time[name] += max(0, min(e, hi) - max(s, lo))
    first = window.devices[0] if window.devices else None
    busy = tr.union(tr.device_intervals(window.trace, first), lo, hi)
    spans = [(s["ts"] - mono_at_lo + lo, s["ts"] + s["dur"] - mono_at_lo + lo,
              s["name"]) for s in window.spans if s.get("ph") == "X"]
    idle: Counter = Counter()
    gaps = sorted(tr.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    if len(gaps) > LABELLED:
        shortest = gaps[LABELLED - 1][1] - gaps[LABELLED - 1][0]
        idle[f"gaps under {shortest * 1e-6:.3f} ms"] = sum(
            g1 - g0 for g0, g1 in gaps[LABELLED:])
    for g0, g1 in gaps[:LABELLED]:
        mid = (g0 + g1) // 2
        open_spans = sorted(set(tr.enclosing(spans, mid)))
        if open_spans:
            label = "+".join(open_spans)
        else:
            marks = tr.enclosing(window.trace.annotations, mid)
            label = {"bench.campaign": "campaign, outside program spans",
                     "bench.window": "between campaigns"}.get(
                         marks[-1] if marks else "", "unattributed")
        idle[label] += g1 - g0
    return {"device_ops": [[n, t * 1e-9] for n, t in op_time.most_common(TOP)
                           if t > 0],
            "idle_gaps": [[n, t * 1e-9] for n, t in idle.most_common(TOP)]}
