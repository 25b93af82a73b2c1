"""Reduce a JAX profiler trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one
event per device operation (named by its HLO text, ``%fusion.1 = ...``)
and the ``XLA Modules`` line one per executed program, named after its
jitted function (``jit__halo_sweeps(42)``).  The benchmark's own
``TraceAnnotation`` spans are host events; the one named ``window`` marks
the measured window on the trace's clock.  The line that holds it is the
Python threads' line, where JAX also records what Python asked of it
(``np.asarray(jax.Array)``, ``PjitFunction(_apply_A)``, ``shard_args``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class TraceSummary:
    window_s: float  # the measured window on the trace's clock
    busy_s: float  # union of device-op intervals, mean over the chips used
    kernels: Dict[str, Tuple[float, int]]  # program -> (device s, calls)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)  # by label

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load_events(logdir: str) -> List[Event]:
    """Every event of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def program_name(module_event: str) -> str:
    """``jit__halo_sweeps(42)`` -> ``_halo_sweeps``."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[len("jit_"):] if name.startswith("jit_") else name


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(op_event: str) -> str:
    """``%fusion.1 = f32[8]{0} fusion(...)`` -> ``fusion.1``."""
    return op_event.split(" = ", 1)[0].lstrip("%")


def summarize(events: Sequence[Event], labels: Sequence[str] = ("solve",),
              top: int = 10) -> Optional[TraceSummary]:
    """Busy time, kernel time and idle time of the window.

    Returns None when the trace holds no device operation or no window
    annotation (a CPU run, or a trace that was cut).  ``top_ops`` sums
    device time by ``program/op``.  ``idle_gaps`` sums the idle time of
    the first chip by what the Python threads were doing: the event of
    their line that overlaps a gap most, other than the benchmark's
    annotations, else the innermost annotation in ``labels``, else
    ``window``.
    """
    win = [e for e in events if e.name == WINDOW
           and not DEVICE_PLANE.match(e.plane)]
    ops: Dict[str, List[Tuple[float, float]]] = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE:
            ops.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    if not win or not ops:
        return None
    lo, hi = win[0].start_ns, win[0].end_ns
    busy = {p: union((max(s, lo), min(e, hi)) for s, e in iv if e > lo
                     and s < hi) for p, iv in ops.items()}
    busy_ns = sum(e - s for iv in busy.values() for s, e in iv) / len(busy)

    modules = sorted((e for e in events if DEVICE_PLANE.match(e.plane)
                      and e.line == MODULES_LINE), key=lambda e: e.start_ns)
    starts = [m.start_ns for m in modules]
    kernels: Dict[str, Tuple[float, int]] = {}
    for m in modules:
        s, n = kernels.get(program_name(m.name), (0.0, 0))
        kernels[program_name(m.name)] = (s + m.dur_ns * 1e-9, n + 1)
    op_time: Dict[str, float] = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = (program_name(modules[i].name) if i >= 0
                    and modules[i].end_ns >= e.start_ns else "?")
            key = f"{prog}/{op_name(e.name)}"
            op_time[key] = op_time.get(key, 0.0) + e.dur_ns * 1e-9

    python = [e for e in events if (e.plane, e.line)
              == (win[0].plane, win[0].line)]
    spans = sorted((e for e in python if e.name in labels),
                   key=lambda e: e.dur_ns)  # innermost first
    doing = sorted((e for e in python if e.name not in labels
                    and e.name != WINDOW), key=lambda e: e.start_ns)
    doing_starts = [h.start_ns for h in doing]
    longest = max((h.dur_ns for h in doing), default=0.0)

    def label(s: float, e: float) -> str:
        near = doing[bisect.bisect_left(doing_starts, s - longest):
                     bisect.bisect_left(doing_starts, e)]
        over = [(min(e, h.end_ns) - max(s, h.start_ns), h.name)
                for h in near if h.end_ns > s]
        if over:
            return max(over)[1]
        mid = (s + e) / 2
        return next((a.name for a in spans
                     if a.start_ns <= mid <= a.end_ns), WINDOW)

    idle: Dict[str, float] = {}
    for s, e in gaps(busy[sorted(busy)[0]], lo, hi):
        key = label(s, e)
        idle[key] = idle.get(key, 0.0) + (e - s) * 1e-9
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9, kernels=kernels,
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top])
