"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's
own ``ProfileData``. Device events are the operations on each accelerator
plane (``/device:TPU:<n>``), on its ``XLA Ops`` line; the window is the
host span ``bench.traced_window`` that the harness opens around the traced
slice. All times are seconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# control-flow operations enclose the operations of their bodies, which
# the trace lists as well; they are left out of busy time, gaps and totals
CONTAINERS = ("while", "conditional", "call")

# an operand as the trace's instruction text prints it: ``shape{layout}
# %name``; memory space 1 (``S(1)``) in the layout is the core's VMEM
_OPERAND = re.compile(r"\w+\[[^\]]*\]\{([^}]*)\} %([\w.\-]+)")


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO instruction's name (``%copy.131 = ...`` -> ``copy.131``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def is_container(self) -> bool:
        return self.op.split(".", 1)[0] in CONTAINERS


@dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[str, List[Event]]          # per device plane, sorted by start
    modules: Dict[str, List[Event]]
    host: List[Event]                    # every host thread's events

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def all_ops(self) -> List[Event]:
        return [e for evs in self.ops.values() for e in evs]

    def leaf_ops(self) -> Dict[str, List[Event]]:
        """Each device's operations without the control-flow containers."""
        return {p: [e for e in evs if not e.is_container]
                for p, evs in self.ops.items()}


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        s = e.start_ns * 1e-9
        out.append(Event(e.name, s, s + e.duration_ns * 1e-9))
    out.sort(key=lambda e: e.start)
    return out


def load(trace_dir: str) -> Optional[Trace]:
    """The trace under ``trace_dir``; None when no device plane is in it
    (a host-only run)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, host, window = {}, {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line):
                    if e.name == WINDOW_SPAN:
                        window = (e.start, e.end)
                    host.append(e)
    if not ops or window is None:
        return None
    return Trace(window, ops, modules, host)


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = tr.window
    per = [union([(e.start, e.end) for e in evs], lo, hi)
           for evs in tr.leaf_ops().values()]
    return sum(per) / len(per)


def gaps(tr: Trace, min_s: float = 0.0) -> List[Tuple[float, float]]:
    """Idle intervals of the first device inside the window, longest
    first."""
    lo, hi = tr.window
    evs = next(iter(tr.leaf_ops().values()))
    out, t = [], lo
    for e in evs:
        if e.start > t:
            out.append((t, min(e.start, hi)))
        t = max(t, e.end)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    out = [(s, e) for s, e in out if e - s > min_s]
    return sorted(out, key=lambda g: g[0] - g[1])


def host_label(tr: Trace, start: float, end: float) -> str:
    """What the host was doing in ``[start, end]``: the shortest host event
    that covers at least half of it (the most specific activity), else the
    one that overlaps it most; the traced-window span itself excepted."""
    cover, best, best_ov = None, "none", 0.0
    for e in tr.host:
        if e.name == WINDOW_SPAN or e.name.startswith("ThreadpoolListener"):
            continue
        ov = min(e.end, end) - max(e.start, start)
        if ov >= 0.5 * (end - start) and (cover is None
                                          or e.dur < cover.dur):
            cover = e
        if ov > best_ov:
            best, best_ov = e.name, ov
    return (cover.name if cover is not None else best)[:80]


def in_window(tr: Trace, evs: List[Event]) -> List[Event]:
    lo, hi = tr.window
    return [e for e in evs if e.start >= lo and e.end <= hi]


def kernel_events(tr: Trace, kernel: str) -> List[Event]:
    """Operations of a Pallas kernel (matched by its ``name=``) inside the
    window, on every device."""
    return [e for e in in_window(tr, tr.all_ops()) if _is(e, kernel)]


def staged_operands(e: Event) -> List[str]:
    """The operands of an instruction that the compiler placed in VMEM
    (``S(1)``): their producers read them from HBM, not the instruction."""
    args = e.name.split(" = ", 1)[-1]
    return [name for layout, name in _OPERAND.findall(args)
            if "S(1)" in layout]


def kernel_calls(tr: Trace, kernel: str) -> List[Tuple[Event, List[Event]]]:
    """Each call of ``kernel`` inside the window with the operations that
    staged its VMEM operands: for each staged operand that the trace times,
    its last execution before the call and after the previous one. A call
    for which one is not found so (as at the window's start) is left out."""
    lo, hi = tr.window
    out = []
    for evs in tr.ops.values():
        runs: Dict[str, List[Event]] = {}
        for e in evs:
            runs.setdefault(e.op, []).append(e)
        ends = {op: [e.end for e in rs] for op, rs in runs.items()}
        prev = lo
        for k in evs:
            if not _is(k, kernel):
                continue
            start_after, prev = prev, k.end
            if k.start < lo or k.end > hi:
                continue
            staged = []
            for op in staged_operands(k):
                if op not in runs:          # no time of its own: a bitcast
                    continue
                i = bisect.bisect_right(ends[op], k.start) - 1
                if i < 0 or runs[op][i].start < start_after:
                    staged = None
                    break
                staged.append(runs[op][i])
            if staged is not None:
                out.append((k, staged))
    return out


def _is(e: Event, kernel: str) -> bool:
    """A Pallas call's HLO instruction is named after the kernel's
    ``name=``: ``paged_attention``, ``paged_attention.3``."""
    op = e.op
    return op == kernel or (op.startswith(kernel + ".")
                            and op[len(kernel) + 1:].isdigit())


def top_ops(tr: Trace, n: int = 10) -> List[List[object]]:
    """The operations that took most device time (control-flow operations,
    which only enclose others, left out), seconds per device."""
    tot: Dict[str, float] = {}
    for e in in_window(tr, tr.all_ops()):
        if not e.is_container:
            tot[e.op] = tot.get(e.op, 0.0) + e.dur
    return [[k, v / len(tr.ops)]
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(tr: Trace, n: int = 10) -> List[List[object]]:
    return [[host_label(tr, s, e), e - s] for s, e in gaps(tr)[:n]]


def modules_with(tr: Trace, kernel: str) -> List[Event]:
    """Module executions (whole jitted programs) inside the window that
    contain at least one operation of ``kernel``."""
    out = []
    for plane, mods in tr.modules.items():
        ks = [e for e in tr.ops.get(plane, ()) if _is(e, kernel)]
        j = 0
        for m in in_window(tr, mods):
            while j < len(ks) and ks[j].start < m.start:
                j += 1
            if j < len(ks) and ks[j].end <= m.end:
                out.append(m)
    return out
