"""The program's own host spans in a profiler trace, reduced by name.

The engine's host loop writes its spans (``engine/...``, ``frontend/...``,
``pool/...``, ``sched/...``) into the profiler's trace as TraceMe events on
the engine thread, on the clock of the device's operations. A program that
writes none gives these functions nothing to read: they return empty
results, and the readers built on them None. All times are seconds on the
trace's clock.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

from bench.lib import trace

SYNC_WAIT = "engine/sync_wait"     # the host blocked on a decode window
PREFIXES = ("engine/", "frontend/", "pool/", "sched/")
NONE = "(none)"                    # time outside every program span


def program_spans(tr: trace.Trace) -> List[trace.Event]:
    return [e for e in tr.host if e.name.startswith(PREFIXES)]


def host_turns(tr: trace.Trace) -> List[Tuple[float, float]]:
    """The host's turn between decode windows: for each consecutive pair of
    ``engine/sync_wait`` spans wholly inside the traced window, the
    interval from the end of the first to the start of the second (pull,
    apply and deliver, poll, admit, lane upload, dispatch)."""
    waits = sorted(trace.in_window(tr, [e for e in tr.host
                                        if e.name == SYNC_WAIT]),
                   key=lambda e: e.start)
    return [(a.end, b.start) for a, b in zip(waits, waits[1:])]


def _segments(evs: List[trace.Event], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut at every span boundary, each piece with the name of
    the innermost (shortest) span covering it, or ``NONE``."""
    cuts = sorted({lo, hi} | {t for e in evs for t in (e.start, e.end)
                              if lo < t < hi})
    evs = sorted(evs, key=lambda e: e.start)
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(evs) and evs[i].start <= a:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e.end >= b]
        inner = min(active, key=lambda e: e.dur) if active else None
        out.append((a, b, inner.name if inner is not None else NONE))
    return out


def by_span(tr: trace.Trace, intervals) -> Dict[str, float]:
    """Seconds of ``intervals`` (inside the traced window) under each
    innermost program span: a parent's entry is its self time."""
    lo, hi = tr.window
    segs = _segments(program_spans(tr), lo, hi)
    starts = [s for s, _, _ in segs]
    out: Dict[str, float] = {}
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        j = max(bisect.bisect_right(starts, s) - 1, 0)
        while j < len(segs) and segs[j][0] < e:
            a, b, name = segs[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            j += 1
    return out


def idle_by_span(tr: trace.Trace) -> Dict[str, float]:
    """The first device's idle time in the traced window
    (``bench.lib.trace.gaps``) split by the innermost program span covering
    it."""
    return by_span(tr, trace.gaps(tr))
