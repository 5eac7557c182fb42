"""Window selections and statistics shared by the metric readers. Every
statistic is over all requests or tokens the rule selects, never a sample."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation) of all ``values``."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(w) -> list:
    return [r for r in w.records.values() if w.t_start <= r.due < w.t_end]


def ttfts(w) -> List[float]:
    """Seconds from each request's due time to its first token, for every
    request due in the window; one that never got a first token counts
    from its due time to the end of the wait for it."""
    end = max([w.t_end] + [r.finish_t or 0.0 for r in w.records.values()])
    return [(r.first_t if r.first_t is not None else end) - r.due
            for r in due_in_window(w)]


# Tokens of one delivery (one host sync of the engine) reach the client
# within microseconds of each other; deliveries are whole decode windows
# apart.
DELIVERY_GAP_S = 0.005


def deliveries(r):
    """A request's deliveries: (start, end, tokens), ``start`` being the
    previous delivery (the submission for the first)."""
    out, prev, i, ts = [], r.submit_t, 0, r.token_t
    while i < len(ts):
        j = i + 1
        while j < len(ts) and ts[j] - ts[j - 1] < DELIVERY_GAP_S:
            j += 1
        out.append((prev, ts[j - 1], j - i))
        prev, i = ts[j - 1], j
    return out


def tokens_in_window(w) -> float:
    """Tokens delivered in the window, each delivery's tokens spread evenly
    over the time since the request's previous delivery: a window edge
    splits a delivery instead of counting it whole or not at all, and a
    stall before a delivery counts as time without tokens."""
    total = 0.0
    for r in w.records.values():
        for a, b, n in deliveries(r):
            if b > a:
                total += n * max(0.0, min(b, w.t_end) - max(a, w.t_start)) \
                    / (b - a)
            elif w.t_start <= b < w.t_end:
                total += n
    return total


def finished_in_window(w) -> list:
    return [r for r in w.records.values()
            if r.finish_t is not None and w.t_start <= r.finish_t < w.t_end
            and not r.cancelled and r.error is None]


def decode_steps(tr, layers: int) -> float:
    """Decode steps in the traced window: ``paged_attention`` runs once
    per layer and step, for every slot at once."""
    from bench.lib import trace
    return len(trace.kernel_events(tr, "paged_attention")) / layers


def roofline_pct(run, kernel: str) -> Optional[float]:
    """Share of the kernel's device time that its roofline bound needs:
    calls x max(flops / peak, bytes / HBM bandwidth) over the measured time.
    Where the compiler staged an operand in VMEM, the operation that staged
    it read it from HBM, so its time counts as the kernel's."""
    import importlib

    from bench.lib import trace
    if run.trace is None:
        return None
    calls = trace.kernel_calls(run.trace, kernel)
    spent = sum(k.dur + sum(p.dur for p in staged) for k, staged in calls)
    if not calls or spent <= 0:
        return None
    flops, nbytes = importlib.import_module(
        f"bench.kernels.{kernel}").counts(run.model, run.mix)
    t_min = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * len(calls) * t_min / spent
