"""From the start of a request's prefill to its first token (the program's
``RequestMetrics``: prefill, splice into its slot, first sample), median
over requests due in the window."""
from bench.lib import stats


def read(run):
    v = [r.prefill_s for r in stats.due_in_window(run.window)
         if r.prefill_s is not None]
    p = stats.percentile(v, 50)
    return None if p is None else 1e3 * p
