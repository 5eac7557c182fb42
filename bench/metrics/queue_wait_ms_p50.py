"""From each request's submission to the start of its prefill (the
program's ``RequestMetrics.prefill_start_t``), median over requests due in
the window: the serving layer's queue, without the sender's lateness."""
from bench.lib import stats


def read(run):
    v = [r.queue_wait_s for r in stats.due_in_window(run.window)
         if r.queue_wait_s is not None]
    p = stats.percentile(v, 50)
    return None if p is None else 1e3 * p
