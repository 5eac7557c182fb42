"""Per request finished in the window, (last token - first token) / (n - 1);
the 95th percentile over all of them."""
from bench.lib import stats


def read(run):
    v = [(r.token_t[-1] - r.token_t[0]) / (len(r.token_t) - 1)
         for r in stats.finished_in_window(run.window) if len(r.token_t) > 1]
    p = stats.percentile(v, 95)
    return None if p is None else 1e3 * p
