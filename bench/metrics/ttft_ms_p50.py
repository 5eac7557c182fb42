"""Time to first token from each request's due time, 50th percentile
over every request due in the window."""
from bench.lib import stats


def read(run):
    v = stats.percentile(stats.ttfts(run.window), 50)
    return None if v is None else 1e3 * v
