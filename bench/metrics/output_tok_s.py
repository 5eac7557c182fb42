"""Tokens delivered to clients in the window, over the window; a delivery
that the window's edge cuts counts for the share of its time inside
(``bench.lib.stats.tokens_in_window``)."""
from bench.lib import stats


def read(run):
    w = run.window
    return stats.tokens_in_window(w) / (w.t_end - w.t_start)
