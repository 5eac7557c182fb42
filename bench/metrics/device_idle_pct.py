"""1 - (union of device operation intervals / traced window), in %.
Control-flow operations, which enclose the operations of their bodies, are
left out, so idle time inside a loop shows."""
from bench.lib import trace


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(tr) / tr.window_s)
