"""Device time of the host-pool DMA kernels (``recall_gather_host``,
``write_host``) in the traced window, per decode step."""
from bench.lib import stats, trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    steps = stats.decode_steps(tr, run.model["num_hidden_layers"])
    if steps <= 0:
        return None
    dma = sum(e.dur for k in ("recall_gather_host", "write_host")
              for e in trace.kernel_events(tr, k))
    return 1e3 * dma / steps
