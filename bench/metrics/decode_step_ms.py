"""Device time of the decode-window programs (those that run
``paged_attention``) in the traced window, per decode step."""
from bench.lib import trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    mods = trace.modules_with(tr, "paged_attention")
    ks = trace.kernel_events(tr, "paged_attention")
    calls = sum(1 for m in mods for e in ks
                if m.start <= e.start and e.end <= m.end)
    steps = calls / run.model["num_hidden_layers"]
    if steps <= 0:
        return None
    return 1e3 * sum(m.dur for m in mods) / steps
