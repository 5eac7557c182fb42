"""The longest host turn between decode windows in the traced window
(``bench.lib.spans.host_turns``): a stall of the engine's host loop."""
from bench.lib import spans


def read(run):
    turns = spans.host_turns(run.trace) if run.trace is not None else []
    if not turns:
        return None
    return 1e3 * max(e - s for s, e in turns)
