"""The host's mean turn between decode windows in the traced window, from
the program's ``engine/sync_wait`` spans: end of one wait to the start of
the next (``bench.lib.spans.host_turns``)."""
from bench.lib import spans


def read(run):
    turns = spans.host_turns(run.trace) if run.trace is not None else []
    if not turns:
        return None
    return 1e3 * sum(e - s for s, e in turns) / len(turns)
