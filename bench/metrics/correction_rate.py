"""Share of (KV head, layer, step) selections that speculative retrieval
had to correct, from the program's counters over the window."""


def read(run):
    w = run.window
    return w.corrected / w.kv_head_steps if w.kv_head_steps > 0 else None
