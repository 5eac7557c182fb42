"""Set-up: weights, engine, compile or cache load, warm-up of every shape
the cell uses, and (closed loop) the first prompts' prefills."""


def read(run):
    return run.window.setup_s
