"""Real (unpadded) nodes trained on per second of the whole window: every
step, epoch-end sync and stall inside it. A data-parallel group's
wrap-around repeats are not counted."""


def read(run):
    return run.nodes / run.window_s
