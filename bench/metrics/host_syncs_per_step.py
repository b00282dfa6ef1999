"""Device scalars read back to the host per step: the `syncs` stat over
the `steps` stat, summed over the `engine.epoch_end` spans inside the
window (a count the program makes from what it reads)."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return None if got is None else got.syncs_per_step()
