"""Milliseconds of the program's `engine.epoch_end` spans inside the
window over the steps of the epochs they close (their `steps` stat): the
epoch-end read-back of every step's loss and aux scalars. Nothing when
no epoch ended inside the traced window."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return None if got is None else got.epoch_end_ms_per_step()
