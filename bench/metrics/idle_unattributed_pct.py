"""Share of the traced window in which device 0 is idle and no program
span (`repro.*`) is open on any host thread: idle time that the
program's spans cannot name."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return None if got is None else got.unattributed_pct()
