"""Megabytes (1e6 bytes) of host payload per window step: the bytes of
every leaf the step is handed, which the step moves to the device."""


def read(run):
    if not run.steps:
        return None
    return sum(run.payload_bytes) / run.steps / 1e6
