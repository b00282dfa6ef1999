"""Peak device memory in use over the run, on the fullest chip, in
megabytes (1e6 bytes): memory_stats()["peak_bytes_in_use"]."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e6
