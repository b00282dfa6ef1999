"""Host milliseconds per window step in the program's `batch.gather` span
(self time): the padded features (with a precomputed Â·X), labels and
masks of the batch's nodes."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return None if got is None else got.span_ms_per_step("batch.gather")
