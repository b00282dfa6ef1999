"""Host milliseconds per window step in the program's `batch.slice` span
(self time): `graph.subgraph`, the induced subgraph on the batch's
nodes, with its relabel array and row gathers."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return None if got is None else got.span_ms_per_step("batch.slice")
