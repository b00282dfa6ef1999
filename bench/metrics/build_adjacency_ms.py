"""Host milliseconds per window step in the program's `batch.adjacency`
span (self time): the dense Â's fill and `normalize_dense`, or
`normalize_csr` and the block-ELL tiling."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return (None if got is None
            else got.span_ms_per_step("batch.adjacency"))
