"""Device milliseconds per window step of the kernels led by an op of the
`gcn.aggregate` scope (Â·(XW), dense or block-ELL), forward and
backward, on device 0. A kernel counts whole, with whatever XLA fused
into it: on TPU the activations and layer norms that follow."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return None if got is None else got.scope_ms_per_step("gcn.aggregate")
