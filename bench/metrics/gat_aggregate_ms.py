"""Device milliseconds per window step of the kernels led by an op of the
`gat.aggregate` scope (the α·(W h) products of every head, forward and
backward, with the bias and the heads' concatenation or mean), on device
0. A kernel counts whole, with whatever XLA fused into it."""
from bench import gat_trace


def read(run):
    got = gat_trace.read(run)
    return None if got is None else got.scope_ms_per_step("gat.aggregate")
