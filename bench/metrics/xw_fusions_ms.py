"""Device milliseconds per window step of the kernels led by an op of the
`gcn.xw` scope (X·W plus bias) or the fused `gcn.xw_aggregate` scope,
forward and backward, on device 0. A kernel counts whole, with whatever
XLA fused into it: on TPU the weight-gradient matmuls carry Adam's
update of their weights."""
from bench import program_trace


def read(run):
    got = program_trace.read(run)
    return (None if got is None
            else got.scope_ms_per_step("gcn.xw", "gcn.xw_aggregate"))
