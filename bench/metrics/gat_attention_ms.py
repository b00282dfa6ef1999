"""Device milliseconds per window step of the kernels led by an op of the
`gat.attention` scope (the attention logits, LeakyReLU, mask and
softmax, forward and backward), on device 0. A kernel counts whole, with
whatever XLA fused into it."""
from bench import gat_trace


def read(run):
    got = gat_trace.read(run)
    return None if got is None else got.scope_ms_per_step("gat.attention")
