"""Share of its roofline that the dense masked attention reaches: the
least time the chip could take for one step's attention and aggregation
on the (cap, cap) block, max(operations / bf16 peak, bytes / HBM peak)
from `bench.gat_flops` (`Run.gat_attention`), over the device time per
step of the kernels led by the `gat.attention` and `gat.aggregate`
scopes."""
from bench import gat_trace


def read(run):
    work = getattr(run, "gat_attention", None)
    if not run.peaks or work is None:
        return None
    got = gat_trace.read(run)
    ms = (None if got is None
          else got.scope_ms_per_step("gat.attention", "gat.aggregate"))
    if not ms:
        return None
    least_s = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                  work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
