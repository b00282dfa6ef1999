"""Required training operations per second over the chips' bf16 peak:
3x the forward's X·W over real nodes plus 2·nnz(Â)·width per layer
(bench/flops.py), for the window's steps, over the window's seconds."""


def read(run):
    if not run.peaks or run.required_flops is None or not run.steps:
        return None
    rate = run.required_flops() / run.window_s
    return 100.0 * rate / (run.peaks["bf16_flops_per_s"] * run.chips)
