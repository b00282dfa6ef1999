"""Host milliseconds inside the sampler's iterator per window step: the
payload build of each raw batch (the harness times `next()` on it)."""


def read(run):
    if not run.steps:
        return None
    return 1e3 * sum(run.build_s) / run.steps
