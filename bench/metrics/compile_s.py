"""Host seconds from `Engine.fit` to the return of the first step's
dispatch, which compiles the step (or loads it from the cache)."""


def read(run):
    return run.setup_parts["compile_s"]
