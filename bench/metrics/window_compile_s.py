"""Host seconds inside the window that JAX spent tracing, lowering and
compiling programs, or reading them from the cache: its monitoring
events under /jax/core/compile/. Every shape is warmed up in set-up, so
anything above 0 is a stall that the window pays for."""


def read(run):
    return run.window_compile_s
