"""Where the entry points keep JAX's persistent compilation cache.

A cache directory only hits when it stays put, so its path is fixed:
`$JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads
that variable itself), else `<repo>/.jax_cache`. Entry points call
`enable_compile_cache()` from their `main()`; importing a module never
changes JAX's configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
