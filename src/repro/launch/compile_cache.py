"""Where the entry points keep JAX's persistent compilation cache.

A cache directory only hits when it stays put, so its path is fixed:
`$JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads
that variable itself), else `<repo>/.jax_cache`. Entry points call
`enable_compile_cache()` from their `main()`; importing a module never
changes JAX's configuration.

The cache's key covers op metadata, so that a cached executable carries
the op names, and so the named scopes (docs/tracing.md), of the program
that asked for it rather than those of an older version. The price: the
metadata holds source lines, so an edit that shifts a line of the step's
source compiles the step anew.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
