"""Faults planted under a training cell's timed path, to show that the
comparison in `bench.compare` catches them. Used by the tests under
`tests/benchmark/` and by `bench/calibrate.py`; a benchmark run plants
nothing.

  unchanged        the step computes, then hands back the state it was
                   given;
  half_batch       the second half of each batch's real nodes gets no loss
                   weight, so the loss is the mean over the rest;
  stale_partition  the batcher is handed a partition that belongs to no
                   graph of this cell (the program's assignment with its
                   node ids shuffled), as a stale cache entry would be.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

STEP_PLANTS = ("unchanged", "half_batch")
BUILD_PLANTS = ("stale_partition",)
PLANTS = STEP_PLANTS + BUILD_PLANTS


def _check(name):
    if name is not None and name not in PLANTS:
        raise ValueError(f"unknown fault {name!r} (known: {PLANTS})")


def _unchanged(step):
    def planted(state, payload):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _, loss, aux = step(state, payload)
        return kept, loss, aux
    return planted


def _half_batch(step):
    def planted(state, payload):
        payload = list(payload)
        mask = np.array(payload[4], np.float32)
        real = np.atleast_1d(np.asarray(payload[5]))
        rows = mask.reshape(-1, mask.shape[-1])
        for row, n in zip(rows, real):
            row[int(n) // 2:] = 0.0
        payload[4] = rows.reshape(mask.shape)
        return step(state, tuple(payload))
    return planted


@contextlib.contextmanager
def plant_build(name):
    """Break the program's set-up (graph to batcher) for the duration."""
    _check(name)
    if name not in BUILD_PLANTS:
        yield
        return
    from repro.core import experiment
    kept = experiment.build_partition

    def stale(spec, graph):
        parts, stats = kept(spec, graph)
        return np.random.default_rng(0).permutation(parts), stats

    experiment.build_partition = stale
    try:
        yield
    finally:
        experiment.build_partition = kept


@contextlib.contextmanager
def plant(name, backend):
    """Break `backend` (an engine's step backend) for the duration."""
    _check(name)
    if name not in STEP_PLANTS:
        yield
        return
    kept = backend.step
    backend.step = (_unchanged if name == "unchanged" else _half_batch)(kept)
    try:
        yield
    finally:
        backend.step = kept
