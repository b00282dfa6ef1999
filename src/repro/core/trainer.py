"""Cluster-GCN trainer (paper Algorithm 1) + exact full-graph evaluation.

`train_cluster_gcn` is a thin wrapper over the step-driven Engine
(repro.core.engine): it picks the StepBackend (single-device jit, or
shard_map data-parallel when `mesh=` is given), assembles the standard
hooks (periodic eval, verbose logging), and runs `Engine.fit()` — the
signature and training trajectories are unchanged from the pre-Engine
inline loops (locked by tests/test_engine.py). For the declarative
config-first path — presets, checkpoint/resume, preemption — see
repro.core.experiment and `python -m repro.launch.run_experiment`.

Evaluation propagates the FULL graph layer-by-layer with scipy CSR on
the host — exact (no sampling bias), memory O(N·F) per layer, and
independent of the training batching (this is how the paper evaluates
too).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax
import numpy as np

from repro.core import gat
from repro.core.batching import ClusterBatcher
from repro.core.engine import (Engine, EvalHook, LoggingHook,  # noqa: F401
                               ShardMapBackend, SingleDeviceBackend,
                               TrainResult, _dp_groups, make_train_step)
from repro.core.gcn import GCNConfig, micro_f1
from repro.graph.csr import CSRGraph
from repro.graph.normalization import normalize_csr
from repro.kernels.ops import spmm as spmm_dispatch
from repro.nn.optim import Optimizer


def full_graph_logits(params, graph: CSRGraph, cfg: GCNConfig,
                      norm: str = "eq10", diag_lambda: float = 0.0,
                      batch_rows: int = 65536) -> np.ndarray:
    """Exact layer-wise propagation on the host (scipy CSR); graph
    attention (cfg.arch="gat") propagates over the edge list instead."""
    if cfg.arch == "gat":
        return gat.full_graph_logits(params, graph, cfg.residual)
    import scipy.sparse as sp
    ip, ix, dt = normalize_csr(graph.indptr, graph.indices, graph.data,
                               norm, diag_lambda)
    a = sp.csr_matrix((dt, ix, ip), shape=(graph.num_nodes,) * 2)
    h = graph.features.astype(np.float32)
    if cfg.precompute_ax:
        h = a @ h
    layers = jax.tree_util.tree_map(np.asarray, params["layers"])
    for i, layer in enumerate(layers):
        z = h @ layer["w"] + layer["b"]
        if not (i == 0 and cfg.precompute_ax):
            z = a @ z
        if i < len(layers) - 1:
            if cfg.residual and z.shape == h.shape:
                z = z + h
            z = np.maximum(z, 0.0)
            if cfg.layernorm:
                mu = z.mean(-1, keepdims=True)
                sd = z.std(-1, keepdims=True)
                z = (z - mu) / (sd + 1e-6) * layer["ln_scale"]
        h = z
    return h


def evaluate(params, graph: CSRGraph, cfg: GCNConfig, mask: np.ndarray,
             norm: str = "eq10", diag_lambda: float = 0.0) -> float:
    """Micro-F1 (multilabel) or accuracy (multiclass) on `mask` nodes."""
    logits = full_graph_logits(params, graph, cfg, norm, diag_lambda)
    if cfg.multilabel:
        y = graph.labels[mask]
        pred = (logits[mask] > 0).astype(np.float32)
        tp = float((pred * y).sum())
        fp = float((pred * (1 - y)).sum())
        fn = float(((1 - pred) * y).sum())
        return micro_f1(tp, fp, fn)
    pred = logits[mask].argmax(-1)
    return float((pred == graph.labels[mask]).mean())


def train_cluster_gcn(graph: CSRGraph, batcher: ClusterBatcher,
                      cfg: GCNConfig, opt: Optimizer, num_epochs: int,
                      seed: int = 0, eval_every: int = 0,
                      eval_graph: Optional[CSRGraph] = None,
                      spmm: Callable = spmm_dispatch,
                      verbose: bool = False,
                      mesh=None, compression=None,
                      dp_axis: str = "data",
                      sparse_adj: bool = False,
                      prefetch: int = 0) -> TrainResult:
    """Paper Algorithm 1. `graph` is the training graph (inductive);
    `eval_graph` (default: graph) is the full graph for evaluation.
    With `mesh=`, trains data-parallel over the mesh's `dp_axis` (one
    cluster batch per shard per step, gradients all-reduced — optionally
    compressed, see repro.dist.compression). `sparse_adj=True` switches
    the batcher to BlockEllAdj batches, so every Â·(XW) in the step runs
    through the differentiable block-ELL spmm (Pallas kernel on TPU)
    instead of the dense XLA matmul — the loss is mathematically
    identical (verified to 1e-4/step by tests/test_sparse_equivalence).
    `prefetch=N` (repro.core.prefetch) builds batches N ahead on a
    background thread — including the DP stacking and the device_put —
    overlapping host batch construction with the device step; batch
    order and results are identical to the synchronous loop (0 keeps
    the fully synchronous path).

    Eval runs every `eval_every` epochs on the val split, falling back
    to the TEST split with a one-time warning when val_mask is missing
    or empty (the split actually used is recorded per history entry as
    `eval_split`; the ExperimentSpec path makes the split explicit via
    run.eval_split)."""
    if sparse_adj and not batcher.sparse_adj:
        batcher = dataclasses.replace(batcher, sparse_adj=True)
    if cfg.precompute_ax and not getattr(batcher, "precompute_ax", False):
        # stale caller: the model expects payload-time A'X (paper §6.2)
        # but the sampler was built without it — rebuild to match rather
        # than silently skipping layer 1's propagation on raw features
        warnings.warn(
            "cfg.precompute_ax=True but the batcher was built with "
            "precompute_ax=False — rebuilding the batcher with "
            "payload-time A'X aggregation to match the model "
            "(build samplers with precompute_ax=True to silence this)",
            stacklevel=2)
        batcher = dataclasses.replace(batcher, precompute_ax=True)
    if mesh is not None:
        backend = ShardMapBackend(cfg, opt, mesh, dp_axis=dp_axis,
                                  compression=compression, spmm=spmm)
    else:
        backend = SingleDeviceBackend(cfg, opt, spmm)
    hooks = []
    if eval_every:
        hooks.append(EvalHook(eval_graph if eval_graph is not None
                              else graph, cfg,
                              every=eval_every, split="auto",
                              norm=batcher.norm,
                              diag_lambda=batcher.diag_lambda))
    if verbose:
        hooks.append(LoggingHook())
    engine = Engine(batcher, cfg, backend, epochs=num_epochs, seed=seed,
                    prefetch=prefetch, hooks=hooks)
    return engine.fit()
