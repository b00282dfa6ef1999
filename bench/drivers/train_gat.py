"""Training driver for graph attention (`model.arch="gat"`): the path of
`bench/drivers/train.py`, `Engine.fit` through `build_experiment` with
the same wrappers, window and read-back, held to the GAT reference
(`bench.gat_reference`) in place of the GCN one.

Set-up hands the engine weights made from the seed by the reference's
own initialiser. After the window the reference replays the checked
steps on the same batches, each stated as an edge list over its real
nodes, and `bench.compare` turns the two into the numbers that decide
`correct`. The run also carries the operations and HBM bytes of one
step's dense masked attention on the (cap, cap) block
(`Run.gat_attention`, from `bench.gat_flops`), which
`gat_attention_roofline_pct` reads.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict

import jax
import numpy as np

from bench import compare, faults, gat_flops, gat_reference, reference
from bench import trace as tracing
from bench.drivers.train import (TRACE_SECONDS, Probe, Window, _memory_peak,
                                 _report_stalls, _spec)
from bench.record import Run


def _check_reference_covers(spec) -> None:
    m, o = spec.model, spec.optim
    unsupported = {"model.arch": m.arch != "gat",
                   "model.dropout": m.dropout != 0,
                   "optim.weight_decay": o.weight_decay != 0,
                   "optim.clip_norm": o.clip_norm is not None,
                   "execution.microbatches": spec.execution.microbatches
                   != 1,
                   "batch.sampler": spec.batch.sampler != "cluster"}
    if o.name != "adamw" or any(unsupported.values()):
        raise ValueError(f"the GAT reference covers arch 'gat' "
                         f"without dropout, Adam without weight decay or "
                         f"clipping, and the cluster sampler; this cell "
                         f"sets {[k for k, v in unsupported.items() if v]}")


def _shapes(graph, spec):
    out_dim = (graph.labels.shape[1] if graph.labels.ndim == 2
               else int(graph.labels.max()) + 1)
    return gat_reference.layer_shapes(graph.features.shape[1],
                                      spec.model.hidden_dim, out_dim,
                                      spec.model.heads)


def run(ctx) -> Run:
    from repro.core.experiment import build_experiment, build_graph

    extra = dict(ctx.overrides)
    precision = extra.pop("matmul_precision",
                          ctx.config.get("matmul_precision", "default"))
    spec = _spec(ctx, extra)
    _check_reference_covers(spec)
    k = int(ctx.traffic.get("check_steps", 3))
    ctx_precision = (contextlib.nullcontext() if precision == "default"
                     else jax.default_matmul_precision(precision))
    trace_dir = ctx.cache_dir / "trace" / ctx.workload if ctx.trace else None
    if trace_dir is not None:
        tracing.clear(trace_dir)

    t0 = time.perf_counter()
    graph = build_graph(spec)
    with faults.plant_build(ctx.plant):
        exp = build_experiment(spec, graph=graph)
    graph_s = time.perf_counter() - t0
    engine = exp.engine
    probe = Probe(engine)
    seconds = min(ctx.seconds, TRACE_SECONDS) if ctx.trace else ctx.seconds
    window = Window(probe, k, seconds, trace_dir)
    engine.hooks.append(window)

    shapes = _shapes(graph, spec)
    params = gat_reference.init_params(ctx.seeds["weights"], shapes)
    params0 = jax.device_get(params)
    rng0 = jax.random.PRNGKey(ctx.seeds["dropout"])
    engine.init_state = lambda: engine.backend.init(params, rng0)
    group = int(getattr(engine.backend, "group_size", 1))

    with ctx_precision, faults.plant(ctx.plant, engine.backend):
        engine.fit()
        window.close(engine)
    if window.t_start is None:
        raise RuntimeError(f"training stopped after {engine.global_step} "
                           f"steps, before the window opened")
    _report_stalls(window, exp.batcher.steps_per_epoch())
    del params
    memory_peak = _memory_peak(ctx.devices)
    parts, cap = exp.parts, exp.batcher.node_cap
    window_losses = np.asarray(jax.device_get(window.window_losses),
                               np.float64)
    engine.state = None
    exp = engine = None
    gc.collect()

    rgraph = reference.Graph.from_arrays(
        graph.indptr, graph.indices, graph.data, graph.features,
        graph.labels, graph.train_mask)
    if not rgraph.multilabel:
        raise ValueError("the GAT reference covers multilabel graphs only")
    numbers = reference.partition_numbers(rgraph, parts,
                                          spec.partition.num_parts)
    if numbers["partition.invalid"]:
        raise RuntimeError(f"the program's partition is no assignment of "
                           f"the graph's nodes to {spec.partition.num_parts}"
                           f" parts: {numbers}")
    batches = reference.Batches(parts, spec.batch.clusters_per_batch,
                                spec.batch.seed, spec.batch.pad_multiple,
                                spec.batch.node_cap)
    if batches.cap != cap:
        raise RuntimeError(f"the program pads batches to {cap} nodes, "
                           f"Algorithm 1 over its partition to "
                           f"{batches.cap}")
    numbers.update(_compare(window, rgraph, batches, spec, params0, group))

    t_start, t_end = window.t_start, window.t_end
    builds = [d for (t, d) in probe.builds if t_start <= t <= t_end]
    raws = window.window_raws

    def required_flops() -> float:
        return float(sum(
            gat_flops.train_flops(batches.size(e, i),
                                  reference.block_nnz(rgraph,
                                                      batches.nodes(e, i)),
                                  shapes)
            for e, i in raws))

    reduced = None
    if trace_dir is not None:
        reduced = tracing.reduce(tracing.load(trace_dir),
                                 [d.id for d in ctx.devices],
                                 steps=len(window.window_losses))
    run = Run(
        chips=ctx.chips, peaks={},
        setup_s=t_start - ctx.t_process_start,
        setup_parts={"graph_s": graph_s,
                     "compile_s": window.t_first - window.t_fit},
        window_compile_s=window.stalls.compile_s,
        window_s=t_end - t_start, steps=len(window.window_losses),
        nodes=int(sum(batches.size(e, i) for e, i in raws)),
        attempted=len(window_losses),
        failed=int((~np.isfinite(window_losses)).sum()),
        build_s=builds, payload_bytes=window.window_bytes,
        memory_peak_bytes=memory_peak, trace=reduced,
        required_flops=required_flops, numbers=numbers)
    # one step on one chip: the readers divide device 0's time by steps
    run.gat_attention = gat_flops.dense_attention(cap, shapes, precision)
    return run


def _compare(window: Window, rgraph, batches, spec, params0,
             group: int) -> Dict[str, float]:
    step_batches = []
    for raws in window.check_raws:
        if len(raws) != group:
            raise RuntimeError(f"a checked step trained on {len(raws)} "
                               f"raw batches, expected {group}")
        step_batches.append([gat_reference.edge_batch(rgraph,
                                                      batches.nodes(e, i))
                             for e, i in raws])
    o = spec.optim
    ref_losses, ref_grad, ref_params = gat_reference.run_steps(
        params0, step_batches, residual=spec.model.residual, lr=o.lr,
        b1=o.b1, b2=o.b2, eps=o.eps)
    losses = [float(x) for x in jax.device_get(window.check_losses)]
    grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - o.b1),
                                  window.mu1)
    sub = lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64)
    update = jax.tree_util.tree_map(sub, window.params_k, params0)
    ref_update = jax.tree_util.tree_map(sub, ref_params, params0)
    return compare.training_numbers(losses, grad, update, ref_losses,
                                    ref_grad, ref_update)
