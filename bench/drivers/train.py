"""Training driver: `Engine.fit` through `build_experiment`, as
`run_experiment` runs it, with the configuration's preset and overrides
and the traffic mix's overrides.

Set-up builds the graph and the experiment, hands the engine weights made
from the seed, and lets `fit` compile and run the first `check_steps`
steps, reading back what the comparison needs. The window then opens on
that same engine, inside the same `fit`, and closes at the first step
after `seconds`, on `block_until_ready` of the state. After the window
the device peak is read, the program's state is freed, and the reference
(`bench.reference`) replays the checked steps.

The harness sees the program only through its own wrappers: the sampler's
`epoch` iterator (host build time, which raw batches a step trained on),
the backend's `stream` (payload bytes) and `step` (dispatch), and the
engine's epoch record (the epoch-end read-back of every loss), each under
a profiler annotation so that idle gaps on the device can be attributed.
While the window is open it also counts JAX's compile events and Python's
garbage collections, and a line on standard error names the window's
longest step, so that a stall in an untraced run has a name.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from bench import compare, faults, flops, reference
from bench import trace as tracing
from bench.record import Run

# a traced run traces this much of its window at most: the trace, its
# file and its reduction grow with the steps in it
TRACE_SECONDS = 4.0
# JAX's monitoring events of tracing, lowering and compiling a program
COMPILE_EVENTS = "/jax/core/compile/"


class Stalls:
    """What stalls the host while the window is open: JAX's compile-path
    events (a program traced, lowered, compiled or read from the cache)
    and Python's garbage collections."""

    def __init__(self):
        self.compiles: List[tuple] = []         # (event, seconds)
        self.gc_s = 0.0
        self._gc_start = None

    def _event(self, event, duration, **_):
        if event.startswith(COMPILE_EVENTS):
            self.compiles.append((event, float(duration)))

    def _gc(self, phase, _info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc_s += time.perf_counter() - self._gc_start
            self._gc_start = None

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)
        gc.callbacks.remove(self._gc)

    @property
    def compile_s(self) -> float:
        return sum(d for _, d in self.compiles)


class Probe:
    """Wrappers on one engine's sampler and backend."""

    def __init__(self, engine):
        self.pulled: List[tuple] = []           # raw (epoch, index) so far
        self.payloads = collections.deque()     # (raws, bytes) per payload
        self.builds: List[tuple] = []           # (end time, seconds)
        epoch, stream, step = (engine.batcher.epoch, engine.backend.stream,
                               engine.backend.step)

        def timed_epoch(e, start_step=0):
            it, i = epoch(e, start_step=start_step), start_step
            while True:
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.build"):
                    b = next(it, None)
                if b is None:
                    return
                t1 = time.perf_counter()
                self.builds.append((t1, t1 - t))
                self.pulled.append((e, i))
                i += 1
                yield b

        def counted_stream(batches):
            for payload in stream(batches):
                nbytes = sum(int(np.asarray(x).nbytes)
                             for x in jax.tree_util.tree_leaves(payload))
                self.payloads.append((list(self.pulled), nbytes))
                self.pulled.clear()
                yield payload

        def annotated_step(state, payload):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                return step(state, payload)

        def annotated_record(*args, **kw):
            with jax.profiler.TraceAnnotation("bench.epoch_end"):
                return record(*args, **kw)

        engine.batcher.epoch = timed_epoch
        engine.backend.stream = counted_stream
        engine.backend.step = annotated_step
        # the epoch's record reads every step's loss back to the host
        record = getattr(engine, "_epoch_record", None)
        if record is not None:
            engine._epoch_record = annotated_record


def _opt_mu(state):
    opt = state["opt"] if "opt" in state else state["dist"]["opt"]
    return opt.mu


class Window:
    """Engine hook: reads the checked steps, then opens the window and
    closes it once `seconds` have passed."""

    def __init__(self, probe: Probe, check_steps: int, seconds: float,
                 trace_dir=None):
        self.probe, self.k, self.seconds = probe, check_steps, seconds
        self.trace_dir = trace_dir
        self.check_losses, self.check_raws = [], []
        self.window_losses, self.window_raws, self.window_bytes = [], [], []
        self.mu1 = self.params_k = None
        self.t_fit = self.t_first = self.t_start = self.t_end = None
        self.step_times: List[float] = []       # host clock at each step
        self.stalls = Stalls()
        self._span = None

    def on_fit_start(self, engine) -> None:
        self.t_fit = time.perf_counter()

    def on_step(self, engine, info) -> None:
        step = info["global_step"]
        raws, nbytes = self.probe.payloads.popleft()
        if step == 1:
            self.t_first = time.perf_counter()
        if step <= self.k:
            self.check_losses.append(info["loss"])
            self.check_raws.append(raws)
            if step == 1:
                self.mu1 = jax.device_get(_opt_mu(engine.state))
            if step == self.k:
                self.params_k = jax.device_get(
                    engine.backend.params(engine.state))
                jax.block_until_ready(engine.state)
                if self.trace_dir is not None:
                    tracing.start(self.trace_dir)
                    self._span = jax.profiler.TraceAnnotation("bench.window")
                    self._span.__enter__()
                self.stalls.__enter__()
                self.t_start = time.perf_counter()
            return
        self.step_times.append(time.perf_counter())
        self.window_losses.append(info["loss"])
        self.window_raws.extend(raws)
        self.window_bytes.append(nbytes)
        if time.perf_counter() - self.t_start >= self.seconds:
            engine.request_stop("window closed")

    def close(self, engine) -> None:
        jax.block_until_ready(engine.state)
        self.t_end = time.perf_counter()
        if self.t_start is not None:
            self.stalls.__exit__(None, None, None)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def _dims(graph, spec):
    in_dim = graph.features.shape[1]
    out_dim = (graph.labels.shape[1] if graph.labels.ndim == 2
               else int(graph.labels.max()) + 1)
    return reference.layer_dims(in_dim, spec.model.hidden_dim, out_dim,
                                spec.model.num_layers)


def _reference_model(spec, multilabel: bool) -> reference.Model:
    m, o = spec.model, spec.optim
    unsupported = {"model.residual": m.residual,
                   "model.precompute_ax": m.precompute_ax,
                   "optim.weight_decay": o.weight_decay != 0,
                   "optim.clip_norm": o.clip_norm is not None,
                   "execution.microbatches": spec.execution.microbatches
                   != 1,
                   "batch.sampler": spec.batch.sampler != "cluster"}
    if o.name != "adamw" or any(unsupported.values()):
        raise ValueError(f"the reference covers Adam without weight decay "
                         f"or clipping, the cluster sampler, no residual "
                         f"and no precomputed A'X; this cell sets "
                         f"{[k for k, v in unsupported.items() if v]}")
    return reference.Model(num_layers=m.num_layers, dropout=m.dropout,
                           layernorm=m.layernorm, multilabel=multilabel,
                           lr=o.lr, b1=o.b1, b2=o.b2, eps=o.eps)


def _spec(ctx, extra):
    from repro.core.experiment import apply_overrides, preset, validate
    spec = preset(ctx.config["preset"])
    overrides = {k: v for k, v in ctx.config.items() if "." in k}
    overrides.update(ctx.traffic.get("overrides", {}))
    overrides.update({
        "partition.cache_dir": str(ctx.cache_dir / "partitions"),
        "batch.seed": ctx.seeds["batches"],
        "run.seed": ctx.seeds["weights"]})
    overrides.update(extra)
    return validate(apply_overrides(spec, overrides))


def run(ctx) -> Run:
    from repro.core.experiment import build_experiment, build_graph

    extra = dict(ctx.overrides)
    precision = extra.pop("matmul_precision",
                          ctx.config.get("matmul_precision", "default"))
    spec = _spec(ctx, extra)
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

    dims = _dims(graph, spec)
    params = reference.init_params(ctx.seeds["weights"], dims,
                                   spec.model.layernorm)
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
    parts = exp.parts
    window_losses = np.asarray(jax.device_get(window.window_losses),
                               np.float64)
    engine.state = None
    exp = engine = None
    gc.collect()

    rgraph = reference.Graph.from_arrays(
        graph.indptr, graph.indices, graph.data, graph.features,
        graph.labels, graph.train_mask)
    numbers = reference.partition_numbers(rgraph, parts,
                                          spec.partition.num_parts)
    if numbers["partition.invalid"]:
        raise RuntimeError(f"the program's partition is no assignment of "
                           f"the graph's nodes to {spec.partition.num_parts}"
                           f" parts: {numbers}")
    batches = reference.Batches(parts,
                                spec.batch.clusters_per_batch,
                                spec.batch.seed, spec.batch.pad_multiple,
                                spec.batch.node_cap)
    numbers.update(_compare(window, rgraph, batches, spec, params0, rng0,
                            group))

    t_start, t_end = window.t_start, window.t_end
    builds = [d for (t, d) in probe.builds if t_start <= t <= t_end]
    raws = window.window_raws

    def required_flops() -> float:
        return float(sum(
            flops.train_flops(batches.size(e, i),
                              reference.block_nnz(rgraph,
                                                  batches.nodes(e, i)),
                              dims, spec.model.precompute_ax)
            for e, i in raws))

    reduced = None
    if trace_dir is not None:
        reduced = tracing.reduce(tracing.load(trace_dir),
                                 [d.id for d in ctx.devices],
                                 steps=len(window.window_losses))
    return Run(
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


def _report_stalls(window: Window, steps_per_epoch: int) -> None:
    """One line on standard error: the window's longest step on the host
    clock, where it fell in its epoch, and the host's stalls."""
    times = [window.t_start] + window.step_times
    gaps = np.diff(times)
    if not len(gaps):
        return
    i = int(np.argmax(gaps))
    step = window.k + i + 1                 # global step, 1-based
    print(f"bench: window {len(gaps)} steps, median step "
          f"{float(np.median(gaps)):.6f} s, longest {float(gaps[i]):.6f} s "
          f"at step {step} (step {(step - 1) % steps_per_epoch + 1} of "
          f"{steps_per_epoch} in its epoch); compile events "
          f"{len(window.stalls.compiles)} ({window.stalls.compile_s:.6f} s);"
          f" garbage collection {window.stalls.gc_s:.6f} s",
          file=sys.stderr, flush=True)


def _memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _compare(window: Window, rgraph, batches, spec, params0, rng0,
             group: int) -> Dict[str, float]:
    step_batches = []
    for raws in window.check_raws:
        if len(raws) != group:
            raise RuntimeError(f"a checked step trained on {len(raws)} "
                               f"raw batches, expected {group}")
        step_batches.append([
            reference.padded_batch(rgraph, batches.nodes(e, i), batches.cap,
                                   spec.batch.norm, spec.batch.diag_lambda)
            for e, i in raws])
    model = _reference_model(spec, rgraph.multilabel)
    ref_losses, ref_grad, ref_params = reference.run_steps(
        params0, rng0, step_batches, model)
    losses = [float(x) for x in jax.device_get(window.check_losses)]
    grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - model.b1),
                                  window.mu1)
    sub = lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64)
    update = jax.tree_util.tree_map(sub, window.params_k, params0)
    ref_update = jax.tree_util.tree_map(sub, ref_params, params0)
    return compare.training_numbers(losses, grad, update, ref_losses,
                                    ref_grad, ref_update)
