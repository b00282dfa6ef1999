"""Step-driven training engine: ONE epoch loop over a StepBackend.

Before this module, `core.trainer.train_cluster_gcn` carried two inline
epoch loops (single-device jit and shard_map data-parallel) and the
fault-tolerance subsystems (runtime.CheckpointManager, PreemptionHandler)
sat outside them. The Engine inverts that:

* `StepBackend` — the protocol one training step implements.
  `SingleDeviceBackend` wraps the jit'd per-batch step;
  `ShardMapBackend` wraps `dist.steps.make_gcn_train_step` plus the
  `_dp_groups` stacking that feeds one cluster batch per data shard.
  Both own their RNG threading, so the Engine's loop is backend-agnostic
  and trajectories are bitwise-identical to the old inline loops.
* Hooks — objects with any of `on_fit_start/on_step/on_epoch/on_eval/
  on_fit_end`, fired by the Engine. Periodic eval (EvalHook), checkpoint
  cadence (CheckpointHook), metric logging (LoggingHook) and
  preemption-triggered save (PreemptionHook: SIGTERM → checkpoint →
  clean exit) all run through this seam instead of inline `if`s.
* Resume — `Engine.fit(resume=True)` restores the latest checkpoint
  (params/opt/RNG state tree + JSON metadata carrying epoch,
  step-in-epoch, partial-epoch loss/aux accumulators and history) and
  fast-forwards the batch stream to the exact position, so a killed run
  continues on the exact trajectory of an unkilled one — mid-epoch
  included. Batch order needs no stored state: ClusterBatcher reseeds
  per (seed, epoch), so skipping the first k payloads of epoch e
  reproduces the tail exactly.

`core.trainer.train_cluster_gcn` is now a thin wrapper over this class;
`core.experiment.build_experiment` builds one from a declarative
ExperimentSpec.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import math
import signal as _signal
import time
import warnings
from typing import (Any, Callable, Dict, Iterator, List, Optional, Protocol,
                    Sequence, Tuple, Union, runtime_checkable)

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.batching import Sampler
from repro.core.gcn import GCNConfig, gcn_loss, init_gcn, micro_f1
from repro.core.precision import (all_finite, init_scale_state,
                                  policy_from_config, scale_loss,
                                  select_tree, unscale_grads,
                                  update_scale_state)
from repro.core.prefetch import prefetch_iter
from repro.kernels.ops import spmm as spmm_dispatch
from repro.kernels.ops import spmm_xw as spmm_xw_dispatch
from repro.nn.optim import Optimizer, apply_updates
from repro.runtime import faults, tracing
from repro.runtime.resilience import StragglerDetector

PyTree = Any

# deepest depth execution.prefetch="auto" will ever pick: past ~4
# queued batches the producer thread is saturated and extra depth only
# holds more payload memory live (it also bounds the tile-pool aliasing
# check for auto runs, which must budget for the worst case up front)
AUTO_PREFETCH_MAX = 4

# fit() must NOT clear an externally-installed fault plan when the
# engine itself has none (chaos tests install plans around fit), so the
# no-plan path enters a null context instead of fault_scope(None)
_NULL_CTX = contextlib.nullcontext()


@dataclasses.dataclass
class TrainResult:
    history: List[Dict[str, float]]
    params: Any
    seconds: float


def make_train_step(cfg: GCNConfig, opt: Optimizer,
                    spmm: Callable = spmm_dispatch,
                    spmm_xw: Callable = spmm_xw_dispatch):
    """Single-device jit'd step. With cfg.loss_scaling == "none" (the
    default) the returned step takes (params, opt_state, rng, batch) and
    its jaxpr is EXACTLY the pre-precision-policy step — bitwise-locked
    by tests/test_precision.py. A scaled policy returns a 5-arg step
    (params, opt_state, rng, scale_state, batch): the gradient is taken
    of loss·scale, unscaled in fp32, and a non-finite gradient skips the
    update (params/opt unchanged) while dynamic scaling backs the scale
    off — the standard mixed-precision recipe."""
    pol = policy_from_config(cfg)
    if not pol.scaled:
        def step(params, opt_state, rng, batch_tuple):
            rng, sub = jax.random.split(rng)
            (loss, aux), grads = jax.value_and_grad(gcn_loss, has_aux=True)(
                params, batch_tuple, cfg, train=True, rng=sub,
                spmm=spmm, spmm_xw=spmm_xw)
            with jax.named_scope("optim.update"):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
            return params, opt_state, rng, loss, aux
        return faults.wrap_step_faults(jax.jit(step, donate_argnums=(0, 1)))

    def scaled_loss(params, batch_tuple, sub, scale):
        loss, aux = gcn_loss(params, batch_tuple, cfg, train=True,
                             rng=sub, spmm=spmm, spmm_xw=spmm_xw)
        return scale_loss(loss, scale), (loss, aux)

    def step(params, opt_state, rng, scale_state, batch_tuple):
        rng, sub = jax.random.split(rng)
        (_, (loss, aux)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params, batch_tuple, sub,
                                       scale_state["scale"])
        grads = unscale_grads(grads, scale_state["scale"])
        finite = all_finite(grads)
        with jax.named_scope("optim.update"):
            updates, new_opt = opt.update(grads, opt_state, params)
            new_params = apply_updates(params, updates)
        params = select_tree(finite, new_params, params)
        opt_state = select_tree(finite, new_opt, opt_state)
        scale_state = update_scale_state(scale_state, finite, pol)
        return params, opt_state, rng, scale_state, loss, aux
    return faults.wrap_step_faults(jax.jit(step, donate_argnums=(0, 1, 3)))


def _dp_groups(batches, n: int):
    """Stream fixed-shape batches into groups of exactly n (one per data
    shard), grouped by leaf-shape signature so fill-adaptive K buckets
    (ClusterBatcher k_slots="auto", repro.core.kslots) never mix inside
    one stacked step — np.stack needs uniform shapes and each bucket is
    its own jit cache entry anyway. Holds at most n batches per bucket
    plus each bucket's first n, which wrap-around-fill that bucket's
    short final group (duplicating a few clusters at the epoch boundary
    keeps shapes static for jit). Never materializes the whole epoch;
    with a single bucket ("cap" policy or dense batches) this is exactly
    the old single-queue behavior."""
    pending, firsts = {}, {}
    for b in batches:
        key = tuple(tuple(leaf.shape)
                    for leaf in jax.tree_util.tree_leaves(b))
        first = firsts.setdefault(key, [])
        if len(first) < n:
            # deep-copy: a builder reusing host tile buffers
            # (ClusterBatcher reuse_tile_buffers=True) recycles b's
            # arrays a few batches later, but firsts must survive to the
            # epoch's final short group
            first.append(jax.tree_util.tree_map(np.copy, b))
        group = pending.setdefault(key, [])
        group.append(b)
        if len(group) == n:
            yield group
            pending[key] = []
    for key, group in pending.items():      # insertion (arrival) order
        if group:
            first, j = firsts[key], 0
            while len(group) < n:
                group.append(first[j % len(first)])
                j += 1
            yield group


# ----------------------------------------------------------------------
# step backends
# ----------------------------------------------------------------------
@runtime_checkable
class StepBackend(Protocol):
    """One training step, including its RNG threading and any payload
    reshaping (stacking) the step function needs.

    Contract, method by method:

    * `init(params, rng)` → the backend's state: an arbitrary pytree
      that must be (a) fully checkpointable (CheckpointManager
      save/restore round-trips it leaf-for-leaf — no closures, no
      host-only state the trajectory depends on) and (b) the ONLY
      mutable thing a step touches, so state_k+1 = step(state_k,
      payload_k) is a pure function and resume-from-checkpoint is
      bitwise-exact.
    * `stream(batches)` adapts the sampler's per-batch tuples into the
      payloads `step` consumes — the identity for a single device,
      same-shape grouping + leaf-stacking (one batch per shard) for
      data-parallel. It must be a lazy iterator (an epoch is never
      materialized; prefetch wraps it) and must not depend on wall
      clock or external RNG.
    * `step(state, payload)` → (new_state, loss, aux). The backend owns
      its RNG threading (split inside the jit, or on the host before a
      shard_map call) — the Engine never touches RNG, which is what
      keeps trajectories identical across backends wrapping the same
      math.
    * `params(state)` extracts the current model parameters for eval /
      TrainResult.

    Implementations: SingleDeviceBackend (jit per-batch step),
    ShardMapBackend (dist.steps data-parallel step). Custom backends
    (e.g. multi-host) plug into Engine/ExperimentSpec through this
    seam alone.
    """

    def init(self, params: PyTree, rng: jax.Array) -> PyTree: ...

    def stream(self, batches: Iterator) -> Iterator: ...

    def step(self, state: PyTree, payload) -> Tuple[PyTree, Any, Dict]: ...

    def params(self, state: PyTree) -> PyTree: ...


class SingleDeviceBackend:
    """The plain jit'd per-batch step (rng split inside the jit, exactly
    the pre-Engine single-device loop)."""

    # one raw sampler payload in flight per step (Engine's pool-depth
    # guard sizes tile-buffer lifetime off this)
    group_size = 1

    def __init__(self, cfg: GCNConfig, opt: Optimizer,
                 spmm: Callable = spmm_dispatch,
                 spmm_xw: Callable = spmm_xw_dispatch):
        self.opt = opt
        self._policy = policy_from_config(cfg)
        self._step = make_train_step(cfg, opt, spmm, spmm_xw)

    def init(self, params, rng):
        state = {"params": params, "opt": self.opt.init(params), "rng": rng}
        if self._policy.scaled:
            state["scale"] = init_scale_state(self._policy)
        return state

    def stream(self, batches):
        return batches

    def _args(self, state, payload):
        return tuple(state[k] for k in self._state_keys()) + (payload,)

    def _state_keys(self):
        # the step's positional state arguments, in order
        return (("params", "opt", "rng", "scale") if self._policy.scaled
                else ("params", "opt", "rng"))

    def step(self, state, payload):
        *new, loss, aux = self._step(*self._args(state, payload))
        return dict(zip(self._state_keys(), new)), loss, aux

    def lower(self, state, payload):
        """AOT-lower the step `step(state, payload)` would run (compile
        time and the compiled program, without running it)."""
        return self._step.__wrapped__.lower(*self._args(state, payload))

    def params(self, state):
        return state["params"]


class ShardMapBackend:
    """Data-parallel shard_map step (dist.steps.make_gcn_train_step):
    `stream` groups same-shape batches into stacks of one-per-data-shard
    (so fill-adaptive K buckets never mix), `step` splits the rng on the
    host and feeds the stacked payload — exactly the pre-Engine DP loop.
    """

    def __init__(self, cfg: GCNConfig, opt: Optimizer, mesh, *,
                 dp_axis: str = "data", compression=None,
                 microbatches: int = 1, compression_group_size=None,
                 spmm: Callable = spmm_dispatch,
                 spmm_xw: Callable = spmm_xw_dispatch):
        from repro.dist.steps import (init_gcn_train_state,
                                      make_gcn_train_step)
        self.opt = opt
        self.compression = compression
        self.mesh, self.dp_axis = mesh, dp_axis
        self.dsize = int(mesh.shape[dp_axis])
        # where a stacked payload lives: one batch per device of the DP
        # axis (the Engine's prefetch transfer places it there directly)
        self.batch_sharding = NamedSharding(mesh, PartitionSpec(dp_axis))
        self.microbatches = max(1, int(microbatches))
        # _dp_groups holds up to dsize*microbatches raw sampler payloads
        # before the stack copies them — that whole group must outlive
        # any tile-buffer recycling (Engine's pool-depth guard)
        self.group_size = self.dsize * self.microbatches
        self._policy = policy_from_config(cfg)
        self._init_state = init_gcn_train_state
        self._step = make_gcn_train_step(
            cfg, opt, mesh, axis_name=dp_axis, compression=compression,
            microbatches=self.microbatches,
            compression_group_size=compression_group_size, spmm=spmm,
            spmm_xw=spmm_xw)

    def init(self, params, rng):
        return {"dist": self._init_state(params, self.opt, self.mesh,
                                         axis_name=self.dp_axis,
                                         compression=self.compression,
                                         policy=self._policy),
                "rng": rng}

    def stream(self, batches):
        # leaf-wise stack (adj may be a BlockEllAdj pytree); under
        # prefetch the grouping + stacking runs on the producer thread,
        # overlapped with the device step. With microbatches=m the stack
        # is dsize*m deep — each shard scans its m batches sequentially,
        # accumulating gradients before the one sync.
        return (jax.tree_util.tree_map(lambda *ls: np.stack(ls), *group)
                for group in _dp_groups(batches,
                                        self.dsize * self.microbatches))

    def step(self, state, payload):
        rng, sub = jax.random.split(state["rng"])
        dist, loss, aux = self._step(state["dist"], sub, payload)
        return {"dist": dist, "rng": rng}, loss, aux

    def params(self, state):
        return state["dist"]["params"]


# ----------------------------------------------------------------------
# hooks
# ----------------------------------------------------------------------
_EVAL_SPLITS = ("auto", "train", "val", "test")


def resolve_eval_mask(graph, split: str,
                      warner: Optional[Callable[[str], None]] = None
                      ) -> Tuple[str, np.ndarray]:
    """Map an eval-split name to (resolved_name, mask). split="auto"
    keeps the historical behavior — val_mask unless it is missing/empty,
    then test_mask — but `warner` is called on that fallback so silent
    test-set evaluation during training is at least loud."""
    if split not in _EVAL_SPLITS:
        raise ValueError(f"eval_split must be one of {_EVAL_SPLITS}; "
                         f"got {split!r}")
    if split == "auto":
        if graph.val_mask is not None and graph.val_mask.any():
            return "val", graph.val_mask
        if warner is not None:
            warner("eval_split='auto' fell back to the TEST split "
                   "(val_mask is missing or empty) — validation scores "
                   "are test-set scores; set run.eval_split explicitly")
        return "test", graph.test_mask
    mask = getattr(graph, f"{split}_mask")
    if mask is None or not mask.any():
        raise ValueError(
            f"eval_split={split!r} but the graph's {split}_mask is "
            f"{'missing' if mask is None else 'empty'} — evaluating on "
            f"it would produce NaN scores; pick a split with nodes "
            f"(or 'auto' for the warn-on-fallback behavior)")
    return split, mask


class EvalHook:
    """Periodic full-graph evaluation. Mutates the (shared) epoch record
    in place — the Engine appends the record to history before firing
    on_epoch hooks, so `val_score`/`eval_split` land in history and in
    any checkpoint metadata written by later hooks."""

    def __init__(self, eval_graph, cfg: GCNConfig, *, every: int,
                 split: str = "auto", norm: str = "eq10",
                 diag_lambda: float = 0.0):
        if split not in _EVAL_SPLITS:
            raise ValueError(f"eval_split must be one of {_EVAL_SPLITS}; "
                             f"got {split!r}")
        if split != "auto":
            resolve_eval_mask(eval_graph, split)   # fail at build time,
            # not epochs into training, when the explicit mask is empty
        self.graph, self.cfg, self.every, self.split = \
            eval_graph, cfg, every, split
        self.norm, self.diag_lambda = norm, diag_lambda
        self._warned = False

    def _warn_once(self, msg: str):
        if not self._warned:
            self._warned = True
            warnings.warn(msg, stacklevel=4)

    def on_epoch(self, engine: "Engine", rec: Dict) -> None:
        if not self.every or (rec["epoch"] + 1) % self.every:
            return
        from repro.core.trainer import evaluate
        split, mask = resolve_eval_mask(self.graph, self.split,
                                        self._warn_once)
        rec["val_score"] = evaluate(engine.backend.params(engine.state),
                                    self.graph, self.cfg, mask,
                                    self.norm, self.diag_lambda)
        rec["eval_split"] = split
        for h in engine.hooks:
            fn = getattr(h, "on_eval", None)
            if fn is not None:
                fn(engine, rec)


class CheckpointHook:
    """Epoch-cadence checkpointing through the engine's manager.
    Cadence saves are async (CheckpointManager snapshots to host, then
    writes on a background thread, overlapped with the next epoch);
    only the preemption-path save is blocking."""

    def __init__(self, every: int = 1):
        self.every = max(1, int(every))

    def on_epoch(self, engine: "Engine", rec: Dict) -> None:
        if (rec["epoch"] + 1) % self.every == 0:
            engine.save_checkpoint(blocking=False)

    def on_fit_end(self, engine: "Engine") -> None:
        if engine.checkpoint is not None:
            engine.checkpoint.wait()


class LoggingHook:
    """The old verbose=True per-epoch print."""

    def on_epoch(self, engine: "Engine", rec: Dict) -> None:
        print({k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in rec.items()})


class PreemptionHook:
    """SIGTERM/SIGINT → finish the in-flight step, blocking checkpoint,
    clean exit (Engine.fit returns the partial TrainResult and sets
    engine.preempted). Wraps runtime.resilience.PreemptionHandler —
    signal handlers are installed only for the duration of fit()."""

    def __init__(self, handler=None):
        if handler is None:
            from repro.runtime.resilience import PreemptionHandler
            handler = PreemptionHandler()
        self.handler = handler

    def on_fit_start(self, engine: "Engine") -> None:
        self.handler.__enter__()

    def on_step(self, engine: "Engine", info: Dict) -> None:
        if self.handler.should_stop:
            engine.request_stop(reason="preempted")

    def on_fit_end(self, engine: "Engine") -> None:
        self.handler.__exit__(None, None, None)


class StopAtStepHook:
    """Test/ops helper: request a clean stop (checkpoint + exit) after
    `global_step` reaches `stop_after` steps — a deterministic stand-in
    for a mid-run kill."""

    def __init__(self, stop_after: int):
        self.stop_after = int(stop_after)

    def on_step(self, engine: "Engine", info: Dict) -> None:
        if info["global_step"] >= self.stop_after:
            engine.request_stop(reason=f"stop_at_step {self.stop_after}")


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------
class Engine:
    """ONE loop over `backend.step`, from cold start or checkpoint.

    fit(resume=True) restores the newest checkpoint in `checkpoint` (a
    runtime.CheckpointManager) and fast-forwards epoch / step-in-epoch /
    partial loss accumulators so the trajectory continues exactly where
    the saved run stopped; with no checkpoint on disk it cold-starts.
    """

    def __init__(self, batcher: Sampler, cfg: GCNConfig,
                 backend: StepBackend, *, epochs: int, seed: int = 0,
                 prefetch: Union[int, str] = 0, hooks: Sequence = (),
                 checkpoint=None, fault_plan=None,
                 max_consecutive_skipped: Optional[int] = None,
                 divergence_factor: Optional[float] = None,
                 prefetch_timeout: float = 600.0):
        if cfg.precompute_ax and not getattr(batcher, "precompute_ax",
                                             False):
            raise ValueError(
                "cfg.precompute_ax=True but the sampler was built with "
                "precompute_ax=False: the model expects the payload's "
                "features to be pre-aggregated (A'X, paper §6.2) and "
                "layer 1 would silently skip propagation on raw "
                "features. Rebuild the sampler with precompute_ax=True "
                "(ExperimentSpec.build_batcher does this automatically).")
        # prefetch="auto": start synchronous, measure the host-build /
        # device-step ratio over a warmup epoch, then pick the depth
        # (see _auto_prefetch_depth). Until measured, depth is 0.
        self.prefetch_auto = prefetch == "auto"
        self.prefetch = 0 if self.prefetch_auto else int(prefetch)
        self._auto_depth: Optional[int] = None
        self._auto_ratio: Optional[float] = None
        pool = getattr(batcher, "_tile_pool", None)
        if pool is not None:
            # TileBufferPool recycles a buffer after `depth` further
            # same-key requests; each batch makes 2 requests per ring
            # key (forward + transposed tiles share a key for square
            # cap×cap batches), so the pool holds depth//2 live batches.
            # Batches that must be simultaneously alive: the prefetch
            # queue plus the in-flight and just-built ones (single
            # device), or a full _dp_groups stack plus the one being
            # built (data parallel — raw pooled payloads are only
            # retained inside the group; firsts/stacks are copies).
            group = int(getattr(backend, "group_size", 1))
            # auto prefetch must budget for the deepest depth it may
            # ever pick, not the warmup's 0
            depth_bound = (AUTO_PREFETCH_MAX if self.prefetch_auto
                           else self.prefetch)
            need = group + 1 if group > 1 else depth_bound + 2
            live = pool.depth // 2
            if live < need:
                raise ValueError(
                    f"tile-buffer pool depth {pool.depth} holds only "
                    f"{live} live batches but this run keeps {need} in "
                    f"flight ("
                    + (f"data-parallel group of {group} + 1 being built"
                       if group > 1 else
                       f"prefetch={depth_bound} queued + 2 in flight")
                    + ") — recycled buffers would alias live payloads "
                    f"and silently corrupt training. Deepen the pool "
                    f"(TileBufferPool(depth={2 * need}) on the sampler), "
                    f"lower execution.prefetch, or disable "
                    f"batch.reuse_tile_buffers.")
        self.batcher = batcher
        self.cfg = cfg
        self.backend = backend
        self.epochs = int(epochs)
        self.seed = int(seed)
        self.hooks = list(hooks)
        self.checkpoint = checkpoint
        # fault injection + divergence guards (runtime.faults /
        # docs/robustness.md). All default OFF; the None paths add one
        # global check per step — trajectories stay bitwise-identical
        # (locked by tests/test_faults.py).
        self.fault_plan = fault_plan
        self.max_consecutive_skipped = (
            None if max_consecutive_skipped is None
            else int(max_consecutive_skipped))
        self.divergence_factor = (None if divergence_factor is None
                                  else float(divergence_factor))
        self._guards_on = (self.max_consecutive_skipped is not None
                           or self.divergence_factor is not None)
        self.prefetch_timeout = float(prefetch_timeout)
        self.diverged = False
        self.straggler = StragglerDetector()
        # does the sampler expose the cheap fast-forward seam
        # (epoch(e, start_step=k))? Third-party Samplers may predate it.
        try:
            self._start_seam = "start_step" in inspect.signature(
                self.batcher.epoch).parameters
        except (TypeError, ValueError):
            self._start_seam = False
        self.state: Optional[PyTree] = None
        self.history: List[Dict[str, float]] = []
        self.global_step = 0
        self.preempted = False
        self.stop_reason: Optional[str] = None
        self._stop = False
        self._skip_stop_checkpoint = False
        self._consec_nonfinite = 0
        self._finite_losses: List[float] = []
        # current resume point: (epoch, step_in_epoch, losses, auxes)
        self._position: Tuple[int, int, list, list] = (0, 0, [], [])
        # device scalars read back by the current epoch's record
        self._reads = 0

    # -- state ----------------------------------------------------------
    def init_state(self) -> PyTree:
        params = init_gcn(jax.random.PRNGKey(self.seed), self.cfg)
        return self.backend.init(params, jax.random.PRNGKey(self.seed + 1))

    def request_stop(self, reason: str = "requested") -> None:
        if not self._stop:
            self._stop = True
            self.stop_reason = reason

    # -- checkpointing --------------------------------------------------
    def save_checkpoint(self, blocking: bool = True) -> None:
        """Persist state + loop position. Loss/aux accumulators are
        host floats in the metadata — float() of an f32 scalar is exact,
        so the post-resume epoch record is bit-identical to an unkilled
        run's."""
        if self.checkpoint is None or self.state is None:
            return
        epoch, step_in_epoch, losses, auxes = self._position
        meta = {
            "epoch": epoch, "step_in_epoch": step_in_epoch,
            "global_step": self.global_step,
            "losses": [float(l) for l in losses],
            "auxes": [{k: float(v) for k, v in a.items()} for a in auxes],
            # snapshot: an async save json-dumps on the writer thread
            # while the loop keeps appending to self.history
            "history": [dict(h) for h in self.history],
        }
        self.checkpoint.save(self.global_step, self.state,
                             blocking=blocking, metadata=meta)

    def _try_restore(self) -> bool:
        if self.checkpoint is None:
            return False
        # newest VALID step: corrupt newer steps are quarantined with a
        # warning and we land on the previous good one — fit() then
        # re-fast-forwards the batch stream to wherever that is, which
        # the (seed, epoch)-pure streams make exact
        step = (self.checkpoint.latest_valid_step()
                if hasattr(self.checkpoint, "latest_valid_step")
                else self.checkpoint.latest_step())
        if step is None:
            return False
        template = self.init_state()
        self.state = self.checkpoint.restore(template, step=step)
        meta = self.checkpoint.read_metadata(step)
        if "history" not in meta:
            raise ValueError(
                f"checkpoint step {step} in {self.checkpoint.directory} "
                f"carries no Engine resume metadata (it was saved by a "
                f"direct CheckpointManager.save, not Engine.fit) — "
                f"restore it manually or start without resume=True")
        self.history = list(meta["history"])
        self.global_step = int(meta["global_step"])
        self._position = (int(meta["epoch"]), int(meta["step_in_epoch"]),
                          list(meta["losses"]),
                          [dict(a) for a in meta["auxes"]])
        return True

    # -- divergence guards ----------------------------------------------
    _GUARD_WINDOW = 32          # trailing finite losses the median sees
    _GUARD_WARMUP = 8           # finite steps before the explosion guard arms

    def _params_finite(self) -> bool:
        return all(
            bool(np.isfinite(np.asarray(jax.device_get(leaf))).all())
            for leaf in jax.tree_util.tree_leaves(
                self.backend.params(self.state)))

    def _check_divergence(self, loss) -> None:
        """Per-step guard, run only when a guard is configured (the
        float() here forces a device sync — keeping the default path
        free of it is part of the zero-cost guarantee)."""
        lf = float(loss)
        if not math.isfinite(lf):
            self._consec_nonfinite += 1
            lim = self.max_consecutive_skipped
            if lim is not None and self._consec_nonfinite >= lim:
                self._divergence_stop(
                    f"{self._consec_nonfinite} consecutive non-finite "
                    f"losses")
            return
        self._consec_nonfinite = 0
        fac = self.divergence_factor
        if fac is not None and len(self._finite_losses) >= \
                self._GUARD_WARMUP:
            w = self._finite_losses
            med = sorted(w)[len(w) // 2]
            if lf > fac * med:
                # loss exploded: the params that produced it are suspect
                # even if still finite — roll back to last-good
                self._divergence_stop(
                    f"loss {lf:.6g} exceeded {fac:g}x the trailing "
                    f"median {med:.6g}", restore=True)
                return
        self._finite_losses.append(lf)
        if len(self._finite_losses) > self._GUARD_WINDOW:
            del self._finite_losses[0]

    def _divergence_stop(self, reason: str, restore: bool = False) -> None:
        """Abort cleanly: keep the current state when its params are
        finite (the stop path's blocking save then persists it as
        last-good), otherwise restore the newest valid checkpoint —
        and never persist a poisoned state. The structured reason lands
        in engine.stop_reason → metrics.json."""
        self.diverged = True
        if restore or not self._params_finite():
            if self._try_restore():
                reason += ("; restored the last-good checkpoint "
                           f"(global step {self.global_step})")
            else:
                self._skip_stop_checkpoint = True
                reason += ("; no valid checkpoint to restore — final "
                           "state NOT saved")
                warnings.warn(
                    "divergence abort with no restorable checkpoint: "
                    "the returned params are the diverged ones "
                    "(configure run.checkpoint_dir to get rollback)",
                    stacklevel=3)
        self.request_stop(reason=f"divergence: {reason}")

    # -- hook plumbing --------------------------------------------------
    def _fire(self, name: str, *args) -> None:
        for h in self.hooks:
            fn = getattr(h, name, None)
            if fn is not None:
                fn(self, *args)

    # -- the loop -------------------------------------------------------
    def fit(self, resume: bool = False) -> TrainResult:
        """Run the training loop; returns TrainResult(history, params,
        seconds).

        resume=False always cold-starts from `init_state()`.
        resume=True restores the NEWEST checkpoint in the configured
        CheckpointManager and continues the exact trajectory of an
        unkilled run — mid-epoch included:

        * the state pytree (params/optimizer/RNG, whatever the backend's
          `init` built) is restored leaf-for-leaf;
        * JSON metadata restores epoch, step-in-epoch, the partial-epoch
          loss/aux accumulators and the completed history rows;
        * the batch stream is fast-forwarded by discarding the first
          `step_in_epoch` payloads: every Sampler's epoch stream is a
          pure function of (sampler seed, epoch), so the skip reproduces
          the remaining sequence exactly (cluster AND SAINT samplers —
          locked by tests/test_engine.py and tests/test_samplers.py
          over prefetch∈{0,2} and the 2-device DP backend).

        With resume=True but nothing restorable (no manager, or an
        empty directory) it warns and cold-starts; a checkpoint written
        by a bare CheckpointManager.save (no Engine metadata) raises
        instead of silently restarting the epoch.

        Robustness plumbing (docs/robustness.md): `fault_plan` is
        installed for the duration of fit (sites fire inside the step
        wrappers, prefetch and checkpoint writes); when the sampler has
        the `epoch(e, start_step=k)` seam and the backend consumes one
        raw batch per step, the fast-forward skips batch CONSTRUCTION
        instead of building-and-discarding, and a silently-crashed
        prefetch producer is rebuilt once from the same seam; the
        divergence guards (`max_consecutive_skipped`,
        `divergence_factor`) stop the run with a structured
        `stop_reason` instead of training on garbage."""
        with faults.fault_scope(self.fault_plan) \
                if self.fault_plan is not None else _NULL_CTX:
            return self._fit(resume)

    @staticmethod
    def _timed_iter(it: Iterator, acc: List[float]) -> Iterator:
        """Pass-through iterator accumulating time spent inside
        next(it) into acc[0] — measures host-side batch build (group/
        stack included, since it wraps the backend stream) during the
        auto-prefetch warmup epoch."""
        while True:
            t = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            acc[0] += time.perf_counter() - t
            yield item

    @staticmethod
    def _auto_prefetch_depth(ratio: float) -> int:
        """host_build_over_step ratio → prefetch depth. Below 5% the
        producer thread costs more than it hides (stay synchronous);
        above, queue ~2x the ratio so one builder stays ahead of
        device steps, capped at AUTO_PREFETCH_MAX (a saturated single
        producer gains nothing from a deeper queue)."""
        if ratio < 0.05:
            return 0
        return max(1, min(AUTO_PREFETCH_MAX, int(np.ceil(2.0 * ratio))))

    def _fit(self, resume: bool) -> TrainResult:
        restored = resume and self._try_restore()
        if resume and not restored:
            warnings.warn(
                "resume=True but there is nothing to restore "
                + ("(no checkpoint manager configured)"
                   if self.checkpoint is None else
                   f"(no checkpoints in {self.checkpoint.directory})")
                + " — cold-starting from epoch 0", stacklevel=2)
        if not restored:
            self.state = self.init_state()
            self.history = []
            self.global_step = 0
            self._position = (0, 0, [], [])
        self._stop = False
        self.preempted = False
        self.diverged = False
        self.stop_reason = None
        self._skip_stop_checkpoint = False
        self._consec_nonfinite = 0
        self._finite_losses = []
        start_epoch, skip_steps, losses, auxes = self._position
        # one raw batch per step → the sampler's start_step seam maps
        # 1:1 onto stream positions (a DP backend groups/stacks batches,
        # so it keeps the build-and-discard path)
        seam = (self._start_seam
                and int(getattr(self.backend, "group_size", 1)) == 1)

        if self.prefetch_auto:
            # re-measure on every fit() call — prefetch is bitwise-
            # transparent to the trajectory, so a resumed run picking a
            # different depth than the original is harmless
            self._auto_depth = None
            self._auto_ratio = None
        t0 = time.perf_counter()
        fit_error: Optional[BaseException] = None
        try:
            # inside the try so a raising on_fit_start hook still gets
            # on_fit_end cleanup (e.g. PreemptionHook's signal handlers)
            self._fire("on_fit_start")
            for epoch in range(start_epoch, self.epochs):
                start = skip_steps if (skip_steps and seam) else 0
                raw = (self.batcher.epoch(epoch, start_step=start)
                       if start else self.batcher.epoch(epoch))
                stream = self.backend.stream(b.astuple() for b in raw)
                step_in_epoch = start
                if skip_steps and not start:
                    # fast-forward a resumed mid-epoch position the slow
                    # way (no seam / DP grouping): the stream is a pure
                    # function of (batcher seed, epoch), so discarding
                    # the first k payloads reproduces the tail exactly
                    for _ in range(skip_steps):
                        next(stream, None)
                    step_in_epoch = skip_steps
                skip_steps = 0
                # auto: synchronous warmup epoch (depth 0) until the
                # build/step ratio is measured, then the tuned depth
                measuring = self.prefetch_auto and self._auto_depth is None
                effective = ((self._auto_depth or 0) if self.prefetch_auto
                             else self.prefetch)
                # a DP payload goes straight to its shards, not device 0
                transfer = (functools.partial(
                    jax.device_put,
                    device=getattr(self.backend, "batch_sharding", None))
                    if effective > 0 else None)
                build_acc = [0.0]
                step_total = 0.0
                if measuring:
                    stream = self._timed_iter(stream, build_acc)
                rebuild = None
                if seam and effective > 0:
                    # one-shot producer restart after a silent prefetch
                    # crash: rebuild the epoch tail right after the
                    # `consumed` payloads already trained on
                    def rebuild(consumed, _e=epoch, _s=step_in_epoch):
                        return (b.astuple() for b in self.batcher.epoch(
                            _e, start_step=_s + consumed))
                flagged = 0
                payloads = prefetch_iter(
                    stream, effective, transfer=transfer,
                    hang_timeout=self.prefetch_timeout, rebuild=rebuild)
                try:
                    while True:
                        t_loop = time.perf_counter()
                        with tracing.span("engine.wait"):
                            payload = next(payloads, None)
                        if payload is None:
                            break
                        with tracing.span("engine.step"):
                            t_step = time.perf_counter()
                            self.state, loss, aux = self.backend.step(
                                self.state, payload)
                            if measuring:
                                # the device's time, not the enqueue's
                                jax.block_until_ready(loss)
                                step_total += time.perf_counter() - t_step
                        losses.append(loss)
                        auxes.append(aux)
                        self.global_step += 1
                        step_in_epoch += 1
                        self._position = (epoch, step_in_epoch, losses,
                                          auxes)
                        if self._guards_on:
                            self._check_divergence(loss)
                        if faults.maybe_fail("sigterm.at_step",
                                             index=self.global_step):
                            # after the step completed, before hooks see
                            # it — exactly where a scheduler's kill
                            # usually lands
                            _signal.raise_signal(_signal.SIGTERM)
                        with tracing.span("engine.hooks"):
                            self._fire("on_step", {
                                "epoch": epoch,
                                "step_in_epoch": step_in_epoch,
                                "global_step": self.global_step,
                                "loss": loss, "aux": aux})
                        # the host loop's period: wait, step and hooks
                        if self.straggler.flag_step(time.perf_counter()
                                                    - t_loop):
                            flagged += 1
                        if self._stop:
                            break
                finally:
                    # stops a prefetch producer now, not at collection
                    payloads.close()
                if self._stop:
                    self.preempted = True
                    if not self._skip_stop_checkpoint:
                        self.save_checkpoint(blocking=True)
                    break
                rec = self._epoch_record(epoch, losses, auxes, t0, flagged)
                if self.prefetch_auto:
                    # wall-time diagnostics like "time"/"flagged_steps":
                    # resumed-run comparisons strip them the same way
                    rec["prefetch_depth"] = effective
                    if measuring and step_total > 0:
                        self._auto_ratio = build_acc[0] / step_total
                        self._auto_depth = self._auto_prefetch_depth(
                            self._auto_ratio)
                        rec["host_build_over_step"] = self._auto_ratio
                self.history.append(rec)
                self._position = (epoch + 1, 0, [], [])
                losses, auxes = [], []
                with tracing.span("engine.hooks"):
                    self._fire("on_epoch", rec)
                if self._stop:          # stop requested by an epoch hook
                    self.preempted = True
                    if not self._skip_stop_checkpoint:
                        self.save_checkpoint(blocking=True)
                    break
        except BaseException as e:
            fit_error = e
            raise
        finally:
            try:
                self._fire("on_fit_end")
            finally:
                if self.checkpoint is not None:
                    # surface a failed FINAL async save (its error is
                    # otherwise only raised on the next save/wait — i.e.
                    # never) without masking an in-flight fit exception
                    try:
                        self.checkpoint.wait()
                    except BaseException as we:  # noqa: BLE001
                        if fit_error is None:
                            raise
                        warnings.warn(
                            f"a background checkpoint save also failed "
                            f"during error teardown: {we!r}",
                            stacklevel=2)
        return TrainResult(history=self.history,
                           params=self.backend.params(self.state),
                           seconds=time.perf_counter() - t0)

    def _read_back(self, x) -> float:
        """One device-to-host read of a scalar, counted in `_reads` for
        the `engine.epoch_end` span's `syncs` stat."""
        self._reads += 1
        return float(x)

    def _epoch_record(self, epoch: int, losses, auxes, t0,
                      flagged: int = 0) -> Dict:
        self._reads = 0
        with tracing.span("engine.epoch_end", steps=len(losses)) as span:
            read = self._read_back
            rec = {"epoch": epoch,
                   "loss": float(np.mean([read(l) for l in losses])),
                   "time": time.perf_counter() - t0,
                   # straggler diagnostic (StragglerDetector.flag_step):
                   # wall-time-derived, so resumed-run histories may
                   # differ here (tests strip it like "time")
                   "flagged_steps": flagged}
            if self.cfg.multilabel:
                tp = sum(read(a["tp"]) for a in auxes)
                fp = sum(read(a["fp"]) for a in auxes)
                fn = sum(read(a["fn"]) for a in auxes)
                rec["train_f1"] = micro_f1(tp, fp, fn)
            else:
                c = sum(read(a["correct"]) for a in auxes)
                n = sum(read(a["n"]) for a in auxes)
                rec["train_acc"] = c / max(n, 1.0)
            span.set_metadata(syncs=self._reads)
        return rec
