"""Sharded step builders.

LM path (pjit / GSPMD): `make_train_step` closes over a CellPolicy and
returns a pure (state, batch) -> (state, metrics) function. Sharding
comes entirely from the jit in/out shardings built with
repro.dist.sharding — the step body only adds activation constraints
and the microbatch gradient-accumulation loop. `spec_train_state` gives
the TensorSpec tree for the full train state (params + Adam moments), so
state materialization / AOT shapes / shardings all derive from one tree.

GCN path (shard_map): `make_gcn_train_step` runs the paper's training
step data-parallel — each shard of the 'data' axis consumes its own
stack of cluster batches (the block-diagonal objective of Eq. 6/7
decomposes exactly across clusters), and gradients sync with an optional
compressed all-reduce (repro.dist.compression). The returned step is
shape-polymorphic over the block-ELL K of sparse batches: with
fill-adaptive k_slots buckets (repro.core.kslots) each bucket is one
entry in jax.jit's shape-keyed cache — at most len(buckets) compiles —
and the trainer's DP stacker only ever groups same-bucket batches.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.gcn import GCNConfig, gcn_loss
from repro.core.precision import (all_finite, init_scale_state,
                                  policy_from_config, scale_loss,
                                  select_tree, unscale_grads,
                                  update_scale_state)
from repro.kernels.ops import spmm as spmm_dispatch
from repro.kernels.ops import spmm_xw as spmm_xw_dispatch
from repro.dist.compression import (DEFAULT_GROUP_SIZE, bf16_psum_mean,
                                    compressed_psum_mean, psum_mean)
from repro.dist.sharding import CellPolicy
from repro.models.config import ArchConfig
from repro.models.lm import (decode_step, encode, lm_loss, prefill,
                             spec_params)
from repro.models.spec import TensorSpec, map_specs
from repro.runtime import faults
from repro.nn.optim import (AdamState, Optimizer, apply_updates,
                            global_norm)

PyTree = Any


# ----------------------------------------------------------------------
# train state (LM)
# ----------------------------------------------------------------------
def spec_train_state(cfg: ArchConfig) -> Dict:
    """TensorSpec tree for {params, step, mu, nu} (Adam-family optimizer
    state — what adamw() builds; sgd reuses the slots it needs)."""
    params = spec_params(cfg)
    moment = lambda s: TensorSpec(s.shape, s.axes, init="zeros",
                                  dtype=jnp.float32)
    return {"params": params,
            "step": TensorSpec((), (), init="zeros", dtype=jnp.int32),
            "mu": map_specs(moment, params),
            "nu": map_specs(moment, params)}


def _constrain(x, spec):
    """with_sharding_constraint that degrades to a no-op only when no
    mesh context is active (plain single-device tests) — a bad spec
    under a real mesh still raises."""
    if spec is None:
        return x
    from repro.models.layers import ambient_axes
    if ambient_axes() == (None, None):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _split_microbatches(batch: PyTree, m: int, batch_axis) -> PyTree:
    """(B, ...) -> (m, B//m, ...) per leaf, re-pinning the sharded batch
    dim (now dim 1) so the reshape doesn't derail SPMD propagation."""
    def split(x):
        if x.shape[0] % m:
            raise ValueError(
                f"global batch {x.shape[0]} not divisible by "
                f"microbatches={m}")
        y = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        if batch_axis is not None:
            y = _constrain(y, P(None, batch_axis,
                                *([None] * (y.ndim - 2))))
        return y
    return jax.tree_util.tree_map(split, batch)


# ----------------------------------------------------------------------
# LM steps
# ----------------------------------------------------------------------
def make_train_step(cfg: ArchConfig, policy: CellPolicy, opt: Optimizer,
                    act_spec=None) -> Callable:
    """(state, batch) -> (state, metrics). Loss/remat/chunking follow the
    policy; with microbatches > 1, gradients accumulate over an on-device
    scan (the batch axis stays sharded within each microbatch)."""
    batch_axis = act_spec[0] if act_spec is not None and len(act_spec) \
        else None

    def loss_fn(params, mb):
        loss, metrics = lm_loss(params, cfg, mb, remat=policy.remat,
                                loss_chunk=policy.loss_chunk,
                                act_spec=act_spec)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        params = state["params"]
        m = policy.microbatches
        if m > 1:
            mbs = _split_microbatches(batch, m, batch_axis)

            def mb_fn(carry, mb):
                g_acc, loss_acc, acc_acc = carry
                (loss, metrics), grads = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                return (g_acc, loss_acc + loss,
                        acc_acc + metrics["acc"]), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum, acc_sum), _ = jax.lax.scan(
                mb_fn, (g0, jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / m, grads)
            loss, acc = loss_sum / m, acc_sum / m
        else:
            (loss, metrics), grads = grad_fn(params, batch)
            acc = metrics["acc"]

        opt_state = AdamState(step=state["step"], mu=state["mu"],
                              nu=state["nu"])
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        new_state = {"params": params, "step": opt_state.step,
                     "mu": opt_state.mu, "nu": opt_state.nu}
        metrics = {"loss": loss, "acc": acc,
                   "grad_norm": global_norm(grads)}
        return new_state, metrics

    return step


def make_prefill_step(cfg: ArchConfig, policy: CellPolicy,
                      act_spec=None) -> Callable:
    """(params, batch, caches) -> (last-position logits, caches)."""
    def step(params, batch, caches):
        return prefill(params, cfg, batch, caches, remat=policy.remat,
                       act_spec=act_spec)
    return step


def make_decode_step(cfg: ArchConfig, policy: CellPolicy,
                     act_spec=None) -> Callable:
    """(params, tokens (B,1), caches, pos) -> (next greedy token (B,1),
    logits (B,V), caches)."""
    def step(params, tokens, caches, pos):
        logits, caches = decode_step(params, cfg, tokens, caches, pos,
                                     act_spec=act_spec)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return nxt, logits, caches
    return step


def make_encode_step(cfg: ArchConfig, policy: CellPolicy,
                     act_spec=None) -> Callable:
    """Encoder-only forward: (params, batch) -> frame logits (B,S,V)."""
    def step(params, batch):
        return encode(params, cfg, batch, remat=policy.remat,
                      act_spec=act_spec)
    return step


# ----------------------------------------------------------------------
# GCN data-parallel step (shard_map over cluster batches)
# ----------------------------------------------------------------------
def gcn_state_shardings(mesh, axis_name: str, compression=None,
                        policy=None) -> Dict[str, NamedSharding]:
    """Placement of each DP train-state entry: everything replicated
    except the error-feedback residuals, one row per shard."""
    rep = NamedSharding(mesh, P())
    out = {"params": rep, "opt": rep}
    if isinstance(compression, int):
        out["err"] = NamedSharding(mesh, P(axis_name))
    if policy is not None and policy.scaled:
        out["scale"] = rep
    return out


def init_gcn_train_state(params: PyTree, opt: Optimizer, mesh, *,
                         axis_name: str = "data", compression=None,
                         policy=None) -> Dict:
    """{params, opt} (+ per-shard error-feedback residuals, stacked on a
    leading shard axis, when int compression is on; + replicated loss
    "scale" state when the precision policy uses loss scaling), placed
    on the mesh by `gcn_state_shardings`."""
    nshards = int(mesh.shape[axis_name])
    state = {"params": params, "opt": opt.init(params)}
    if isinstance(compression, int):
        state["err"] = jax.tree_util.tree_map(
            lambda p: jnp.zeros((nshards,) + p.shape, jnp.float32), params)
    if policy is not None and policy.scaled:
        state["scale"] = init_scale_state(policy)
    return jax.device_put(state, gcn_state_shardings(mesh, axis_name,
                                                     compression, policy))


def make_gcn_train_step(cfg: GCNConfig, opt: Optimizer, mesh, *,
                        axis_name: str = "data", compression=None,
                        microbatches: int = 1, compression_group_size=None,
                        spmm: Callable = spmm_dispatch,
                        spmm_xw: Callable = spmm_xw_dispatch) -> Callable:
    """Data-parallel Cluster-GCN step over stacked cluster batches.

    The returned jit'd function maps
        (state, rng, batch_stacked) -> (state, loss, aux)
    where every `batch_stacked` leaf has leading dim G = mesh 'data' size
    × clusters-per-shard (a ClusterBatch.astuple() stack; with a
    sparse_adj batcher the adj leaf is a BlockEllAdj pytree whose leaves
    stack/shard the same way, and each shard's Â·(XW) runs the
    differentiable block-ELL spmm). Each shard takes the gradient of the
    mean loss over its own batches (dropout rng folded per shard), then
    gradients mean-all-reduce across `axis_name`:
      compression=None   exact fp32 psum
      compression="bf16" bf16 wire format
      compression=4|8    int4/int8 symmetric quant + error feedback,
                         with per-group scales every
                         `compression_group_size` elements (None = the
                         compression module's DEFAULT_GROUP_SIZE)
    Loss is the global mean, aux the global sums (micro-F1 parts).

    microbatches=m > 1 splits each shard's q_local batches into m
    sequential scan chunks, accumulating fp32 gradients between the
    single all-reduce — the activation-memory knob for deep GCNs (only
    one chunk's backward graph is live at a time). m=1 (default) keeps
    the one-vmap path bitwise-identical to the pre-microbatch step.

    Loss scaling (cfg.loss_scaling via repro.core.precision): the
    gradient is taken of loss·scale and unscaled BEFORE the all-reduce,
    so error-feedback residuals live in true gradient units; an
    overflowed shard's inf/nan reaches every shard through the reduce
    (quantization maps inf scale to nan payloads), making the
    skip-update decision — params/opt/err frozen, dynamic scale backed
    off — consistent across the mesh by construction.
    """
    if compression not in (None, "bf16", 4, 8):
        raise ValueError(
            f"compression must be None, 'bf16', 4 or 8; got {compression!r}")
    m = int(microbatches)
    if m < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    gsize = DEFAULT_GROUP_SIZE if compression_group_size is None \
        else int(compression_group_size)
    if gsize < 1:
        raise ValueError(f"compression_group_size must be >= 1, got "
                         f"{compression_group_size}")
    nshards = int(mesh.shape[axis_name])
    bits = compression if isinstance(compression, int) else None
    pol = policy_from_config(cfg)
    aux_keys = ("tp", "fp", "fn", "n") if cfg.multilabel \
        else ("correct", "n")

    def shard_fn(state, rng, batch):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
        q_local = jax.tree_util.tree_leaves(batch)[0].shape[0]
        params = state["params"]
        scale = state["scale"]["scale"] if pol.scaled else None

        def chunk_loss(p, chunk, keys):
            losses, auxes = jax.vmap(
                lambda bt, k: gcn_loss(p, bt, cfg, train=True, rng=k,
                                       spmm=spmm,
                                       spmm_xw=spmm_xw))(chunk, keys)
            loss = losses.mean()
            out = scale_loss(loss, scale) if pol.scaled else loss
            return out, (loss, auxes)

        grad_fn = jax.value_and_grad(chunk_loss, has_aux=True)

        if m > 1:
            if q_local % m:
                raise ValueError(
                    f"{q_local} local batches not divisible by "
                    f"microbatches={m}")
            mb = q_local // m
            ks = jax.random.split(rng, q_local)
            ks = ks.reshape((m, mb) + ks.shape[1:])
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((m, mb) + x.shape[1:]), batch)

            def mb_fn(carry, xs):
                g_acc, loss_acc, aux_acc = carry
                chunk, k = xs
                (_, (loss, auxes)), grads = grad_fn(params, chunk, k)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                aux_acc = {kk: aux_acc[kk] + auxes[kk].sum()
                           for kk in aux_acc}
                return (g_acc, loss_acc + loss, aux_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            aux0 = {kk: jnp.zeros((), jnp.float32) for kk in aux_keys}
            (grads, loss_sum, aux_local), _ = jax.lax.scan(
                mb_fn, (g0, jnp.zeros((), jnp.float32), aux0), (mbs, ks))
            grads = jax.tree_util.tree_map(lambda g: g / m, grads)
            loss = loss_sum / m
        else:
            (_, (loss, auxes)), grads = grad_fn(
                params, batch, jax.random.split(rng, q_local))
            aux_local = {kk: v.sum() for kk, v in auxes.items()}

        if pol.scaled:
            # before the reduce: residuals carry true-unit gradients,
            # and an inf scale turns into nan payloads the psum spreads
            grads = unscale_grads(grads, scale)

        new_state = dict(state)
        with jax.named_scope("dp.allreduce"):
            if bits is not None:
                flat_g, treedef = jax.tree_util.tree_flatten(grads)
                flat_e = jax.tree_util.tree_leaves(state["err"])
                synced = [compressed_psum_mean(g, e[0], axis_name,
                                               bits=bits, group_size=gsize)
                          for g, e in zip(flat_g, flat_e)]
                grads = jax.tree_util.tree_unflatten(
                    treedef, [s[0] for s in synced])
                new_state["err"] = jax.tree_util.tree_unflatten(
                    treedef, [s[1][None] for s in synced])
            elif compression == "bf16":
                grads = jax.tree_util.tree_map(
                    lambda g: bf16_psum_mean(g, axis_name), grads)
            else:
                grads = jax.tree_util.tree_map(
                    lambda g: psum_mean(g, axis_name), grads)

        # identical on every shard after the all-reduce
        with jax.named_scope("optim.update"):
            updates, opt_state = opt.update(grads, state["opt"], params)
            new_params = apply_updates(params, updates)
        if pol.scaled:
            # post-sync grads are nan everywhere if ANY shard
            # overflowed, so the skip is mesh-consistent
            finite = all_finite(grads)
            new_state["params"] = select_tree(finite, new_params, params)
            new_state["opt"] = select_tree(finite, opt_state, state["opt"])
            if bits is not None:
                new_state["err"] = select_tree(finite, new_state["err"],
                                               state["err"])
            new_state["scale"] = update_scale_state(state["scale"],
                                                    finite, pol)
        else:
            new_state["params"] = new_params
            new_state["opt"] = opt_state

        loss = psum_mean(loss, axis_name)
        aux = {kk: jax.lax.psum(v, axis_name)
               for kk, v in aux_local.items()}
        return new_state, loss, aux

    state_sh = gcn_state_shardings(mesh, axis_name, compression, pol)
    state_spec = {k: s.spec for k, s in state_sh.items()}
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(state_spec, P(), P(axis_name)),
                       out_specs=(state_spec, P(), P()),
                       check_vma=False)
    # explicit placements, so no argument is staged on device 0 first:
    # one stacked batch per device along the DP axis, state per
    # gcn_state_shardings
    rep = NamedSharding(mesh, P())
    # step.nonfinite_loss injection seam (runtime.faults): transparent
    # passthrough unless a FaultPlan is installed — the stacked batch is
    # the last argument, same as the single-device step
    return faults.wrap_step_faults(jax.jit(
        fn, in_shardings=(state_sh, rep, NamedSharding(mesh, P(axis_name))),
        out_shardings=(state_sh, rep, rep), donate_argnums=(0,)))
