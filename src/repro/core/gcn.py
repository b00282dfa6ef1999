"""GCN model (paper Eq. 1/8/9/10/11) as pure-JAX functions on dense
cluster-batch adjacency blocks.

The per-batch compute is exactly the paper's: Z^{l+1} = Â (X^l W^l),
X^{l+1} = σ(Z^{l+1}); Â is the re-normalized q-cluster union block built
host-side by ClusterBatcher. The Â·H product is the kernel hot-spot — it
dispatches through the adjacency-polymorphic `spmm` (repro.kernels.ops):
a dense Â keeps the XLA matmul; a BlockEllAdj batch (ClusterBatcher
sparse_adj=True) routes to the differentiable block-ELL Pallas product
whose backward runs on the host-built transposed tiles.

With `arch="gat"` the same layer loop runs graph attention layers
(repro.core.gat) on the dense batch Â, whose non-zeros are the mask.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gat
from repro.core.precision import policy_from_config
from repro.kernels.ops import spmm as spmm_dispatch
from repro.kernels.ops import spmm_xw as spmm_xw_dispatch
from repro.nn.core import glorot, zeros_init

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    in_dim: int
    hidden_dim: int
    out_dim: int
    num_layers: int = 3
    dropout: float = 0.2          # paper §4: dropout 20%
    residual: bool = False        # paper Eq. 8
    multilabel: bool = False      # PPI/Amazon: sigmoid BCE; else softmax CE
    layernorm: bool = True        # used by the deep-GCN experiments
    precompute_ax: bool = False   # paper §6.2: A'X arrives pre-aggregated
                                  # in the batch payload (subgraph_payload)
                                  # and layer 1 skips its propagation
    precision: str = "fp32"       # compute dtype ("fp32"|"bf16"); params
                                  # and matmul accumulators stay fp32
    loss_scaling: str = "none"    # "none" | "static" | "dynamic"
    loss_scale: float = 2.0 ** 15  # initial (static: constant) scale
    remat: bool = False           # jax.checkpoint over layer chunks
    remat_chunk: int = 2          # layers per remat chunk
    fuse_spmm: bool = False       # route each layer's Â·(XW+b) through
                                  # the fused one-pass kernel seam
                                  # (ops.spmm_xw) instead of matmul-then-
                                  # spmm; same math, no XW HBM round-trip
    arch: str = "gcn"             # "gcn" | "gat" (repro.core.gat)
    heads: Optional[Tuple[int, ...]] = None  # gat: heads of each layer;
                                  # hidden_dim is then the width per head

    @property
    def dims(self):
        ds = [self.in_dim] + [self.hidden_dim] * (self.num_layers - 1) \
             + [self.out_dim]
        return list(zip(ds[:-1], ds[1:]))


def init_gcn(key, cfg: GCNConfig) -> PyTree:
    if cfg.arch == "gat":
        return gat.init_gat(key, cfg)
    params = {"layers": []}
    for i, (din, dout) in enumerate(cfg.dims):
        key, k1 = jax.random.split(key)
        layer = {"w": glorot(k1, (din, dout)), "b": jnp.zeros((dout,))}
        if cfg.layernorm and i < cfg.num_layers - 1:
            layer["ln_scale"] = jnp.ones((dout,))
        params["layers"].append(layer)
    return params


def _layernorm(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def gcn_forward(params: PyTree, adj, x: jnp.ndarray,
                cfg: GCNConfig, *, train: bool = False,
                rng: Optional[jax.Array] = None,
                spmm: Callable = spmm_dispatch,
                spmm_xw: Callable = spmm_xw_dispatch) -> jnp.ndarray:
    """Returns final-layer logits Z^{(L)}, always fp32 (no activation on
    the last layer).

    Precision (cfg.precision via repro.core.precision.PrecisionPolicy):
    activations and matmul operands run in the policy's compute dtype;
    every matmul accumulates fp32 (preferred_element_type here, the fp32
    VMEM scratch inside the block-ELL kernel) and layernorm statistics
    are fp32. With the default fp32 policy every cast is a no-op and the
    jaxpr is bitwise-identical to the pre-policy forward.

    Memory (cfg.remat / cfg.remat_chunk): layers are grouped into chunks
    of `remat_chunk` and each chunk is wrapped in jax.checkpoint, so the
    backward pass holds one chunk boundary per chunk instead of every
    layer's activations — the knob that lets 8-10-layer GCNs fit.

    Architecture (cfg.arch): "gat" runs `gat.gat_layer` in place of
    Â(XW + b), masked by the dense Â's non-zeros and its diagonal, with
    the identity skip and ELU between layers and the heads averaged at
    the output; dropout, remat and the precision policy are shared.
    """
    pol = policy_from_config(cfg)
    cd = pol.compute_dtype
    layers = params["layers"]
    n = len(layers)
    need_dropout = train and cfg.dropout > 0
    # per-layer dropout keys, pre-split with the SAME sequential
    # rng, sub = split(rng) chain the un-chunked loop used — keys are
    # bitwise-identical, and hoisting them out of the layer loop is what
    # lets remat chunks close over explicit key arguments
    keys = []
    for _ in range(n):
        if need_dropout:
            rng, sub = jax.random.split(rng)
            keys.append(sub)
        else:
            keys.append(None)

    if cfg.arch == "gat":
        with jax.named_scope("gat.attention"):
            mask = gat.attention_mask(adj)

    # named scopes (repro.runtime.tracing) label the device ops of each
    # part of a layer in a profiler trace, backward ops included
    def layer_fn(i, h, layer, key):
        if need_dropout:
            with jax.named_scope("gcn.dropout"):
                keep = 1.0 - cfg.dropout
                h = h * jax.random.bernoulli(key, keep, h.shape) / keep
        if cfg.arch == "gat":
            z = gat.gat_layer(layer, h, mask, concat=i < n - 1,
                              compute_dtype=cd)
            if i < n - 1:
                with jax.named_scope("gcn.activation"):
                    z = gat.activate(z, h, cfg.residual)
            return z.astype(cd)
        propagate = not (i == 0 and cfg.precompute_ax)
        if cfg.fuse_spmm and propagate:
            # fused Â·(XW + b): one seam, no XW materialization between
            # the two products. Same math contract as the unfused branch
            # (operands in cd, fp32 accumulation, fp32 bias add) — in
            # fp32 the two branches are value-identical.
            with jax.named_scope("gcn.xw_aggregate"):
                z = spmm_xw(adj, h.astype(cd), layer["w"], layer["b"])
        else:
            with jax.named_scope("gcn.xw"):
                z = (jnp.matmul(h.astype(cd), layer["w"].astype(cd),
                                preferred_element_type=jnp.float32)
                     + layer["b"]).astype(cd)
            if propagate:                # Â (XW): (b, b)·(b, F')
                with jax.named_scope("gcn.aggregate"):
                    z = spmm(adj, z)
        if i < n - 1:
            with jax.named_scope("gcn.activation"):
                if cfg.residual and z.shape == h.shape:
                    z = z + h.astype(z.dtype)        # paper Eq. 8
                z = jax.nn.relu(z)
                if cfg.layernorm:
                    z = _layernorm(z.astype(jnp.float32),
                                   layer["ln_scale"]).astype(cd)
        return z

    def chunk_fn(h, chunk_layers, chunk_keys, start):
        for j, (layer, key) in enumerate(zip(chunk_layers, chunk_keys)):
            h = layer_fn(start + j, h, layer, key)
        return h

    h = x.astype(cd)
    if cfg.remat:
        chunk = max(1, int(cfg.remat_chunk))
        for s in range(0, n, chunk):
            h = jax.checkpoint(
                lambda h, ls, ks, s=s: chunk_fn(h, ls, ks, s))(
                h, layers[s:s + chunk], keys[s:s + chunk])
    else:
        for i in range(n):
            h = layer_fn(i, h, layers[i], keys[i])
    return h.astype(jnp.float32)


def gcn_loss(params: PyTree, batch_tuple, cfg: GCNConfig, *,
             train: bool = True, rng=None, spmm: Callable = spmm_dispatch,
             spmm_xw: Callable = spmm_xw_dispatch):
    """(loss, aux) on a ClusterBatch.astuple(). aux carries micro-F1 parts.

    With cfg.precompute_ax the A'X product is NOT recomputed here — the
    payload builder (core.batching.subgraph_payload) already aggregated
    the features once on the host (paper §6.2), and layer 1 consumes
    them directly. Samplers built with precompute_ax=False while the
    model expects pre-aggregated features are caught loudly by
    Engine/train_cluster_gcn, not silently mis-trained here.
    """
    adj, feats, labels, node_mask, loss_mask, num_real = batch_tuple
    logits = gcn_forward(params, adj, feats, cfg, train=train, rng=rng,
                         spmm=spmm, spmm_xw=spmm_xw)
    with jax.named_scope("gcn.loss"):
        denom = jnp.maximum(loss_mask.sum(), 1.0)
        if cfg.multilabel:
            y = labels.astype(jnp.float32)
            ll = jnp.maximum(logits, 0) - logits * y + jnp.log1p(
                jnp.exp(-jnp.abs(logits)))
            loss = (ll.sum(-1) * loss_mask).sum() / denom
            pred = (logits > 0).astype(jnp.float32)
            tp = (pred * y * loss_mask[:, None]).sum()
            fp = (pred * (1 - y) * loss_mask[:, None]).sum()
            fn = ((1 - pred) * y * loss_mask[:, None]).sum()
            aux = {"tp": tp, "fp": fp, "fn": fn, "n": denom}
        else:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
            loss = (nll * loss_mask).sum() / denom
            correct = (logits.argmax(-1) == labels).astype(jnp.float32)
            aux = {"correct": (correct * loss_mask).sum(), "n": denom}
        return loss, aux


def micro_f1(tp: float, fp: float, fn: float) -> float:
    denom = 2 * tp + fp + fn
    return float(2 * tp / denom) if denom > 0 else 0.0
