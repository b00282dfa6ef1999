"""Graph attention layers (Veličković et al., ICLR 2018, arXiv:1710.10903)
on Cluster-GCN batches.

Per head k of a layer, with W^k h the head's slice of the one X·W
product:
    s_i = a_l^k · W^k h_i,   t_j = a_r^k · W^k h_j
    e_ij = LeakyReLU_0.2(s_i + t_j)
    α_ij = softmax_j(e_ij)   over j ∈ N(i) ∪ {i}
    z_i^k = Σ_j α_ij W^k h_j + b^k
Hidden layers concatenate their heads; the output layer averages them.
`gcn_forward` (repro.core.gcn) runs the layer loop for `arch="gat"`:
the skip connection, ELU and dropout live there, beside the GCN's.

Two forms of the same layer:
  * `gat_layer`: a batch's dense (cap, cap) Â gives the pattern; the
    attention is a masked softmax over the whole block, so its work is
    that of the cap² block and not of the batch's edges;
  * `edge_layer`: an edge list with a segment softmax, for the full
    graph (`full_graph_logits`), whose dense mask could not exist.
Masked logits take the dtype's lowest finite value, so a masked entry's
α is exactly 0 and no NaN can arise; the diagonal is always unmasked, so
no row's softmax is empty (padding rows attend to themselves alone).
"""
from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.core import glorot

PyTree = Any

# the architecture's constant, not a knob
NEGATIVE_SLOPE = 0.2


def layer_shapes(cfg) -> List[Tuple[int, int, int]]:
    """(input width, heads, width per head) of each layer: hidden layers
    take `hidden_dim` per head and concatenate, the last layer takes
    `out_dim` per head and averages."""
    heads = tuple(cfg.heads)
    din, out = cfg.in_dim, []
    for i, k in enumerate(heads):
        f = cfg.out_dim if i == len(heads) - 1 else cfg.hidden_dim
        out.append((din, k, f))
        din = k * f
    return out


def init_gat(key, cfg) -> PyTree:
    """Glorot weights for W (din, K·F') and for each head's attention
    vectors a_l, a_r (K, F'; each head's a (F', 1) matrix), zero
    biases."""
    params = {"layers": []}
    for din, k, f in layer_shapes(cfg):
        key, kw, kl, kr = jax.random.split(key, 4)
        params["layers"].append({
            "w": glorot(kw, (din, k * f)),
            "a_l": glorot(kl, (k, f, 1))[..., 0],
            "a_r": glorot(kr, (k, f, 1))[..., 0],
            "b": jnp.zeros((k * f,))})
    return params


def attention_mask(adj: jnp.ndarray) -> jnp.ndarray:
    """(cap, cap) bool: the batch Â's non-zeros and the diagonal."""
    n = adj.shape[-1]
    return (adj != 0) | jnp.eye(n, dtype=bool)


def _heads(layer, wh):
    k, f = layer["a_l"].shape
    whk = wh.reshape(wh.shape[0], k, f)
    return (whk, jnp.einsum("nkf,kf->nk", whk, layer["a_l"]),
            jnp.einsum("nkf,kf->nk", whk, layer["a_r"]))


def _combine(layer, z, concat: bool):
    """(n, K, F') head outputs plus bias: concatenated or averaged."""
    k, f = layer["a_l"].shape
    z = z + layer["b"].reshape(k, f)
    return z.reshape(z.shape[0], k * f) if concat else z.mean(1)


def gat_layer(layer: PyTree, h: jnp.ndarray, mask: jnp.ndarray, *,
              concat: bool, compute_dtype=jnp.float32) -> jnp.ndarray:
    """One layer on a dense batch: h (cap, din), mask (cap, cap) from
    `attention_mask`. Matmul operands run in `compute_dtype` with fp32
    accumulation; logits and the softmax stay fp32."""
    cd = compute_dtype
    with jax.named_scope("gat.xw"):
        wh = jnp.matmul(h.astype(cd), layer["w"].astype(cd),
                        preferred_element_type=jnp.float32)
        whk, s, t = _heads(layer, wh)
    with jax.named_scope("gat.attention"):
        e = jax.nn.leaky_relu(s.T[:, :, None] + t.T[:, None, :],
                              NEGATIVE_SLOPE)              # (K, cap, cap)
        e = jnp.where(mask, e, jnp.finfo(e.dtype).min)
        alpha = jax.nn.softmax(e, axis=-1)
    with jax.named_scope("gat.aggregate"):
        z = jnp.einsum("kij,jkf->ikf", alpha.astype(cd), whk.astype(cd),
                       preferred_element_type=jnp.float32)
        return _combine(layer, z, concat)


def activate(z: jnp.ndarray, h: jnp.ndarray, residual: bool
             ) -> jnp.ndarray:
    """A hidden layer's output: the identity skip where `residual` and
    the widths match, then ELU."""
    if residual and z.shape == h.shape:
        z = z + h.astype(z.dtype)
    return jax.nn.elu(z)


def edge_list(indptr, indices, data) -> Tuple[np.ndarray, np.ndarray]:
    """(dst, src) of a CSR graph's non-zero off-diagonal entries plus one
    self-loop per node: the pattern `attention_mask` gives a batch."""
    n = len(indptr) - 1
    dst = np.repeat(np.arange(n), np.diff(indptr))
    src = np.asarray(indices)
    keep = (np.asarray(data) != 0) & (dst != src)
    loops = np.arange(n)
    return (np.concatenate([dst[keep], loops]).astype(np.int32),
            np.concatenate([src[keep], loops]).astype(np.int32))


def edge_layer(layer: PyTree, h: jnp.ndarray, dst: jnp.ndarray,
               src: jnp.ndarray, *, concat: bool) -> jnp.ndarray:
    """One layer over an edge list (every node has its self-loop), fp32:
    a segment softmax over each node's in-edges, then per head a segment
    sum of α-weighted messages (one head's (E, F') at a time)."""
    n = h.shape[0]
    whk, s, t = _heads(layer, h @ layer["w"])
    e = jax.nn.leaky_relu(s[dst] + t[src], NEGATIVE_SLOPE)   # (E, K)
    p = jnp.exp(e - jax.ops.segment_max(e, dst, n)[dst])
    alpha = p / jax.ops.segment_sum(p, dst, n)[dst]
    z = jnp.stack([jax.ops.segment_sum(alpha[:, k, None] * whk[src, k],
                                       dst, n)
                   for k in range(whk.shape[1])], axis=1)
    return _combine(layer, z, concat)


_edge_layer = jax.jit(edge_layer, static_argnames=("concat",))


def full_graph_logits(params: PyTree, graph, residual: bool) -> np.ndarray:
    """Exact forward of a CSR `graph` (with features) over its edge list,
    layer by layer on the default device; final-layer logits on the
    host."""
    dst, src = (jnp.asarray(a) for a in edge_list(graph.indptr,
                                                   graph.indices,
                                                   graph.data))
    h = jnp.asarray(graph.features, jnp.float32)
    layers = params["layers"]
    for i, layer in enumerate(layers):
        last = i == len(layers) - 1
        z = _edge_layer(layer, h, dst, src, concat=not last)
        h = z if last else activate(z, h, residual)
    return np.asarray(h)
