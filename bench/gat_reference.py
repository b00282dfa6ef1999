"""Plain fp32 reference of the first training steps of a graph attention
network (Veličković et al., ICLR 2018, arXiv:1710.10903) on Cluster-GCN
batches.

It states the model as an edge list with a segment softmax, where the
program runs a dense masked softmax over the padded (cap, cap) batch
block, and imports nothing from the program under test. A batch is its
real nodes: the induced subgraph's edges (weight non-zero, no self
loops) plus one self-loop per node, their features, labels and training
mask, padded to a size class by nodes and edges that no real node sees.
Per layer and head k, for each edge j -> i:

    s_i = a_l^k · W^k h_i,   t_j = a_r^k · W^k h_j
    e_ij = LeakyReLU_0.2(s_i + t_j)
    α_ij = exp(e_ij - max_i) / Σ_{j'} exp(e_ij' - max_i)
    z_i^k = Σ_j α_ij W^k h_j + b^k

Hidden layers concatenate the heads, add the input where widths match
(the skip), then ELU; the output layer averages its heads. The loss is
the multilabel sigmoid cross-entropy summed over labels, averaged over
the batch's training nodes; a step of several batches (data-parallel)
takes the mean of their losses. Adam without weight decay. Everything
runs in float32 at "highest" matmul precision.

Departures from the published model, shared with the program: the
generated PPI-shaped graph and four of its clusters stand in for PPI's
24 graphs and the paper's batches of 2; a bias per head; the identity
skip only where widths match (the authors' code also projects the input
where they differ); no dropout.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import Graph

NEGATIVE_SLOPE = 0.2
# a batch's nodes and edges are padded up to multiples of these, so that
# the jitted step compiles once per size class and not once per batch
NODE_BUCKET = 2048
EDGE_BUCKET = 65536


def layer_shapes(in_dim: int, hidden: int, out_dim: int,
                 heads: Sequence[int]) -> List[Tuple[int, int, int]]:
    """(input width, heads, width per head) of each layer."""
    out, din = [], in_dim
    for i, k in enumerate(heads):
        f = out_dim if i == len(heads) - 1 else hidden
        out.append((din, int(k), f))
        din = int(k) * f
    return out


def init_params(seed: int, shapes: Sequence[Tuple[int, int, int]]) -> Dict:
    """Glorot-uniform W (din, K·F') and attention vectors a_l, a_r
    (K, F'; limit that of a (F', 1) matrix), zero biases, made on the
    device in one jitted call from `seed`."""
    shapes = tuple(tuple(s) for s in shapes)

    @jax.jit
    def make(key):
        layers = []
        for key, (din, k, f) in zip(jax.random.split(key, len(shapes)),
                                    shapes):
            kw, kl, kr = jax.random.split(key, 3)
            lim_w = np.sqrt(6.0 / (din + k * f))
            lim_a = np.sqrt(6.0 / (f + 1))
            layers.append({
                "w": jax.random.uniform(kw, (din, k * f), jnp.float32,
                                        -lim_w, lim_w),
                "a_l": jax.random.uniform(kl, (k, f), jnp.float32,
                                          -lim_a, lim_a),
                "a_r": jax.random.uniform(kr, (k, f), jnp.float32,
                                          -lim_a, lim_a),
                "b": jnp.zeros((k * f,), jnp.float32)})
        return {"layers": layers}
    return make(jax.random.PRNGKey(seed))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def edge_batch(graph: Graph, nodes: np.ndarray) -> Dict[str, np.ndarray]:
    """One batch of real nodes as an edge list: `dst`, `src` (batch-local
    ids; edges of the induced subgraph with a non-zero weight, then a
    self-loop per node), features, labels and loss weights. Padding
    nodes (zero features and weight) follow the real ones, and padding
    edges are self-loops of the last of them, so no real node's sums
    see either."""
    sub = graph.adj[nodes][:, nodes].tocoo()
    keep = (sub.data != 0) & (sub.row != sub.col)
    b = len(nodes)
    n = _round_up(b + 1, NODE_BUCKET)
    loops = np.arange(n)
    dst = np.concatenate([sub.row[keep], loops])
    src = np.concatenate([sub.col[keep], loops])
    pad = np.full(_round_up(len(dst), EDGE_BUCKET) - len(dst), n - 1)
    x = np.zeros((n, graph.features.shape[1]), np.float32)
    x[:b] = graph.features[nodes]
    labels = np.zeros((n, graph.labels.shape[1]), np.float32)
    labels[:b] = graph.labels[nodes]
    weight = np.zeros(n, np.float32)
    weight[:b] = graph.train_mask[nodes]
    return {"dst": np.concatenate([dst, pad]).astype(np.int32),
            "src": np.concatenate([src, pad]).astype(np.int32),
            "x": x, "labels": labels, "weight": weight}


def forward(params, batch, residual: bool):
    dst, src, h = batch["dst"], batch["src"], batch["x"]
    n = h.shape[0]
    layers = params["layers"]
    for i, layer in enumerate(layers):
        k, f = layer["a_l"].shape
        wh = (h @ layer["w"]).reshape(n, k, f)
        s = (wh * layer["a_l"]).sum(-1)                    # (n, K)
        t = (wh * layer["a_r"]).sum(-1)
        e = s[dst] + t[src]                                # (E, K)
        e = jnp.where(e > 0, e, NEGATIVE_SLOPE * e)
        e = jnp.exp(e - jax.ops.segment_max(e, dst, n)[dst])
        alpha = e / jax.ops.segment_sum(e, dst, n)[dst]
        heads = [jax.ops.segment_sum(alpha[:, j, None] * wh[src, j], dst, n)
                 for j in range(k)]
        z = jnp.stack(heads, 1) + layer["b"].reshape(k, f)
        if i == len(layers) - 1:
            return z.mean(1)
        z = z.reshape(n, k * f)
        if residual and z.shape == h.shape:
            z = z + h
        h = jnp.where(z > 0, z, jnp.expm1(z))              # ELU
    return h


def batch_loss(params, batch, residual: bool):
    z, y, w = forward(params, batch, residual), batch["labels"], \
        batch["weight"]
    per = (jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
           ).sum(-1)
    return (per * w).sum() / jnp.maximum(w.sum(), 1.0)


@functools.partial(jax.jit, static_argnums=(2,))
def _loss_and_grad(params, batches, residual: bool):
    def mean_loss(p):
        return sum(batch_loss(p, b, residual) for b in batches) \
            / len(batches)
    return jax.value_and_grad(mean_loss)(params)


@jax.jit
def _adam(params, mu, nu, grads, t, lr, b1, b2, eps):
    mu = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g,
                                mu, grads)
    nu = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g,
                                nu, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, a, v: p - lr * (a / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, mu, nu)
    return params, mu, nu


def run_steps(params0, step_batches: Sequence[Sequence[Dict]], *,
              residual: bool, lr: float, b1: float = 0.9,
              b2: float = 0.999, eps: float = 1e-8):
    """Train from `params0` through the given steps (each a list of one
    edge batch per shard). Returns (losses, first gradient, params after
    the last step), all on the host."""
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, batches in enumerate(step_batches, start=1):
            loss, grads = _loss_and_grad(params, tuple(batches), residual)
            if first_grad is None:
                first_grad = jax.device_get(grads)
            params, mu, nu = _adam(params, mu, nu, grads, jnp.float32(t),
                                   lr, b1, b2, eps)
            losses.append(float(loss))
    return losses, first_grad, jax.device_get(params)
