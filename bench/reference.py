"""Plain fp32 reference of the first Cluster-GCN training steps.

This is the yardstick's own statement of what one training step computes
(paper Algorithm 1, Eq. 10/11 per-batch normalisation, the GCN layer
Z = Â (H W + b), ReLU and layer norm between layers, Adam). It imports
nothing from the program under test. It takes the cell's fixed dataset
(graph arrays and the partition assignment) and the weights the benchmark
made from the seed, and nothing else the program produced. The partition
is the program's, so `partition_numbers` holds it to the configuration
before any batch is cut from it.

What it reproduces of the program's contract, because the comparison is
step by step and not statistical:
  * the batch stream: epoch e draws `default_rng((seed, e)).permutation`
    of the clusters and takes q at a time; an over-full union keeps a
    sorted uniform subsample drawn from `default_rng((seed, e, step))`;
  * the padded batch size `node_cap` (q·mean + 3·sqrt(q)·std of the
    cluster sizes, at least the largest cluster, rounded up);
  * the dropout stream: a step splits its key once, each layer splits
    again and draws its mask over the padded (cap, width) activation;
    the data-parallel step folds in the shard index first.
Everything runs in float32 at "highest" matmul precision on dense Â.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp


@dataclasses.dataclass
class Graph:
    """The dataset as plain arrays (no program types)."""
    adj: sp.csr_matrix            # (N, N) edge weights, no self loops
    features: np.ndarray          # (N, F) float32
    labels: np.ndarray            # (N,) int or (N, C) float {0, 1}
    train_mask: np.ndarray        # (N,) bool

    @classmethod
    def from_arrays(cls, indptr, indices, data, features, labels,
                    train_mask=None) -> "Graph":
        n = len(indptr) - 1
        adj = sp.csr_matrix((np.asarray(data, np.float32),
                             np.asarray(indices), np.asarray(indptr)),
                            shape=(n, n))
        mask = (np.ones(n, bool) if train_mask is None
                else np.asarray(train_mask, bool))
        return cls(adj, np.asarray(features, np.float32),
                   np.asarray(labels), mask)

    @property
    def multilabel(self) -> bool:
        return self.labels.ndim == 2


class Batches:
    """Algorithm 1 over a fixed partition: which nodes each step sees."""

    def __init__(self, parts: np.ndarray, q: int, seed: int,
                 pad_multiple: int = 128, node_cap: int | None = None):
        parts = np.asarray(parts)
        num_parts = int(parts.max()) + 1
        if parts.min() < 0:
            raise ValueError("partition assignment has negative ids")
        order = np.argsort(parts, kind="stable")
        self.sizes = np.bincount(parts, minlength=num_parts)
        self.members = np.split(order, np.cumsum(self.sizes)[:-1])
        self.num_parts, self.q, self.seed = num_parts, int(q), int(seed)
        if node_cap is None:
            est = (q * self.sizes.mean()
                   + 3.0 * np.sqrt(q) * self.sizes.std())
            cap = max(int(est), int(self.sizes.max()))
            node_cap = -(-cap // pad_multiple) * pad_multiple
        self.cap = int(node_cap)

    def steps_per_epoch(self) -> int:
        return -(-self.num_parts // self.q)

    @functools.lru_cache(maxsize=8)
    def groups(self, epoch: int) -> Tuple[np.ndarray, ...]:
        order = np.random.default_rng((self.seed, epoch)).permutation(
            self.num_parts)
        return tuple(order[i:i + self.q]
                     for i in range(0, self.num_parts, self.q))

    def size(self, epoch: int, step: int) -> int:
        """Real (unpadded) nodes of batch `step` of `epoch`."""
        return min(int(self.sizes[self.groups(epoch)[step]].sum()),
                   self.cap)

    def nodes(self, epoch: int, step: int) -> np.ndarray:
        group = self.groups(epoch)[step]
        nodes = np.concatenate([self.members[t] for t in group])
        if len(nodes) > self.cap:
            rng = np.random.default_rng((self.seed, epoch, step))
            keep = rng.choice(len(nodes), size=self.cap, replace=False)
            nodes = nodes[np.sort(keep)]
        return nodes


def partition_numbers(graph: Graph, parts, num_parts: int
                      ) -> Dict[str, float]:
    """The partition the batches are cut from, held to what the
    configuration states, since the program made it and both sides use it:
      partition.invalid       entries that break an assignment of every
                              node to one of `num_parts` non-empty parts:
                              a length other than the graph's, ids out of
                              range, empty parts;
      partition.chance_ratio  the share of edges inside a part that a
                              random assignment with these part sizes would
                              keep, over the share this one keeps: small
                              for a clustering of this graph, about 1 for
                              an assignment that belongs to another graph.
    """
    parts = np.asarray(parts)
    n = graph.adj.shape[0]
    invalid = abs(len(parts) - n)
    ok = (parts >= 0) & (parts < num_parts)
    invalid += int((~ok).sum())
    sizes = np.bincount(parts[ok], minlength=num_parts)[:num_parts]
    invalid += int((sizes == 0).sum())
    if invalid:
        return {"partition.invalid": float(invalid),
                "partition.chance_ratio": float("inf")}
    coo = graph.adj.tocoo()
    off = coo.row != coo.col
    inside = float((parts[coo.row[off]] == parts[coo.col[off]]).mean())
    chance = float(((sizes / n) ** 2).sum())
    return {"partition.invalid": 0.0,
            "partition.chance_ratio": (chance / inside if inside > 0
                                       else float("inf"))}


def normalized_block(graph: Graph, nodes: np.ndarray, norm: str,
                     diag_lambda: float) -> np.ndarray:
    """Paper §6.2: the induced subgraph's adjacency, normalised per batch."""
    a = graph.adj[nodes][:, nodes].toarray().astype(np.float64)
    deg = a.sum(1)
    eye = np.eye(len(nodes))
    if norm == "eq1":
        out = a / np.maximum(deg, 1e-9)[:, None]
    elif norm in ("eq9", "eq10", "eq11"):
        out = (a + eye) / (deg + 1.0)[:, None]           # Eq. 10
        if norm == "eq9":
            out = out + eye
        elif norm == "eq11":
            out = out + diag_lambda * np.diag(np.diag(out))
    else:
        raise ValueError(f"unknown normalisation {norm!r}")
    return out.astype(np.float32)


def block_nnz(graph: Graph, nodes: np.ndarray) -> int:
    """Non-zeros of the normalised batch Â: induced edges plus the
    diagonal that every Eq. 9-11 normalisation adds."""
    sub = graph.adj[nodes][:, nodes]
    return int(sub.nnz + len(nodes) - sub.diagonal().astype(bool).sum())


def padded_batch(graph: Graph, nodes: np.ndarray, cap: int, norm: str,
                 diag_lambda: float) -> Dict[str, np.ndarray]:
    """One batch as dense arrays padded to `cap` rows; padding rows have
    no edges and no loss weight, so they change no real row."""
    b = len(nodes)
    adj = np.zeros((cap, cap), np.float32)
    adj[:b, :b] = normalized_block(graph, nodes, norm, diag_lambda)
    x = np.zeros((cap, graph.features.shape[1]), np.float32)
    x[:b] = graph.features[nodes]
    lab = np.zeros((cap,) + graph.labels.shape[1:], graph.labels.dtype)
    lab[:b] = graph.labels[nodes]
    w = np.zeros(cap, np.float32)
    w[:b] = graph.train_mask[nodes]
    return {"adj": adj, "x": x, "labels": lab, "weight": w}


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------
def layer_dims(in_dim: int, hidden: int, out_dim: int,
               num_layers: int) -> List[Tuple[int, int]]:
    ds = [in_dim] + [hidden] * (num_layers - 1) + [out_dim]
    return list(zip(ds[:-1], ds[1:]))


def init_params(seed: int, dims: Sequence[Tuple[int, int]],
                layernorm: bool) -> Dict:
    """Glorot-uniform weights, zero biases, unit layer-norm scales, made
    on the device in one jitted call from `seed`."""
    dims = tuple(tuple(d) for d in dims)

    @jax.jit
    def make(key):
        layers = []
        for i, (k, (din, dout)) in enumerate(
                zip(jax.random.split(key, len(dims)), dims)):
            lim = np.sqrt(6.0 / (din + dout))
            layer = {"w": jax.random.uniform(k, (din, dout), jnp.float32,
                                             -lim, lim),
                     "b": jnp.zeros((dout,), jnp.float32)}
            if layernorm and i < len(dims) - 1:
                layer["ln_scale"] = jnp.ones((dout,), jnp.float32)
            layers.append(layer)
        return {"layers": layers}
    return make(jax.random.PRNGKey(seed))


# ----------------------------------------------------------------------
# the step
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Model:
    num_layers: int
    dropout: float
    layernorm: bool
    multilabel: bool
    lr: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


def _layer_keys(key, n: int):
    keys = []
    for _ in range(n):
        key, sub = jax.random.split(key)
        keys.append(sub)
    return keys


def batch_loss(params, batch, key, m: Model):
    h = batch["x"]
    layers = params["layers"]
    keys = _layer_keys(key, len(layers))
    for i, layer in enumerate(layers):
        if m.dropout > 0:
            keep = 1.0 - m.dropout
            h = h * jax.random.bernoulli(keys[i], keep, h.shape) / keep
        z = batch["adj"] @ (h @ layer["w"] + layer["b"])
        if i < len(layers) - 1:
            z = jax.nn.relu(z)
            if m.layernorm:
                mu = z.mean(-1, keepdims=True)
                var = ((z - mu) ** 2).mean(-1, keepdims=True)
                z = (z - mu) / jnp.sqrt(var + 1e-6) * layer["ln_scale"]
        h = z
    w = batch["weight"]
    if m.multilabel:
        y = batch["labels"].astype(jnp.float32)
        per = (jnp.maximum(h, 0) - h * y
               + jnp.log1p(jnp.exp(-jnp.abs(h)))).sum(-1)
    else:
        per = -jnp.take_along_axis(jax.nn.log_softmax(h, -1),
                                   batch["labels"][:, None].astype(int),
                                   axis=-1)[:, 0]
    return (per * w).sum() / jnp.maximum(w.sum(), 1.0)


def step_keys(rng, shards: int):
    """(next rng, one dropout key per batch of the step)."""
    rng, sub = jax.random.split(rng)
    if shards == 1:
        return rng, [sub]
    return rng, [jax.random.split(jax.random.fold_in(sub, i), 1)[0]
                 for i in range(shards)]


@functools.partial(jax.jit, static_argnums=(3,))
def _grad(params, stacked, keys, m: Model):
    def mean_loss(p):
        losses = jax.vmap(lambda b, k: batch_loss(p, b, k, m))(stacked,
                                                               keys)
        return losses.mean()
    return jax.value_and_grad(mean_loss)(params)


@functools.partial(jax.jit, static_argnums=(4,))
def _adam(params, mu, nu, grads, m: Model, t):
    mu = jax.tree_util.tree_map(lambda a, g: m.b1 * a + (1 - m.b1) * g,
                                mu, grads)
    nu = jax.tree_util.tree_map(lambda a, g: m.b2 * a + (1 - m.b2) * g * g,
                                nu, grads)
    bc1, bc2 = 1 - m.b1 ** t, 1 - m.b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, a, v: p - m.lr * (a / bc1) / (jnp.sqrt(v / bc2) + m.eps),
        params, mu, nu)
    return params, mu, nu


def run_steps(params0, rng, step_batches: Sequence[Sequence[Dict]],
              m: Model):
    """Train from `params0` through the given steps (each a list of one
    batch per shard). Returns (losses, first gradient, params after the
    last step), all on the host."""
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, batches in enumerate(step_batches, start=1):
            rng, keys = step_keys(rng, len(batches))
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                             *batches)
            loss, grads = _grad(params, stacked, jnp.stack(keys), m)
            if first_grad is None:
                first_grad = jax.device_get(grads)
            params, mu, nu = _adam(params, mu, nu, grads, m,
                                   jnp.float32(t))
            losses.append(float(loss))
    return losses, first_grad, jax.device_get(params)
