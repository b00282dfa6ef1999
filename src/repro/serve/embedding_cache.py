"""Per-cluster final-layer embedding cache — the offline half of the
serving layer (docs/serving.md).

The paper's clustering is a natural serving partition: node v's final
embedding lives in exactly one cluster's block, so the METIS assignment
the trainer already caches doubles as the cache key. Two compute paths
produce identical (exact, full-graph) logits:

* `full_graph_embeddings` — the offline batch precompute: layer-wise
  propagation over the WHOLE graph, cluster-block by cluster-block.
  Per layer, the dense transform H·W + b runs over all nodes (row
  chunks, so mmap'd feature files stream instead of materializing),
  then each cluster's rows of the normalized Â are sliced out of the
  global CSR, tiled with the vectorized block-ELL builder and pushed
  through the forward-only block-ELL spmm (Pallas kernel on TPU, the
  XLA oracle elsewhere). A dense Â is NEVER materialized; hidden
  states are shared across clusters so every layer costs O(nnz).
* `embed_cluster` — the lazy single-cluster path used after a live
  update invalidates one cluster: exact L-hop halo propagation. The
  hop-l node set is the hop-(l+1) set plus its neighbors, Â rows are
  sliced to (target, halo) and relabeled, and the same block-ELL spmm
  does the product — so a cluster re-embeds without touching the rest
  of the graph, and the result still equals the one-shot full-graph
  forward (tests/test_serve.py pins both to ≤1e-5).

Both paths mirror `core.trainer.full_graph_logits` operation-for-
operation (transform → propagate → residual → relu → layernorm, the
§6.2 precompute_ax skip included), which is what makes the
serving/training parity test tight.
"""
from __future__ import annotations

import collections
import json
import os
import pathlib
import shutil
import tempfile
import time
from typing import Dict, Iterable, List, Optional

import jax
import numpy as np

from repro.core.gcn import GCNConfig
from repro.core.kslots import pow2_ceil
from repro.graph.csr import CSRGraph
from repro.graph.normalization import normalize_csr
from repro.kernels.ops import _resolve_spmm, block_ell_from_csr


def _forward_spmm(blocks: np.ndarray, cols: np.ndarray,
                  x: np.ndarray) -> np.ndarray:
    """Forward-only block-ELL product (no transpose tiles needed —
    serving never backprops): the Pallas kernel on TPU, the fused XLA
    oracle elsewhere (`_resolve_spmm("auto")`, same dispatch as
    training)."""
    if _resolve_spmm("auto") == "pallas":
        from repro.kernels.block_spmm import spmm_block_ell
        y = spmm_block_ell(jax.numpy.asarray(blocks),
                           jax.numpy.asarray(cols),
                           jax.numpy.asarray(x))
    else:
        from repro.kernels.ref import spmm_block_ell_ref
        y = spmm_block_ell_ref(jax.numpy.asarray(blocks),
                               jax.numpy.asarray(cols),
                               jax.numpy.asarray(x))
    return np.asarray(y, dtype=np.float32)


def _slice_rows(indptr, indices, data, rows):
    """Row-slice a CSR matrix (columns untouched): the flat-gather
    pattern of CSRGraph.subgraph without the column filtering."""
    rows = np.asarray(rows, dtype=np.int64)
    starts, ends = indptr[rows], indptr[rows + 1]
    counts = ends - starts
    total = int(counts.sum())
    pos = np.cumsum(np.concatenate([[0], counts]))
    flat = (np.repeat(starts, counts)
            + np.arange(total, dtype=np.int64)
            - np.repeat(pos[:-1], counts))
    return pos.astype(np.int64), indices[flat], data[flat]


def _pad_to(n: int, block: int) -> int:
    return -(-n // block) * block


def _prop_rows(ip, ix, dt, rows, x_pad, block, nr_pad) -> np.ndarray:
    """y = Â[rows, :] @ x for one cluster block: CSR row slice →
    block-ELL tiles → forward spmm. `x_pad` is the (padded-N, F) dense
    operand shared across clusters within a layer (already on the
    device). Every cluster's tiles are padded to `nr_pad` rows and a
    pow2 slot count (zero tiles, exact), so the kernel compiles once per
    slot bucket and width instead of once per cluster."""
    sip, six, sdt = _slice_rows(ip, ix, dt, rows)
    blocks, cols = block_ell_from_csr(sip, six, sdt,
                                      n_cols=x_pad.shape[0],
                                      block=block, n_rows=nr_pad)
    k = blocks.shape[1]
    pad = ((0, 0), (0, pow2_ceil(k) - k))
    blocks = np.pad(blocks, pad + ((0, 0), (0, 0)))
    cols = np.pad(cols, pad)
    return _forward_spmm(blocks, cols, x_pad)[:len(rows)]


def _inner_activation(z, h_in, layer, cfg: GCNConfig):
    """Residual → relu → layernorm, exactly as the full-graph oracle
    (trainer.full_graph_logits) applies them between layers."""
    if cfg.residual and h_in is not None and z.shape == h_in.shape:
        z = z + h_in
    z = np.maximum(z, 0.0)
    if cfg.layernorm:
        mu = z.mean(-1, keepdims=True)
        sd = z.std(-1, keepdims=True)
        z = (z - mu) / (sd + 1e-6) * layer["ln_scale"]
    return z


def full_graph_embeddings(params, graph: CSRGraph, parts: np.ndarray,
                          cfg: GCNConfig, *, norm: str = "eq10",
                          diag_lambda: float = 0.0, block: int = 128,
                          row_chunk: int = 65536) -> np.ndarray:
    """Exact full-graph GCN logits, propagated cluster-block by
    cluster-block through the forward-only block-ELL spmm. Returns
    (N, out_dim) fp32. Layer-0 dense transforms stream the (possibly
    mmap'd) feature matrix in `row_chunk` rows at a time; with
    cfg.residual the features are materialized once (the residual adds
    the layer input back post-propagation)."""
    ip, ix, dt = normalize_csr(graph.indptr, graph.indices, graph.data,
                               norm, diag_lambda)
    n = graph.num_nodes
    n_pad = _pad_to(n, block)
    layers = jax.tree_util.tree_map(np.asarray, params["layers"])
    num_parts = int(np.asarray(parts).max()) + 1
    clusters = [np.where(parts == c)[0] for c in range(num_parts)]
    nr_pad = _pad_to(max(len(rows) for rows in clusters), block)

    def propagate(x):
        x_pad = np.zeros((n_pad, x.shape[1]), np.float32)
        x_pad[:n] = x
        x_pad = jax.numpy.asarray(x_pad)    # one transfer per layer
        out = np.empty((n, x.shape[1]), np.float32)
        for rows in clusters:
            if len(rows):
                out[rows] = _prop_rows(ip, ix, dt, rows, x_pad, block,
                                       nr_pad)
        return out

    h: Optional[np.ndarray] = None       # None → stream graph.features
    if cfg.precompute_ax:
        h = propagate(np.asarray(graph.features, np.float32))
    elif cfg.residual:
        h = np.asarray(graph.features, np.float32)
    for i, layer in enumerate(layers):
        w, b = layer["w"], layer["b"]
        if h is None:
            z = np.empty((n, w.shape[1]), np.float32)
            for s in range(0, n, row_chunk):
                e = min(n, s + row_chunk)
                z[s:e] = (np.asarray(graph.features[s:e], np.float32)
                          @ w + b)
        else:
            z = h @ w + b
        if not (i == 0 and cfg.precompute_ax):
            z = propagate(z)
        if i < len(layers) - 1:
            z = _inner_activation(z, h, layer, cfg)
        h = z
    return h


def _expand_frontier(ip, ix, nodes) -> np.ndarray:
    """nodes ∪ neighbors(nodes), sorted unique — one halo hop."""
    _, cols, _ = _slice_rows(ip, ix, ix, nodes)   # data unused
    return np.union1d(nodes, cols).astype(np.int64)


def embed_cluster(params, graph: CSRGraph, cfg: GCNConfig,
                  rows: np.ndarray, *, norm: str = "eq10",
                  diag_lambda: float = 0.0,
                  block: int = 128) -> np.ndarray:
    """Exact logits for `rows` only, via L-hop halo propagation — the
    lazy re-embed path after a live update invalidates one cluster.
    The halo grows the active node set one neighbor hop per remaining
    propagation, so every Â row-slice keeps all its non-zeros and the
    result is identical to the full-graph forward restricted to
    `rows`."""
    ip, ix, dt = normalize_csr(graph.indptr, graph.indices, graph.data,
                               norm, diag_lambda)
    layers = jax.tree_util.tree_map(np.asarray, params["layers"])
    hops = len(layers)        # precompute_ax trades layer-0's hop for
    # the up-front feature propagation — total hops stay num_layers
    levels: List[np.ndarray] = [np.unique(np.asarray(rows, np.int64))]
    for _ in range(hops):
        levels.append(_expand_frontier(ip, ix, levels[-1]))
    levels.reverse()          # widest halo first, `rows` last

    def prop(tgt, src_nodes, x):
        """Â[tgt, src_nodes] @ x — exact because src_nodes ⊇ nbrs(tgt)."""
        relabel = np.full(graph.num_nodes, -1, np.int64)
        relabel[src_nodes] = np.arange(len(src_nodes))
        sip, six, sdt = _slice_rows(ip, ix, dt, tgt)
        local = relabel[six]
        assert (local >= 0).all(), "halo missed a neighbor"
        x_pad = np.zeros((_pad_to(len(src_nodes), block), x.shape[1]),
                         np.float32)
        x_pad[:len(src_nodes)] = x
        blocks, cols = block_ell_from_csr(
            sip, local.astype(np.int32), sdt, n_cols=x_pad.shape[0],
            block=block, n_rows=_pad_to(len(tgt), block))
        return _forward_spmm(blocks, cols, x_pad)[:len(tgt)]

    t = 0
    nodes = levels[0]
    h = np.asarray(graph.features[nodes], np.float32)
    if cfg.precompute_ax:
        h = prop(levels[1], nodes, h)
        nodes = levels[1]
        t = 1
    for i, layer in enumerate(layers):
        z = h @ layer["w"] + layer["b"]
        if not (i == 0 and cfg.precompute_ax):
            new_nodes = levels[t + 1]
            z = prop(new_nodes, nodes, z)
            t += 1
        else:
            new_nodes = nodes
        if i < len(layers) - 1:
            # the residual adds the layer INPUT restricted to the
            # (narrower) post-propagation node set
            h_res = h[np.searchsorted(nodes, new_nodes)]
            z = _inner_activation(z, h_res, layer, cfg)
        nodes = new_nodes
        h = z
    # levels[-1] is sorted-unique; map back to the caller's row order
    order = np.searchsorted(nodes, np.asarray(rows, np.int64))
    return h[order]


# ----------------------------------------------------------------------
# the on-disk cache
# ----------------------------------------------------------------------
class EmbeddingCache:
    """Disk cache of per-cluster final-layer embeddings, keyed on
    (checkpoint step, partition fingerprint) — docs/serving.md spells
    out the key scheme and the invalidation rules.

    Layout: <root>/step<NNNN>_<fingerprint>/{manifest.json,
    cluster_<c>.npy}. Writes are atomic AND durable (tmp + fsync +
    rename + directory fsync) so neither a crashed nor a power-lost
    precompute leaves a torn cluster file behind a valid-looking name;
    loads are mmap'd so a query pages in only the rows it touches.
    `recompute_counts` tracks how many times each cluster was
    (re)stored — the surgical-invalidation test locks "a delta
    recomputes ONLY the clusters in its influence region" against it.
    Live updates never mutate a keyed directory in place: `rekey`
    switches to the grown graph's fingerprint, carrying untouched
    cluster files over by hardlink."""

    def __init__(self, root, *, checkpoint_step: int,
                 partition_fingerprint: str):
        self.root = pathlib.Path(root)
        self.checkpoint_step = int(checkpoint_step)
        self.partition_fingerprint = str(partition_fingerprint)
        self.dir = (self.root
                    / f"step{self.checkpoint_step:010d}"
                      f"_{self.partition_fingerprint}")
        self.dir.mkdir(parents=True, exist_ok=True)
        self.recompute_counts: Dict[int, int] = collections.Counter()
        manifest = self.dir / "manifest.json"
        if not manifest.exists():
            manifest.write_text(json.dumps(
                {"checkpoint_step": self.checkpoint_step,
                 "partition_fingerprint": self.partition_fingerprint,
                 "created": time.time()}))

    def path(self, cluster: int) -> pathlib.Path:
        return self.dir / f"cluster_{int(cluster):05d}.npy"

    def has(self, cluster: int) -> bool:
        return self.path(cluster).exists()

    def load(self, cluster: int) -> np.ndarray:
        return np.load(self.path(cluster), mmap_mode="r")

    def store(self, cluster: int, embeddings: np.ndarray) -> None:
        emb = np.ascontiguousarray(embeddings, dtype=np.float32)
        fd, tmp = tempfile.mkstemp(suffix=".npy.tmp", dir=self.dir)
        try:
            with open(fd, "wb") as f:
                np.save(f, emb)
                f.flush()
                # fsync before the rename: rename-then-crash must never
                # publish a name whose data blocks are still in flight
                os.fsync(f.fileno())
            pathlib.Path(tmp).replace(self.path(cluster))
            self._fsync_dir()
        finally:
            pathlib.Path(tmp).unlink(missing_ok=True)
        self.recompute_counts[int(cluster)] += 1

    def _fsync_dir(self) -> None:
        dfd = os.open(self.dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def rekey(self, partition_fingerprint: str, *,
              drop: Iterable[int] = ()) -> "EmbeddingCache":
        """Switch to the directory keyed on a new partition fingerprint
        — the served graph changed under a GraphDelta, so the old key
        no longer describes what the engine serves. Every cached
        cluster except `drop` (the delta's stale set) is carried over
        by hardlink (copy when the filesystem refuses links), and the
        old directory is left byte-for-byte intact: engines still
        serving the base (checkpoint, partition) keep sharing an
        uncontaminated warm cache, and post-delta re-embeds land only
        under the grown graph's own key. `recompute_counts` carries
        across so invalidation tests see one history."""
        if partition_fingerprint == self.partition_fingerprint:
            return self
        new = EmbeddingCache(
            self.root, checkpoint_step=self.checkpoint_step,
            partition_fingerprint=partition_fingerprint)
        new.recompute_counts = self.recompute_counts
        dropped = {int(c) for c in drop}
        for c in self.cached_clusters():
            if c in dropped or new.has(c):
                continue
            try:
                os.link(self.path(c), new.path(c))
            except OSError:
                shutil.copy2(self.path(c), new.path(c))
        new._fsync_dir()
        return new

    def invalidate(self, cluster: int) -> bool:
        """Drop one cluster's cached embeddings (a GraphDelta touched
        it); the next query of the cluster lazily re-embeds. Returns
        whether there was anything to drop."""
        p = self.path(cluster)
        existed = p.exists()
        p.unlink(missing_ok=True)
        return existed

    def cached_clusters(self) -> List[int]:
        return sorted(int(p.stem.split("_")[1])
                      for p in self.dir.glob("cluster_*.npy"))
