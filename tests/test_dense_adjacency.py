"""The dense batch Â built from the edge list (normalized_dense_block)
against the densify-then-normalize_dense build it replaced."""
import numpy as np
import pytest

from repro.core import ClusterBatcher, SaintNodeSampler
from repro.graph import (CSRGraph, make_dataset, normalize_dense,
                         normalized_dense_block, random_partition)

NORMS = ("eq1", "sym", "eq9", "eq10", "eq11")


def _old_block(indptr, indices, data, cap, norm, diag_lambda):
    """The former dense build: scatter A into a zeroed (cap, cap) block,
    normalize its (b, b) corner with normalize_dense, re-zero padding."""
    b = len(indptr) - 1
    dense = np.zeros((cap, cap), np.float32)
    row = np.repeat(np.arange(b), np.diff(indptr))
    dense[row, indices] = data
    dense[:b, :b] = normalize_dense(dense[:b, :b], norm, diag_lambda)
    dense[b:, :] = 0.0
    dense[:, b:] = 0.0
    return dense


def _csr(b, p, seed, *, self_loops=False, empty_row=False, weighted=False):
    """Symmetric random (b, b) CSR with unique slots and rows whose
    columns are NOT sorted (as a relabeled subgraph's are)."""
    rng = np.random.default_rng(seed)
    a = rng.random((b, b)) < p
    a = a | a.T
    np.fill_diagonal(a, False)
    if self_loops:
        a[np.arange(0, b, 3), np.arange(0, b, 3)] = True
    if empty_row:
        a[b // 2, :] = False
        a[:, b // 2] = False
    w = (rng.random((b, b)) + 0.25).astype(np.float32) if weighted \
        else np.ones((b, b), np.float32)
    indptr, indices, data = [0], [], []
    for i in range(b):
        cols = rng.permutation(np.flatnonzero(a[i]))
        indices.extend(cols)
        data.extend(w[i, cols])
        indptr.append(len(indices))
    return (np.asarray(indptr, np.int64), np.asarray(indices, np.int32),
            np.asarray(data, np.float32))


GRAPHS = {
    "no_loops": dict(b=40, cap=64, kw={}),
    "self_loops": dict(b=40, cap=64, kw=dict(self_loops=True)),
    "empty_row": dict(b=40, cap=64, kw=dict(empty_row=True)),
    "padded": dict(b=23, cap=128, kw=dict(self_loops=True)),
    "b_eq_cap": dict(b=64, cap=64, kw=dict(self_loops=True)),
    "weighted": dict(b=40, cap=64, kw=dict(self_loops=True, weighted=True)),
}


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("norm", NORMS)
def test_block_matches_old_build(norm, graph):
    g = GRAPHS[graph]
    ip, ix, dt = _csr(g["b"], 0.15, seed=len(graph), **g["kw"])
    got = normalized_dense_block(ip, ix, dt, g["cap"], norm, diag_lambda=0.7)
    want = _old_block(ip, ix, dt, g["cap"], norm, 0.7)
    assert got.dtype == np.float32 and got.shape == (g["cap"], g["cap"])
    if graph == "weighted":
        # float32 row sums in another order: same math, rounding apart
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
        assert (got[g["b"]:] == 0).all() and (got[:, g["b"]:] == 0).all()
    else:
        assert np.array_equal(got, want)


def test_block_rejects_unknown_method():
    ip, ix, dt = _csr(8, 0.3, seed=0)
    with pytest.raises(ValueError, match="unknown normalization"):
        normalized_dense_block(ip, ix, dt, 8, "eq12")


def test_subgraph_keeps_slots_unique():
    # the new build relies on unique (row, col) slots, as the block-ELL
    # path does — even a node list with repeats must keep them unique
    g = make_dataset("cora", scale=0.3, seed=0)
    rng = np.random.default_rng(0)
    nodes = rng.choice(g.num_nodes, 200, replace=True)
    sub, _ = g.subgraph(nodes)
    row = np.repeat(np.arange(len(nodes)), np.diff(sub.indptr))
    key = row * len(nodes) + sub.indices
    assert len(np.unique(key)) == len(key)


def _cluster_nodes(batcher):
    for step, group in enumerate(batcher._epoch_groups(0)):
        yield batcher._batch_nodes(group, count_overflow=False,
                                   rng_ctx=(0, step))


def _saint_nodes(sampler):
    rng = np.random.default_rng((sampler.seed, 0))
    for _ in range(sampler.steps_per_epoch()):
        yield sampler.draw(rng)[0]


@pytest.mark.parametrize("case", ["cluster_q1", "cluster_q3", "saint_node"])
def test_epoch_payloads_match_old_build(case):
    g = make_dataset("cora", scale=0.3, seed=0)
    if case == "saint_node":
        sampler = SaintNodeSampler(g, budget=60, norm="sym",
                                   batches_per_epoch=4, seed=2)
        node_sets = _saint_nodes(sampler)
    else:
        q = 1 if case == "cluster_q1" else 3
        parts = random_partition(g.num_nodes, 9, seed=1)
        sampler = ClusterBatcher(g, parts, clusters_per_batch=q,
                                 norm="eq10" if q == 1 else "eq11",
                                 diag_lambda=1.0, seed=1)
        node_sets = _cluster_nodes(sampler)
    batches = list(sampler.epoch(0))
    assert len(batches) == sampler.steps_per_epoch()
    for batch, nodes in zip(batches, node_sets):
        sub, _ = g.subgraph(nodes)
        want = _old_block(sub.indptr, sub.indices, sub.data,
                          sampler.node_cap, sampler.norm,
                          sampler.diag_lambda)
        assert np.array_equal(batch.adj, want)
        n = int(batch.num_real)
        assert n == len(nodes)
        assert not batch.adj[n:].any() and not batch.adj[:, n:].any()
