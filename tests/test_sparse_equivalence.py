"""Sparse-vs-dense training equivalence: `train_cluster_gcn` with
BlockEllAdj batches (sparse_adj=True, custom-VJP block-ELL spmm) must
track the dense-Â XLA path step for step — same losses to 1e-4, same
final micro-F1 — on a generated Reddit-scale subgraph, both single
device and through the 2-device shard_map DP step (fast set)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ClusterBatcher, GCNConfig, make_train_step,
                        init_gcn, train_cluster_gcn)
from repro.core.trainer import evaluate
from repro.graph import make_dataset, partition_graph
from repro.nn import adamw

STEPS = 20
TOL = 1e-4


def _setup(seed=0):
    g = make_dataset("reddit", scale=0.02, seed=seed)   # ~1.2k nodes
    parts, _ = partition_graph(g, 5, method="metis", seed=seed)
    cfg = GCNConfig(in_dim=g.features.shape[1], hidden_dim=64,
                    out_dim=int(g.labels.max()) + 1, num_layers=3,
                    dropout=0.0)
    return g, parts, cfg


def test_per_step_loss_drift_under_1e4():
    """20 real optimizer steps, identical batch stream: per-step losses
    of the sparse path stay within 1e-4 of the dense path."""
    g, parts, cfg = _setup()
    opt = adamw(1e-2)
    b_dense = ClusterBatcher(g, parts, clusters_per_batch=1, seed=0)
    b_sparse = ClusterBatcher(g, parts, clusters_per_batch=1, seed=0,
                              sparse_adj=True)
    key = jax.random.PRNGKey(0)
    params_d = init_gcn(key, cfg)
    params_s = jax.tree_util.tree_map(jnp.copy, params_d)
    step = make_train_step(cfg, opt)        # polymorphic spmm dispatch
    st_d, st_s = opt.init(params_d), opt.init(params_s)
    rng_d = rng_s = jax.random.PRNGKey(1)

    done = 0
    epoch = 0
    losses = []
    while done < STEPS:
        stream = zip(b_dense.epoch(epoch), b_sparse.epoch(epoch))
        for bd, bs in stream:
            params_d, st_d, rng_d, loss_d, _ = step(
                params_d, st_d, rng_d, bd.astuple())
            params_s, st_s, rng_s, loss_s, _ = step(
                params_s, st_s, rng_s, bs.astuple())
            drift = abs(float(loss_d) - float(loss_s))
            assert drift < TOL, (done, drift, float(loss_d), float(loss_s))
            losses.append(float(loss_d))
            done += 1
            if done == STEPS:
                break
        epoch += 1
    # the run actually trained (not 20 steps of a frozen model)
    assert losses[-1] < losses[0] * 0.7, losses


def test_trainer_end_to_end_sparse_matches_dense_and_f1_parity():
    """train_cluster_gcn(sparse_adj=True) — the real epoch loop — vs the
    dense default: per-epoch mean losses within 1e-4 over 20 steps, and
    full-graph eval parity at the end."""
    g, parts, cfg = _setup(seed=1)
    batcher = ClusterBatcher(g, parts, clusters_per_batch=1, seed=0)
    res_d = train_cluster_gcn(g, batcher, cfg, adamw(1e-2),
                              num_epochs=STEPS // batcher.steps_per_epoch(),
                              seed=0)
    res_s = train_cluster_gcn(g, batcher, cfg, adamw(1e-2),
                              num_epochs=STEPS // batcher.steps_per_epoch(),
                              seed=0, sparse_adj=True)
    # the caller's batcher must not have been mutated by sparse_adj=True
    assert batcher.sparse_adj is False
    ld = [h["loss"] for h in res_d.history]
    ls = [h["loss"] for h in res_s.history]
    assert max(abs(a - b) for a, b in zip(ld, ls)) < TOL, (ld, ls)
    acc_d = evaluate(res_d.params, g, cfg, g.test_mask)
    acc_s = evaluate(res_s.params, g, cfg, g.test_mask)
    assert abs(acc_d - acc_s) < 0.01, (acc_d, acc_s)


def test_sparse_batch_shapes_are_jit_stable():
    """Every sparse batch in an epoch has identical pytree structure and
    leaf shapes — one compile for the whole run."""
    g, parts, cfg = _setup()
    b = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0,
                       sparse_adj=True)
    shapes = {tuple((leaf.shape, str(leaf.dtype))
                    for leaf in jax.tree_util.tree_leaves(bt.astuple()))
              for bt in b.epoch(0)}
    assert len(shapes) == 1


def test_two_device_dp_step_sparse_matches_dense(run_distributed):
    """make_gcn_train_step on a 2-device mesh with stacked BlockEllAdj
    batches tracks the dense DP run to 1e-4 (fast set — 2 devices)."""
    out = run_distributed("""
import jax, numpy as np
from repro.core import ClusterBatcher, GCNConfig, train_cluster_gcn
from repro.graph import make_dataset, partition_graph
from repro.nn import adamw

from repro.launch.mesh import make_mesh

mesh = make_mesh((2,), ("data",))
g = make_dataset("cora", scale=0.3, seed=0)
cfg = GCNConfig(in_dim=g.features.shape[1], hidden_dim=16,
                out_dim=int(g.labels.max()) + 1, num_layers=2, dropout=0.0)
parts, _ = partition_graph(g, 4, method="metis", seed=0)
batcher = ClusterBatcher(g, parts, clusters_per_batch=1, seed=0)
hist = {}
for sp in (False, True):
    res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2), num_epochs=4,
                            mesh=mesh, sparse_adj=sp)
    hist[sp] = [h["loss"] for h in res.history]
drift = max(abs(a - b) for a, b in zip(hist[False], hist[True]))
assert drift < 1e-4, (drift, hist)
assert hist[True][-1] < hist[True][0] * 0.7, hist[True]
print("SPARSE_DP_OK", drift)
""", devices=2)
    assert "SPARSE_DP_OK" in out
