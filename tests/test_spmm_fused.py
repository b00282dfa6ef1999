"""Differential test tier for the fused Â·(XW + b) block-ELL kernel.

The fused kernel (kernels.block_spmm.spmm_fused) collapses each GCN
layer's dense XW matmul and sparse aggregation into one pass, with a
per-row-block `row_k` map that early-outs the K loop past the true
occupancy. Every claim it makes is checked differentially here:

  * property sweep (interpret mode) against the unfused
    `spmm(adj, (XW+b))` composition — fp32 within 1e-5, bf16 within
    bf16 resolution — over (nrb, ncb, B ∈ {8, 16}, D, F, dtype, fill)
    including all-zero adjacencies (row_k = 0 everywhere) and payloads
    whose K was inflated past the occupancy (row_k < K dead slots);
  * adjoint exactness of the custom VJP: ⟨y, J v⟩ = ⟨Jᵀ y, v⟩ for both
    the x and the w linearizations (the backward runs on the
    transposed tiles + the dW contraction, never autodiff);
  * vmap-vs-loop equality on stacked payloads and jit cache stability
    (same leaf shapes → one trace);
  * a 20-step fused-vs-unfused training-trajectory lock on the
    ppi_tiny recipe — dense batches, sparse batches, and the 2-device
    shard_map DP step — through the real `model.fuse_spmm` knob.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (ClusterBatcher, GCNConfig, init_gcn,
                        make_train_step)
from repro.graph import make_dataset, partition_graph
from repro.kernels import (BlockEllAdj, block_ell_adj_from_dense, spmm,
                           spmm_ell, spmm_fused, spmm_xw)
from repro.nn import adamw

STEPS = 20
TOL = 1e-4


def _block_sparse(rng, nrb, ncb, B, density, kill_rows=0):
    """Dense matrix that is sparse at BLOCK granularity; `kill_rows`
    zeroes that many whole row-blocks (row_k = 0 rows)."""
    dense = np.zeros((nrb * B, ncb * B), np.float32)
    for i in range(nrb):
        for j in range(ncb):
            if rng.random() < density:
                dense[i * B:(i + 1) * B, j * B:(j + 1) * B] = \
                    rng.standard_normal((B, B))
    for i in range(min(kill_rows, nrb)):
        dense[i * B:(i + 1) * B] = 0.0
    return dense


def _unfused_oracle(adj, dense, x, w, b):
    """The unfused composition the fused kernel must match: XW in the
    operand dtype with an fp32 accumulator, fp32 bias add, cast back,
    then the block-ELL aggregation (the 'ref' oracle path)."""
    z = jnp.matmul(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    if b is not None:
        z = z + b
    return spmm(adj, z.astype(x.dtype), mode="ref")


@settings(max_examples=10, deadline=None)
@given(nrb=st.integers(1, 4), ncb=st.integers(1, 4),
       B=st.sampled_from([8, 16]), D=st.integers(1, 20),
       F=st.integers(1, 20), density=st.floats(0.0, 1.0),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       kill_rows=st.integers(0, 2), extra_k=st.integers(0, 3),
       with_bias=st.booleans(), seed=st.integers(0, 2 ** 16))
def test_fused_matches_unfused_property_sweep(nrb, ncb, B, D, F, density,
                                              dtype, kill_rows, extra_k,
                                              with_bias, seed):
    """Fused (interpret mode) ≡ spmm(adj, XW+b) across shapes, dtypes
    and fill patterns, incl. row_k = 0 rows and row_k < K dead slots."""
    rng = np.random.default_rng(seed)
    dense = _block_sparse(rng, nrb, ncb, B, density, kill_rows)
    present = np.abs(dense.reshape(nrb, B, ncb, B)).sum(axis=(1, 3)) > 0
    need = max(int(present.sum(1).max()), 1)
    need_t = max(int(present.sum(0).max()), 1)
    # extra_k > 0 inflates K past the occupancy: trailing dead slots the
    # row_k specialization must skip without changing a single value
    adj = block_ell_adj_from_dense(dense, block=B, k_slots=need + extra_k,
                                   k_slots_t=need_t + extra_k)
    assert adj.row_k is not None and int(adj.row_k.max()) <= need
    cd = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((ncb * B, D)), cd)
    w = jnp.asarray(rng.standard_normal((D, F)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((F,)), jnp.float32) \
        if with_bias else None

    want = _unfused_oracle(adj, dense, x, w, b)
    got = spmm_fused(adj, x, w, b, impl="interpret", block_f=16)
    assert got.shape == (nrb * B, F) and got.dtype == cd
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    scale = max(1.0, float(jnp.abs(want.astype(jnp.float32)).max()))
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert err <= tol * scale, (err, scale, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ref_is_bitwise_the_unfused_composition(dtype):
    """On the 'ref' (CPU training) impl the fused product is BITWISE the
    unfused matmul-then-spmm — the property that makes flipping
    model.fuse_spmm a no-op on existing CPU trajectories."""
    rng = np.random.default_rng(3)
    dense = _block_sparse(rng, 3, 3, 8, 0.5, kill_rows=1)
    adj = block_ell_adj_from_dense(dense, block=8)
    x = jnp.asarray(rng.standard_normal((24, 10)), dtype)
    w = jnp.asarray(rng.standard_normal((10, 6)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((6,)), jnp.float32)
    got = spmm_fused(adj, x, w, b, impl="ref")
    want = _unfused_oracle(adj, dense, x, w, b)
    assert got.dtype == want.dtype
    assert (jnp.asarray(got) == jnp.asarray(want)).all()


def test_fused_vjp_adjoint_exactness():
    """⟨y, J v⟩ = ⟨Jᵀ y, v⟩ for the fused custom VJP, separately for
    the x-linearization (transposed-tile spmm backward) and the
    w-linearization (the dW = Xᵀ(Âᵀḡ) contraction), interpret mode."""
    rng = np.random.default_rng(7)
    dense = _block_sparse(rng, 4, 4, 8, 0.4, kill_rows=1)
    adj = block_ell_adj_from_dense(dense, block=8)
    x = jnp.asarray(rng.standard_normal((32, 9)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((9, 5)), jnp.float32)

    # x-linearization: f(v) = Â (v W) is linear in v
    f = lambda v: spmm_fused(adj, v, w, impl="interpret", block_f=16)
    y = jnp.asarray(rng.standard_normal(f(x).shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
    _, f_vjp = jax.vjp(f, x)
    lhs = float(jnp.vdot(y, f(v)))
    rhs = float(jnp.vdot(f_vjp(y)[0], v))
    assert abs(lhs - rhs) <= 1e-4 * max(1.0, abs(lhs)), (lhs, rhs)

    # w-linearization: g(u) = Â (X u) is linear in u
    g = lambda u: spmm_fused(adj, x, u, impl="interpret", block_f=16)
    u = jnp.asarray(rng.standard_normal(w.shape), jnp.float32)
    _, g_vjp = jax.vjp(g, w)
    lhs = float(jnp.vdot(y, g(u)))
    rhs = float(jnp.vdot(g_vjp(y)[0], u))
    assert abs(lhs - rhs) <= 1e-4 * max(1.0, abs(lhs)), (lhs, rhs)


def test_fused_grads_match_dense_autodiff():
    """d/d{x, w, b} of a fused-product loss vs plain autodiff through
    the dense adjacency — exact in fp32 on the ref impl."""
    rng = np.random.default_rng(11)
    dense = _block_sparse(rng, 3, 3, 8, 0.5)
    adj = block_ell_adj_from_dense(dense, block=8)
    x = jnp.asarray(rng.standard_normal((24, 7)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((7, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4,)), jnp.float32)
    gf = jax.grad(lambda *a: (spmm_fused(adj, *a, impl="ref") ** 2).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    gd = jax.grad(lambda x_, w_, b_:
                  ((jnp.asarray(dense) @ (x_ @ w_ + b_)) ** 2).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    for name, a, d in zip("xwb", gf, gd):
        err = float(jnp.abs(a - d).max())
        assert err <= 1e-4 * max(1.0, float(jnp.abs(d).max())), (name, err)


def test_fused_legacy_payload_without_row_k():
    """A BlockEllAdj built before row_k existed (4 data fields) still
    flows through the fused and unfused kernels — None defaults to
    'every slot is live' (row_k = K)."""
    rng = np.random.default_rng(5)
    dense = _block_sparse(rng, 3, 3, 8, 0.6)
    new = block_ell_adj_from_dense(dense, block=8)
    old = BlockEllAdj(blocks=new.blocks, block_cols=new.block_cols,
                      blocks_t=new.blocks_t, block_cols_t=new.block_cols_t)
    assert old.row_k is None and old.row_k_t is None
    x = jnp.asarray(rng.standard_normal((24, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)
    for impl in ("ref", "interpret"):
        a = spmm_fused(old, x, w, impl=impl, block_f=16)
        b = spmm_fused(new, x, w, impl=impl, block_f=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
        c = spmm_ell(old, x, impl=impl, block_f=16)
        d = spmm_ell(new, x, impl=impl, block_f=16)
        np.testing.assert_allclose(np.asarray(c), np.asarray(d),
                                   atol=1e-6)


def test_fused_vmap_matches_loop():
    """vmap over stacked BlockEllAdj payloads (the DP-step layout)
    equals the per-payload loop."""
    rng = np.random.default_rng(13)
    adjs, denses = [], []
    for s in range(3):
        d = _block_sparse(rng, 3, 3, 8, 0.5, kill_rows=s % 2)
        denses.append(d)
        adjs.append(block_ell_adj_from_dense(d, block=8, k_slots=6,
                                             k_slots_t=6))
    stacked = jax.tree_util.tree_map(lambda *ls: np.stack(ls), *adjs)
    xs = jnp.asarray(rng.standard_normal((3, 24, 7)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((7, 5)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((5,)), jnp.float32)
    got = jax.vmap(lambda a, x: spmm_fused(a, x, w, b, impl="ref"))(
        stacked, xs)
    for i in range(3):
        want = spmm_fused(adjs[i], xs[i], w, b, impl="ref")
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   atol=1e-6)


def test_fused_jit_shape_stability():
    """K (and row_k's length) are SHAPE dims: distinct payloads with the
    same leaf shapes share one jit trace of the fused product."""
    rng = np.random.default_rng(17)
    traces = []

    @jax.jit
    def f(adj, x, w):
        traces.append(1)
        return spmm_fused(adj, x, w, impl="ref")

    w = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    for s in range(3):
        d = _block_sparse(rng, 2, 2, 8, 0.7)
        adj = block_ell_adj_from_dense(d, block=8, k_slots=2, k_slots_t=2)
        x = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
        y = f(adj, x, w)
        assert y.shape == (16, 4) and y.dtype == jnp.float32
    assert len(traces) == 1, "same-shape payloads must share one trace"


# ----------------------------------------------------------------------
# 20-step training-trajectory locks on the ppi_tiny recipe
# ----------------------------------------------------------------------
def _ppi_tiny_setup(seed=0):
    """The ppi_tiny preset's ingredients (configs.ppi.tiny_spec), built
    directly so the lock drives the raw per-step loop."""
    g = make_dataset("ppi", scale=0.03, seed=seed)
    parts, _ = partition_graph(g, 8, method="metis", seed=seed)
    cfg = dict(in_dim=g.features.shape[1], hidden_dim=64,
               out_dim=g.labels.shape[1], num_layers=3, dropout=0.2,
               multilabel=True)
    return g, parts, cfg


def _locked_trajectories(sparse_adj: bool):
    """Two identical 20-step runs, fuse_spmm off vs on; returns the
    per-step loss lists."""
    g, parts, cfg_kw = _ppi_tiny_setup()
    losses = {}
    for fused in (False, True):
        cfg = GCNConfig(fuse_spmm=fused, **cfg_kw)
        batcher = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0,
                                 sparse_adj=sparse_adj)
        params = init_gcn(jax.random.PRNGKey(0), cfg)
        opt = adamw(1e-2)
        step = make_train_step(cfg, opt)
        opt_state, rng = opt.init(params), jax.random.PRNGKey(1)
        out, done, epoch = [], 0, 0
        while done < STEPS:
            for b in batcher.epoch(epoch):
                params, opt_state, rng, loss, _ = step(
                    params, opt_state, rng, b.astuple())
                out.append(float(loss))
                done += 1
                if done == STEPS:
                    break
            epoch += 1
        losses[fused] = out
    return losses


@pytest.mark.parametrize("sparse_adj", [False, True],
                         ids=["dense", "sparse"])
def test_fused_training_trajectory_lock(sparse_adj):
    """20 real optimizer steps on ppi_tiny: the fused path (dense
    spmm_xw / fused block-ELL kernel) tracks the unfused path step for
    step within 1e-4 — dropout rng, loss and optimizer state all flow
    through the same seams."""
    losses = _locked_trajectories(sparse_adj)
    drift = max(abs(a - b)
                for a, b in zip(losses[False], losses[True]))
    assert drift < TOL, (drift, losses)
    # the run actually trained, not 20 steps of a frozen model
    assert losses[True][-1] < losses[True][0], losses[True]


def test_fused_two_device_dp_trajectory_lock(run_distributed):
    """model.fuse_spmm through the 2-device shard_map DP step (stacked
    sparse batches): fused vs unfused losses within 1e-4."""
    out = run_distributed("""
import jax
from repro.core import ClusterBatcher, GCNConfig, train_cluster_gcn
from repro.graph import make_dataset, partition_graph
from repro.nn import adamw

from repro.launch.mesh import make_mesh

mesh = make_mesh((2,), ("data",))
g = make_dataset("ppi", scale=0.03, seed=0)
parts, _ = partition_graph(g, 8, method="metis", seed=0)
cfg_kw = dict(in_dim=g.features.shape[1], hidden_dim=32,
              out_dim=g.labels.shape[1], num_layers=3, dropout=0.0,
              multilabel=True)
batcher = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0)
hist = {}
for fused in (False, True):
    cfg = GCNConfig(fuse_spmm=fused, **cfg_kw)
    res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2), num_epochs=5,
                            mesh=mesh, sparse_adj=True)
    hist[fused] = [h["loss"] for h in res.history]
drift = max(abs(a - b) for a, b in zip(hist[False], hist[True]))
assert drift < 1e-4, (drift, hist)
assert hist[True][-1] < hist[True][0], hist[True]
print("FUSED_DP_OK", drift)
""", devices=2)
    assert "FUSED_DP_OK" in out
