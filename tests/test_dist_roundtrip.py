"""repro.dist coverage beyond the seed tests: long-horizon error-feedback
round-trip and the data-parallel Cluster-GCN step (subprocess — see the
run_distributed fixture in conftest.py)."""


def test_compressed_psum_matches_uncompressed_over_many_steps(
        run_distributed):
    """Error feedback telescopes: the CUMULATIVE compressed mean matches
    the cumulative exact psum mean to tolerance over 200 steps, and the
    residual stays bounded (no drift) on a 2-device mesh."""
    out = run_distributed("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.compression import compressed_psum_mean
from repro.launch.mesh import make_mesh

mesh = make_mesh((2,), ("data",))
D = 128

def one_step(local, err):
    m, e = compressed_psum_mean(local[0], err[0], axis_name="data", bits=8)
    return m[None], e[None]

step = jax.jit(jax.shard_map(one_step, mesh=mesh,
                             in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data"))))

rng = np.random.default_rng(0)
err = jnp.zeros((2, D))
sum_c = np.zeros(D)
sum_x = np.zeros(D)
scales = []
for t in range(200):
    g = rng.normal(size=(2, D)).astype(np.float32) * 0.01
    mean_c, err = step(jnp.asarray(g), err)
    sum_c += np.asarray(mean_c[0])
    sum_x += g.mean(0)
    scales.append(float(np.abs(np.asarray(err)).max()))
rel = np.abs(sum_c - sum_x).max() / np.abs(sum_x).max()
assert rel < 5e-3, rel
# residual bounded by one quantization bucket, not growing with t
assert max(scales[-20:]) < 2 * max(scales[:20]) + 1e-4
print("ROUNDTRIP_OK", rel)
""", devices=2)
    assert "ROUNDTRIP_OK" in out


def test_gcn_data_parallel_step_learns_and_compression_tracks_exact(
        run_distributed):
    """make_gcn_train_step on a 2-device mesh: loss decreases, and the
    int8-compressed run tracks the exact-sync run closely."""
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
for comp in (None, 8):
    res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2), num_epochs=6,
                            mesh=mesh, compression=comp)
    hist[comp] = [h["loss"] for h in res.history]
assert hist[None][-1] < hist[None][0] * 0.7, hist[None]
drift = abs(hist[8][-1] - hist[None][-1]) / abs(hist[None][-1])
assert drift < 0.05, (drift, hist)
print("GCN_DP_OK", drift)
""", devices=2)
    assert "GCN_DP_OK" in out
