"""Graph attention (`model.arch="gat"`, repro.core.gat) on the dense batch
path, held to the edge-list formulation of `bench/gat_reference.py`
(a segment softmax over each node's in-edges, where the program runs a
masked softmax over the padded (cap, cap) block), on seeded random
weights at a small size: in 8 (or 6), heads 2/2/3 of 4, out 5, 60 real
nodes padded to 64, one of them with no edge but its self-loop.

Tolerances: both sides are float32 on the CPU and differ only in the
order of their sums (a row of 64 masked entries against a node's
in-edges; one X·W against per-head slices), so values agree to a few
ulps of their size: 1e-5 relative for logits and the loss. A gradient
chains three softmaxes and their backward sums, which widens that to
1e-4 relative on each leaf (atol 1e-7 for entries near zero). One Adam
step moves every parameter by about lr = 5e-3 whatever its gradient's
size; a relative gradient error e moves it by at most lr·e, so 1e-6
absolute.
"""
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import gat_reference as R  # noqa: E402
from bench import reference as REF  # noqa: E402
from repro.core import gat  # noqa: E402
from repro.core.engine import _dp_groups, make_train_step  # noqa: E402
from repro.core.experiment import (apply_overrides, build_gcn_config,  # noqa: E402
                                   preset, run_experiment, validate)
from repro.core.gcn import GCNConfig, gcn_forward, gcn_loss, init_gcn  # noqa: E402
from repro.core.trainer import full_graph_logits  # noqa: E402
from repro.graph.csr import CSRGraph  # noqa: E402
from repro.graph.normalization import normalized_dense_block  # noqa: E402
from repro.nn.optim import adamw  # noqa: E402

N, CAP, F, OUT, HEADS, LR = 60, 64, 4, 5, (2, 2, 3), 5e-3
LONELY = N - 1                     # the node with only its self-loop


def _cfg(in_dim=8, **kw):
    return GCNConfig(in_dim=in_dim, hidden_dim=F, out_dim=OUT,
                     num_layers=len(HEADS), dropout=0.0, residual=True,
                     multilabel=True, layernorm=False, arch="gat",
                     heads=HEADS, **kw)


def _graph(seed=0, in_dim=8):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, LONELY, (2, 150))
    return CSRGraph.from_edges(
        N, src, dst,
        features=rng.normal(size=(N, in_dim)).astype(np.float32),
        labels=(rng.random((N, OUT)) < 0.3).astype(np.float32),
        train_mask=rng.random(N) < 0.7)


def _dense(g):
    """The program's padded batch of every node, in order."""
    def pad(a):
        out = np.zeros((CAP,) + a.shape[1:], np.float32)
        out[:N] = a
        return out
    adj = normalized_dense_block(g.indptr, g.indices, g.data, CAP, "eq10")
    node_mask = np.arange(CAP) < N
    return (jnp.asarray(adj), jnp.asarray(pad(g.features)),
            jnp.asarray(pad(g.labels)), jnp.asarray(node_mask),
            jnp.asarray(pad(g.train_mask.astype(np.float32))),
            jnp.int32(N))


def _edges(g):
    rg = REF.Graph.from_arrays(g.indptr, g.indices, g.data, g.features,
                               g.labels, g.train_mask)
    return R.edge_batch(rg, np.arange(N))


def _params(cfg, seed=1):
    return R.init_params(seed, R.layer_shapes(cfg.in_dim, F, OUT, HEADS))


def test_reference_weights_fit_the_program():
    cfg = _cfg()
    mine = jax.tree_util.tree_map(jnp.shape,
                                  init_gcn(jax.random.PRNGKey(0), cfg))
    assert jax.tree_util.tree_map(jnp.shape, _params(cfg)) == mine
    assert mine["layers"][2] == {"w": (8, 15), "a_l": (3, 5),
                                 "a_r": (3, 5), "b": (15,)}


@pytest.mark.parametrize("in_dim", [8, 6])   # skip at layers 1-2, or 2
def test_logits_match_the_reference(in_dim):
    cfg, g = _cfg(in_dim), _graph(in_dim=in_dim)
    params = _params(cfg)
    batch = _dense(g)
    got = gcn_forward(params, batch[0], batch[1], cfg)
    want = R.forward(params, _edges(g), residual=True)
    assert got.shape == (CAP, OUT)
    np.testing.assert_allclose(got[:N], want[:N], rtol=1e-5, atol=1e-6)


def test_loss_and_every_gradient_match_the_reference():
    cfg, g = _cfg(), _graph()
    params = _params(cfg)
    (loss, _), grads = jax.value_and_grad(gcn_loss, has_aux=True)(
        params, _dense(g), cfg, train=True, rng=jax.random.PRNGKey(0))
    ref_loss, ref_grads = jax.value_and_grad(R.batch_loss)(
        params, _edges(g), True)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat, ref_flat = (jax.tree_util.tree_leaves_with_path(t)
                      for t in (grads, ref_grads))
    for (path, a), (_, b) in zip(flat, ref_flat):
        assert float(jnp.abs(b).max()) > 0, path
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def test_one_adam_step_matches_the_reference():
    cfg, g = _cfg(), _graph()
    params = _params(cfg)
    opt = adamw(LR)
    step = make_train_step(cfg, opt)
    copy = jax.tree_util.tree_map(jnp.copy, params)
    new, _, _, loss, _ = step(copy, opt.init(copy), jax.random.PRNGKey(0),
                              _dense(g))
    losses, _, ref = R.run_steps(params, [[_edges(g)]], residual=True,
                                 lr=LR)
    np.testing.assert_allclose(loss, losses[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def _layer0(cfg):
    return _params(cfg)["layers"][0]


def test_a_row_with_only_its_self_loop_is_its_own_message():
    cfg, g = _cfg(), _graph()
    adj, x = _dense(g)[:2]
    layer = _layer0(cfg)
    z = gat.gat_layer(layer, x, gat.attention_mask(adj), concat=True)
    assert not np.asarray(adj)[LONELY, :LONELY].any()
    np.testing.assert_allclose(z[LONELY], x[LONELY] @ layer["w"]
                               + layer["b"], rtol=1e-6, atol=1e-7)
    # padding rows attend to themselves alone: finite, no NaN
    assert np.isfinite(np.asarray(z)).all()


def test_masked_columns_get_exactly_zero_weight():
    cfg, g = _cfg(), _graph()
    adj, x = _dense(g)[:2]
    mask = gat.attention_mask(adj)
    layer = _layer0(cfg)
    j = 3
    moved = np.array(x)
    moved[N:] = 1e3                 # padding rows
    moved[j] = -7.0                 # one real node
    a = gat.gat_layer(layer, x, mask, concat=True)
    b = gat.gat_layer(layer, jnp.asarray(moved), mask, concat=True)
    blind = ~np.asarray(mask)[:N, j]    # real rows that do not see j
    assert blind.sum() > N // 2
    np.testing.assert_array_equal(np.asarray(a)[:N][blind],
                                  np.asarray(b)[:N][blind])


def test_heads_concatenate_in_hidden_layers_and_average_at_the_output():
    cfg, g = _cfg(), _graph()
    adj, x = _dense(g)[:2]
    mask = gat.attention_mask(adj)
    layer = _layer0(cfg)
    k, f = layer["a_l"].shape

    def head(i):
        one = {"w": layer["w"][:, i * f:(i + 1) * f],
               "a_l": layer["a_l"][i:i + 1], "a_r": layer["a_r"][i:i + 1],
               "b": layer["b"][i * f:(i + 1) * f]}
        return gat.gat_layer(one, x, mask, concat=True)

    heads = [head(i) for i in range(k)]
    np.testing.assert_allclose(
        gat.gat_layer(layer, x, mask, concat=True),
        jnp.concatenate(heads, 1), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        gat.gat_layer(layer, x, mask, concat=False),
        sum(heads) / k, rtol=1e-6, atol=1e-7)


def test_the_skip_is_added_only_where_widths_match():
    z, h = jnp.full((3, 4), -0.5), jnp.ones((3, 4))
    np.testing.assert_allclose(gat.activate(z, h, True), jnp.full((3, 4),
                                                                  0.5))
    np.testing.assert_allclose(gat.activate(z, h, False),
                               jnp.expm1(jnp.full((3, 4), -0.5)))
    np.testing.assert_allclose(gat.activate(z, jnp.ones((3, 6)), True),
                               jnp.expm1(jnp.full((3, 4), -0.5)))


def test_edge_list_eval_equals_the_dense_batched_forward():
    cfg, g = _cfg(), _graph()
    params = _params(cfg)
    batch = _dense(g)
    dense = gcn_forward(params, batch[0], batch[1], cfg)
    np.testing.assert_allclose(full_graph_logits(params, g, cfg),
                               dense[:N], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("field, value", [
    ("batch.sparse_adj", True), ("model.fuse_spmm", True),
    ("model.precompute_ax", True), ("model.layernorm", True),
    ("model.heads", [4, 4]), ("model.arch", "gin")])
def test_validate_refuses_what_gat_cannot_run(field, value):
    spec = apply_overrides(preset("ppi_gat"), {field: value})
    with pytest.raises(ValueError, match=f"spec.{field}"):
        validate(spec)


def test_validate_refuses_heads_on_a_gcn():
    spec = apply_overrides(preset("ppi_sota"), {"model.heads": [1] * 5})
    with pytest.raises(ValueError, match="spec.model.heads"):
        validate(spec)


def test_ppi_gat_preset_is_the_published_model():
    s = preset("ppi_gat")
    m = s.model
    assert (m.arch, m.heads, m.hidden_dim, m.num_layers) == \
        ("gat", [4, 4, 6], 256, 3)
    assert (m.residual, m.layernorm, m.dropout) == (True, False, 0.0)
    assert (s.optim.lr, s.optim.weight_decay) == (5e-3, 0.0)
    assert (s.data.scale, s.partition.num_parts,
            s.batch.clusters_per_batch) == (4.06743, 50, 4)
    cfg = build_gcn_config(s, _graph())
    assert cfg.heads == (4, 4, 6) and cfg.arch == "gat"
    assert [d for d in gat.layer_shapes(
        GCNConfig(in_dim=50, hidden_dim=256, out_dim=121, arch="gat",
                  heads=(4, 4, 6)))] == [(50, 4, 256), (1024, 4, 256),
                                         (1024, 6, 121)]


def test_run_experiment_trains_gat_through_the_engine():
    spec = apply_overrides(preset("ppi_gat"), {
        "data.scale": 0.03, "partition.num_parts": 8,
        "batch.clusters_per_batch": 2, "model.hidden_dim": 8,
        "partition.cache": False, "run.epochs": 2, "run.eval_every": 1,
        "run.verbose": False})
    exp, res = run_experiment(spec)
    assert exp.cfg.arch == "gat"
    assert [h["epoch"] for h in res.history] == [0, 1]
    assert all(np.isfinite(h["loss"]) for h in res.history)
    assert 0.0 <= res.history[-1]["val_score"] <= 1.0


SCOPES = re.compile(r"(?<![\w.])(gat|gcn)\.([a-z_]+)")


def _scopes(cfg, batch):
    """The model scopes named in the lowered loss's op locations."""
    params = init_gcn(jax.random.PRNGKey(0), cfg)
    f = jax.jit(lambda p, b: gcn_loss(p, b, cfg, train=False)[0])
    text = f.lower(params, batch).as_text(debug_info=True)
    return {".".join(m) for m in SCOPES.findall(text)}


def test_the_gcn_path_holds_no_gat_scope():
    g = _graph()
    batch = _dense(g)
    gcn = GCNConfig(in_dim=8, hidden_dim=F, out_dim=OUT, num_layers=3,
                    multilabel=True)
    scopes = _scopes(gcn, batch)
    assert {"gcn.xw", "gcn.aggregate"} <= scopes
    assert not any(s.startswith("gat.") for s in scopes)
    scopes = _scopes(_cfg(), batch)
    assert {"gat.xw", "gat.attention", "gat.aggregate"} <= scopes
    assert not {"gcn.xw", "gcn.aggregate"} & scopes


def test_data_parallel_step_takes_the_mean_gradient(run_distributed):
    out = run_distributed(f"""
import sys
sys.path.insert(0, {str(ROOT)!r}); sys.path.insert(0, {str(ROOT / 'tests')!r})
import jax, jax.numpy as jnp, numpy as np
import test_gat as T
from repro.core.engine import ShardMapBackend
from repro.core.gcn import gcn_loss
from repro.launch.mesh import make_mesh
from repro.nn.optim import adamw
cfg = T._cfg()
params = T._params(cfg)
batches = [T._dense(T._graph(seed)) for seed in (0, 1)]
backend = ShardMapBackend(cfg, adamw(T.LR), make_mesh((2,), ("data",)))
state = backend.init(jax.tree_util.tree_map(jnp.copy, params),
                      jax.random.PRNGKey(0))
payload = next(iter(backend.stream(iter(
    [tuple(np.asarray(x) for x in b) for b in batches]))))
state, loss, _ = backend.step(state, payload)
g = state["dist"]["opt"].mu
grads = [jax.grad(lambda p: gcn_loss(p, b, cfg, train=False)[0])(params)
         for b in batches]
worst = max(float(jnp.abs(a / 0.1 - (b + c) / 2).max()
                  / jnp.abs((b + c) / 2).max())
            for a, b, c in zip(*(jax.tree_util.tree_leaves(t)
                                 for t in (g, *grads))))
print("WORST", worst)
""", devices=2)
    worst = float(out.split("WORST")[1])
    # the same float32 sums on each shard, then one mean over two
    assert worst < 1e-5


def test_dp_wraparound_repeats_are_copies_not_new_pulls():
    """The short last group of an epoch is filled with copies of its
    first batches; the sampler yields each raw batch once, so a count
    of what it yielded (the benchmark's nodes) leaves the repeats
    out."""
    pulled = []

    def sampler():
        for i in range(5):
            pulled.append(i)
            yield (np.full((2,), i),)

    groups = list(_dp_groups(sampler(), 4))
    assert len(groups) == 2 and pulled == [0, 1, 2, 3, 4]
    assert [int(b[0][0]) for b in groups[1]] == [4, 0, 1, 2]


def test_serving_refuses_gat():
    from repro.launch import serve_gcn
    from repro.serve.engine import ServeEngine
    with pytest.raises(ValueError, match="arch='gat'"):
        ServeEngine(None, None, np.zeros(1, np.int32), _cfg(), cache=None)
    with pytest.raises(SystemExit):
        serve_gcn.main(["--preset", "ppi_gat"])
