"""Serving layer (repro.serve): serving/training parity, surgical
cache invalidation, bucket ladder, params-only checkpoint restore,
GraphDelta/append semantics, spec wiring, and the serve_gcn CLI."""
import json

import jax
import numpy as np
import pytest

from repro.core.experiment import (ExperimentSpec, build_experiment,
                                   preset, validate)
from repro.core.gcn import GCNConfig, init_gcn
from repro.core.trainer import full_graph_logits
from repro.graph.csr import CSRGraph, append_graph
from repro.graph.partition import partition_fingerprint
from repro.runtime.checkpoint import CheckpointManager
from repro.serve import (BalanceMonitor, EmbeddingCache, GraphDelta,
                         ServeEngine, apply_delta, embed_cluster,
                         full_graph_embeddings)

PARITY_TOL = 1e-5


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One trained ppi_tiny run shared by the module: spec, the built
    Experiment (graph/parts/cfg), and its checkpoint dir."""
    spec = preset("ppi_tiny")
    spec.run.epochs = 2
    spec.run.checkpoint_dir = str(tmp_path_factory.mktemp("serve-ck"))
    exp = build_experiment(spec)
    exp.fit()
    return spec, exp


@pytest.fixture()
def engine(trained, tmp_path):
    spec, exp = trained
    return ServeEngine.from_checkpoint(spec, graph=exp.graph,
                                       cache_root=tmp_path / "cache")


def _dense_ref(engine):
    return np.asarray(full_graph_logits(
        engine.params, engine.graph, engine.cfg, norm=engine.norm,
        diag_lambda=engine.diag_lambda))


# ----------------------------------------------------------------------
# serving/training parity
# ----------------------------------------------------------------------
def test_cached_serving_matches_dense_forward(engine):
    """Every served logit — warm cache, all clusters — matches the
    one-shot dense full-graph forward to 1e-5, explicitly including
    nodes with cross-cluster edges (the rows training's within-cluster
    approximation drops, and serving must not)."""
    engine.warm()
    ref = _dense_ref(engine)
    g = engine.graph
    r = engine.query(np.arange(g.num_nodes))     # chunked over buckets
    assert np.abs(r.logits - ref).max() <= PARITY_TOL
    # the cross-cluster nodes specifically
    row_of = np.repeat(np.arange(g.num_nodes), g.degrees)
    cross = np.unique(row_of[engine.parts[row_of]
                             != engine.parts[g.indices]])
    assert len(cross) > 0, "ppi_tiny partition has no cut edges?"
    rc = engine.query(cross[:engine.buckets[-1]])
    assert np.abs(rc.logits - ref[rc.node_ids]).max() <= PARITY_TOL
    # probabilities come from the jit'd step: multilabel ppi → sigmoid
    np.testing.assert_allclose(
        rc.probs, 1.0 / (1.0 + np.exp(-rc.logits)), atol=1e-6)


def test_halo_reembed_equals_blocked_full_pass(trained):
    """The lazy single-cluster L-hop-halo path and the blocked
    full-graph pass agree — an invalidated cluster re-embeds to the
    same values it would get from a full precompute."""
    spec, exp = trained
    params = init_gcn(jax.random.PRNGKey(0), exp.cfg)
    z = full_graph_embeddings(params, exp.graph, exp.parts, exp.cfg,
                              norm=spec.batch.norm,
                              diag_lambda=spec.batch.diag_lambda)
    for c in (0, exp.parts.max()):
        rows = np.where(exp.parts == c)[0]
        zc = embed_cluster(params, exp.graph, exp.cfg, rows,
                           norm=spec.batch.norm,
                           diag_lambda=spec.batch.diag_lambda)
        assert np.abs(zc - z[rows]).max() <= PARITY_TOL


# ----------------------------------------------------------------------
# live updates: surgical invalidation
# ----------------------------------------------------------------------
def test_delta_influence_region_touched_clusters():
    """`apply_delta` invalidates exactly the clusters intersecting the
    num_layers-hop neighborhood of the changed nodes: on a path graph a
    far cluster is provably unreachable within L hops and stays out of
    the touched set, while near clusters are in it."""
    n = 12                                   # path 0-1-...-11
    g = CSRGraph.from_edges(n, range(n - 1), range(1, n),
                            features=np.eye(n, dtype=np.float32))
    parts = np.repeat(np.arange(3), 4)       # [0..3] [4..7] [8..11]
    delta = GraphDelta(src=(0,), dst=(2,))   # changes Â rows/cols 0, 2
    _, _, touched = apply_delta(g, parts, delta, num_layers=3)
    assert touched == [0, 1]                 # 3-hop region = {0..5}
    _, _, touched = apply_delta(g, parts, delta, num_layers=1)
    assert touched == [0]                    # 1-hop region = {0..3}
    with pytest.raises(ValueError, match="num_layers"):
        apply_delta(g, parts, delta, num_layers=0)


def test_delta_invalidation_is_surgical(tmp_path):
    """On a graph where the delta's influence region provably stays
    inside cluster 0, ONLY cluster 0 recomputes (counter-locked), every
    other cluster answers bitwise-identically to pre-delta, and EVERY
    cluster — touched or not — matches the dense forward on the GROWN
    graph. Also pins the re-key: the base cache directory keeps all its
    cluster files, so engines on the un-grown graph stay clean."""
    rng = np.random.default_rng(0)
    n = 24                                   # path graph, 4 clusters of 6
    g = CSRGraph.from_edges(
        n, range(n - 1), range(1, n),
        features=rng.normal(size=(n, 5)).astype(np.float32))
    parts = np.repeat(np.arange(4), 6)
    cfg = GCNConfig(in_dim=5, hidden_dim=8, out_dim=3, num_layers=2)
    params = init_gcn(jax.random.PRNGKey(0), cfg)
    cache = EmbeddingCache(
        tmp_path, checkpoint_step=0,
        partition_fingerprint=partition_fingerprint(g, parts))
    eng = ServeEngine(params, g, parts, cfg, cache=cache, max_batch=32)
    eng.warm()
    base_dir = eng.cache.dir
    pre = eng.query(np.arange(n))
    before = dict(eng.cache.recompute_counts)

    # edge 0-2: 2-hop region = {0..4}, strictly inside cluster {0..5}
    info = eng.apply_delta(GraphDelta(src=(0,), dst=(2,)))
    assert info["touched_clusters"] == [0]
    assert info["invalidated_clusters"] == [0]
    # cache re-keyed onto the grown fingerprint; base dir untouched
    assert eng.cache.dir != base_dir
    assert sorted(int(p.stem.split("_")[1])
                  for p in base_dir.glob("cluster_*.npy")) == [0, 1, 2, 3]

    post = eng.query(np.arange(n))
    ref = _dense_ref(eng)                    # dense forward, grown graph
    assert np.abs(post.logits - ref).max() <= PARITY_TOL
    rest = np.arange(6, n)                   # clusters 1-3: untouched
    assert np.array_equal(pre.logits[rest], post.logits[rest])
    assert np.array_equal(pre.probs[rest], post.probs[rest])
    after = dict(eng.cache.recompute_counts)
    for c in range(4):
        expected = before.get(c, 0) + (1 if c == 0 else 0)
        assert after.get(c, 0) == expected, (c, before, after)


def test_delta_invalidation_exact_on_ppi(engine):
    """The same contract on ppi_tiny, whose partition has real cut
    edges: after a delta, every cluster — inside or outside the touched
    set — serves logits matching the dense forward on the grown graph,
    and untouched clusters answer bitwise-identically without
    recomputing."""
    engine.warm()
    g, parts = engine.graph, engine.parts
    c_target = int(parts[0])
    in_c = np.where(parts == c_target)[0]
    # a genuinely NEW edge: re-announcing an existing one is a no-op
    u = int(in_c[0])
    nbrs = set(int(w) for w in g.neighbors(u))
    v = next(int(w) for w in in_c[::-1]
             if int(w) != u and int(w) not in nbrs)
    before = dict(engine.cache.recompute_counts)
    pre = engine.query(np.arange(g.num_nodes))

    info = engine.apply_delta(GraphDelta(src=(u,), dst=(v,)))
    touched = info["touched_clusters"]
    assert c_target in touched
    assert info["invalidated_clusters"] == touched   # cache was warm

    post = engine.query(np.arange(engine.graph.num_nodes))
    ref = _dense_ref(engine)
    # the serving-parity contract survives the delta for EVERY node,
    # cross-cluster edges included — not just the touched cluster
    assert np.abs(post.logits - ref).max() <= PARITY_TOL
    untouched_nodes = np.where(~np.isin(parts, touched))[0]
    if len(untouched_nodes):
        assert np.array_equal(pre.logits[untouched_nodes],
                              post.logits[untouched_nodes])
    after = dict(engine.cache.recompute_counts)
    for c in range(engine.num_parts):
        expected = before.get(c, 0) + (1 if c in touched else 0)
        assert after.get(c, 0) == expected, (c, before, after)
    # re-announcing the same edge: graph unchanged → nothing stale
    again = engine.apply_delta(GraphDelta(src=(u,), dst=(v,)))
    assert again["touched_clusters"] == []
    assert again["invalidated_clusters"] == []


def test_delta_new_node_joins_neighbor_cluster(engine):
    engine.warm()
    anchor = 3
    c_anchor = int(engine.parts[anchor])
    n_before = engine.graph.num_nodes
    feat = np.ones((1, engine.graph.features.shape[1]), np.float32)
    info = engine.apply_delta(GraphDelta(
        src=(anchor,), dst=(n_before,), num_new_nodes=1, features=feat))
    assert engine.graph.num_nodes == n_before + 1
    assert int(engine.parts[n_before]) == c_anchor
    assert c_anchor in info["touched_clusters"]
    # the new node is servable and exact
    ref = _dense_ref(engine)
    r = engine.query([n_before])
    assert np.abs(r.logits - ref[n_before]).max() <= PARITY_TOL


def test_balance_monitor_warns_and_fires_hook():
    fired = []
    mon = BalanceMonitor(threshold=1.5,
                         on_rebalance=lambda imb, sizes: fired.append(imb))
    ok = np.repeat(np.arange(4), 5)               # perfectly balanced
    assert mon.check(ok) == pytest.approx(1.0)
    assert fired == []
    skew = np.concatenate([ok, np.zeros(10, int)])  # cluster 0 triples
    with pytest.warns(RuntimeWarning, match="re-partition"):
        imb = mon.check(skew)
    assert imb > 1.5 and len(fired) == 1
    # warn-once per exceedance streak: no second warning while high
    mon.check(skew)
    assert len(fired) == 1
    with pytest.raises(ValueError):
        BalanceMonitor(threshold=1.0)


# ----------------------------------------------------------------------
# bucket ladder / padding
# ----------------------------------------------------------------------
def test_bucket_ladder_padding_and_chunking(engine):
    engine.warm()
    assert engine.buckets == [1, 8, 64, 256]
    assert engine.bucket_for(1) == 1
    assert engine.bucket_for(2) == 8
    assert engine.bucket_for(65) == 256
    r = engine.query([0, 1, 2])                  # pads 3 → 8
    assert r.bucket == 8 and r.logits.shape == (3, engine.cfg.out_dim)
    assert r.topk_ids.shape == (3, engine.top_k)
    # oversize request: chunked through the cap bucket, order kept
    ids = np.arange(engine.graph.num_nodes)[:300]
    big = engine.query(ids)
    assert big.bucket == 256 and len(big.logits) == 300
    np.testing.assert_array_equal(big.node_ids, ids)
    with pytest.raises(ValueError, match="out of range"):
        engine.query([engine.graph.num_nodes])


def test_explicit_buckets_validated():
    spec = preset("ppi_tiny")
    spec.serve.buckets = [4, 32]
    validate(spec)
    spec.serve.buckets = [32, 4]
    with pytest.raises(ValueError, match="serve.buckets"):
        validate(spec)
    spec.serve.buckets = []
    with pytest.raises(ValueError, match="serve.buckets"):
        validate(spec)
    spec.serve.buckets = None
    spec.serve.imbalance_threshold = 1.0
    with pytest.raises(ValueError, match="imbalance_threshold"):
        validate(spec)


# ----------------------------------------------------------------------
# embedding cache mechanics
# ----------------------------------------------------------------------
def test_embedding_cache_store_load_invalidate(tmp_path):
    cache = EmbeddingCache(tmp_path, checkpoint_step=7,
                           partition_fingerprint="abc123")
    assert "step0000000007_abc123" in str(cache.dir)
    emb = np.arange(12, dtype=np.float32).reshape(3, 4)
    cache.store(1, emb)
    assert cache.has(1) and cache.cached_clusters() == [1]
    np.testing.assert_array_equal(np.asarray(cache.load(1)), emb)
    assert cache.recompute_counts[1] == 1
    assert cache.invalidate(1) is True
    assert not cache.has(1)
    assert cache.invalidate(1) is False          # idempotent
    # no stray tmp files from the atomic write
    assert not list(cache.dir.glob("*.tmp"))


def test_embedding_cache_rekey_carries_untouched(tmp_path):
    cache = EmbeddingCache(tmp_path, checkpoint_step=7,
                           partition_fingerprint="base")
    a = np.zeros((2, 3), np.float32)
    b = np.ones((2, 3), np.float32)
    cache.store(0, a)
    cache.store(1, b)
    new = cache.rekey("grown", drop=[1])
    assert new.dir != cache.dir
    assert new.has(0) and not new.has(1)
    np.testing.assert_array_equal(np.asarray(new.load(0)), a)
    # base directory untouched: both clusters still served from it
    assert cache.cached_clusters() == [0, 1]
    # counter history carries across; same fingerprint is a no-op
    assert new.recompute_counts is cache.recompute_counts
    assert new.rekey("grown") is new


def test_cache_key_changes_with_partition(trained):
    spec, exp = trained
    fp1 = partition_fingerprint(exp.graph, exp.parts)
    fp2 = partition_fingerprint(exp.graph, (exp.parts + 1)
                                % (exp.parts.max() + 1))
    assert fp1 != fp2


# ----------------------------------------------------------------------
# CSR append
# ----------------------------------------------------------------------
def test_append_graph_semantics():
    g = CSRGraph.from_edges(3, [0, 1], [1, 2],
                            features=np.eye(3, dtype=np.float32))
    g2 = append_graph(g, num_new_nodes=1, src=[2], dst=[3],
                      features=np.zeros((1, 3), np.float32))
    assert g2.num_nodes == 4
    assert sorted(g2.neighbors(3)) == [2]
    assert sorted(g2.neighbors(2)) == [1, 3]
    # input untouched; re-announcing a known edge is a no-op
    assert g.num_nodes == 3
    g3 = append_graph(g2, src=[0], dst=[1])
    assert g3.num_edges == g2.num_edges
    with pytest.raises(ValueError, match="out of range"):
        append_graph(g, src=[0], dst=[5])
    with pytest.raises(ValueError, match="features"):
        append_graph(g, num_new_nodes=1)


# ----------------------------------------------------------------------
# params-only checkpoint restore
# ----------------------------------------------------------------------
@pytest.fixture
def params_tree():
    return {"w": jax.numpy.arange(6.0).reshape(2, 3),
            "b": jax.numpy.ones((3,))}


def test_restore_params_from_engine_checkpoint(trained):
    """restore_params on a real training checkpoint returns exactly the
    params the full Engine restore would."""
    spec, exp = trained
    mgr = CheckpointManager(spec.run.checkpoint_dir)
    template = init_gcn(jax.random.PRNGKey(spec.run.seed), exp.cfg)
    params, step = mgr.restore_params(template)
    assert step == mgr.latest_valid_step()
    full = exp.engine.backend.params(
        mgr.restore(exp.engine.state, step=step))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(full)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_restore_params_walks_back_past_corrupt_newest(tmp_path,
                                                       params_tree):
    """Same self-healing semantics as Engine.fit(resume=True): the
    corrupt newest step is quarantined and the previous intact one is
    served; an explicitly requested corrupt step still raises."""
    m = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    m.save(1, {"params": params_tree})
    m.save(2, {"params": jax.tree_util.tree_map(lambda x: x + 100.0,
                                                params_tree)})
    shard = tmp_path / "step_0000000002" / "shard_0.npz"
    z = np.load(shard)
    arrs = {k: z[k] for k in z.files}
    arrs["params__w"] = arrs["params__w"] + 1.0   # crc mismatch
    np.savez(shard, **arrs)
    with pytest.raises(IOError, match="checksum"):
        m.restore_params(params_tree, step=2)
    with pytest.warns(UserWarning, match="quarantined"):
        params, step = m.restore_params(params_tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(params["w"]),
                                  np.arange(6.0).reshape(2, 3))


def test_restore_params_all_corrupt_raises(tmp_path, params_tree):
    m = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    m.save(1, {"params": params_tree})
    shard = tmp_path / "step_0000000001" / "shard_0.npz"
    shard.write_bytes(b"garbage")
    with pytest.warns(UserWarning, match="quarantined"):
        with pytest.raises(FileNotFoundError, match="no valid"):
            m.restore_params(params_tree)


def test_restore_params_finds_dist_prefix(tmp_path, params_tree):
    """ShardMapBackend states keep params under dist/params — the
    params-only loader finds either layout."""
    m = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    m.save(3, {"dist": {"params": params_tree}, "extra": params_tree})
    params, step = m.restore_params(params_tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(params["b"]), np.ones(3))
    m2 = CheckpointManager(str(tmp_path / "other"), async_save=False)
    m2.save(1, {"opt_state": params_tree})
    with pytest.raises(KeyError, match="params"):
        m2.restore_params(params_tree)


# ----------------------------------------------------------------------
# spec wiring
# ----------------------------------------------------------------------
def test_serve_spec_round_trip_and_back_compat():
    spec = preset("ppi_tiny")
    spec.serve.max_batch = 64
    spec.serve.top_k = 3
    text = spec.to_json()
    again = ExperimentSpec.from_json(text)
    assert again.serve.max_batch == 64 and again.serve.top_k == 3
    assert json.loads(again.to_json()) == json.loads(text)
    # specs written before the serve section existed still load
    d = json.loads(text)
    d.pop("serve")
    old = ExperimentSpec.from_dict(d)
    assert old.serve.max_batch == 256          # defaults
    with pytest.raises(ValueError, match="unknown field"):
        ExperimentSpec.from_dict(
            {**json.loads(preset("ppi_tiny").to_json()),
             "serve": {"nope": 1}})


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
def test_serve_gcn_cli_end_to_end(trained, tmp_path, capsys, monkeypatch):
    from repro.launch.serve_gcn import main
    # main() keeps the persistent compile cache where this variable says;
    # set after jax started, it leaves the cache off in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    spec, _ = trained
    bench = tmp_path / "BENCH_serve.json"
    rc = main(["--preset", "ppi_tiny", "--queries", "96",
               "--checkpoint-dir", spec.run.checkpoint_dir,
               "--results-dir", str(tmp_path / "results"),
               "--verify-parity", "--bench-out", str(bench)])
    assert rc == 0
    doc = json.loads(bench.read_text())
    buckets = [r for r in doc["rows"] if "p50_s" in r]
    assert len(buckets) >= 2                     # ≥2 padding buckets
    for r in buckets:
        assert np.isfinite(r["p50_s"]) and r["p50_s"] > 0
        assert np.isfinite(r["p99_ms"])
    assert doc["qps"] > 0
    assert any(r["name"].endswith("/precompute") for r in doc["rows"])
