"""GraphSAINT-style sampler subsystem (repro.core.samplers):

* Sampler-protocol conformance and the fixed-shape payload contract
  (same contract the cluster batcher emits — that is what lets the
  Engine/backends consume samplers polymorphically);
* epoch-stream determinism: the batch sequence is a pure function of
  (seed, epoch), bitwise;
* loss-normalization unbiasedness, Monte-Carlo: E[Σ w_v·f_v] over
  sampled training nodes equals the full-graph training sum for any
  per-node values f (the raw estimator), and the self-normalized batch
  loss that gcn_loss computes estimates the full-graph mean training
  loss;
* ExperimentSpec integration: batch.sampler round-trips through JSON,
  validate() rejects bad values, the default budget derivation, and
  kill → `Engine.fit(resume=True)` reproducing the straight-run
  trajectory bitwise for both samplers (the cluster-batcher guarantee,
  extended);
* the sparse block-ELL path (k_slots="auto" bucket planning) working
  unchanged on SAINT batches, and the run_experiment CLI driving
  `--set batch.sampler=saint_node` end-to-end.
"""
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core import StopAtStepHook
from repro.core.batching import ClusterBatcher, Sampler
from repro.core.experiment import (BatchSpec, DataSpec, ExperimentSpec,
                                   ModelSpec, OptimSpec, PartitionSpec,
                                   RunSpec, apply_overrides,
                                   build_experiment, preset, validate)
from repro.core.gcn import GCNConfig, init_gcn
from repro.core.samplers import SaintEdgeSampler, SaintNodeSampler
from repro.core.trainer import full_graph_logits
from repro.graph.generators import make_dataset


@pytest.fixture(scope="module")
def graph():
    return make_dataset("cora", scale=0.2, seed=0)   # ~540 nodes


def _sampler(graph, kind, **kw):
    if kind == "node":
        return SaintNodeSampler(graph, kw.pop("budget", 128), **kw)
    if kind == "node_deg":
        return SaintNodeSampler(graph, kw.pop("budget", 128),
                                degree_weighted=True, **kw)
    return SaintEdgeSampler(graph, kw.pop("budget", 96), **kw)


KINDS = ["node", "node_deg", "edge"]


# ----------------------------------------------------------------------
# protocol + payload contract
# ----------------------------------------------------------------------
def test_samplers_satisfy_protocol(graph):
    parts = np.arange(graph.num_nodes) % 8
    assert isinstance(ClusterBatcher(graph, parts), Sampler)
    for kind in KINDS:
        assert isinstance(_sampler(graph, kind), Sampler)


@pytest.mark.parametrize("kind", KINDS)
def test_payload_contract(graph, kind):
    s = _sampler(graph, kind, seed=1)
    batch = next(iter(s.epoch(0)))
    cap = s.node_cap
    assert cap % s.pad_multiple == 0
    assert batch.adj.shape == (cap, cap)
    assert batch.features.shape == (cap, graph.features.shape[1])
    b = int(batch.num_real)
    assert 0 < b <= cap
    assert batch.node_mask.sum() == b
    # padding rows/cols of the adjacency are exactly zero
    assert not batch.adj[b:].any() and not batch.adj[:, b:].any()
    # loss weights: zero on padding and non-training nodes, else > 0
    assert not batch.loss_mask[b:].any()
    nodes, w = s.draw(np.random.default_rng((s.seed, 0)))
    assert np.array_equal(batch.features[:b],
                          graph.features[nodes])   # same draw stream
    train = graph.train_mask[nodes]
    np.testing.assert_allclose(batch.loss_mask[:b],
                               w * train.astype(np.float32), rtol=1e-6)
    assert (w > 0).all()


@pytest.mark.parametrize("kind", KINDS)
def test_epoch_stream_deterministic_per_seed_and_epoch(graph, kind):
    a, b = _sampler(graph, kind, seed=3), _sampler(graph, kind, seed=3)
    ba, bb = list(a.epoch(1)), list(b.epoch(1))
    assert len(ba) == a.steps_per_epoch() > 1
    for x, y in zip(ba, bb):
        for lx, ly in zip(x.astuple(), y.astuple()):
            np.testing.assert_array_equal(np.asarray(lx), np.asarray(ly))
    # a different epoch (or seed) yields a different stream
    other = next(iter(a.epoch(0)))
    assert not np.array_equal(other.features, ba[0].features)


def test_edge_sampler_needs_edges():
    g = make_dataset("cora", scale=0.2, seed=0)
    import repro.graph.csr as csr
    empty = csr.CSRGraph(indptr=np.zeros(5, np.int64),
                         indices=np.zeros(0, np.int32),
                         data=np.zeros(0, np.float32))
    with pytest.raises(ValueError, match="at least one edge"):
        SaintEdgeSampler(empty, 4)
    with pytest.raises(ValueError, match="budget"):
        SaintNodeSampler(g, 0)
    with pytest.raises(ValueError, match="node_cap"):
        SaintNodeSampler(g, 256, node_cap=128)


# ----------------------------------------------------------------------
# loss-normalization unbiasedness (Monte Carlo)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_loss_weights_unbiased(graph, kind):
    """E[Σ_v w_v·f_v] over sampled TRAIN nodes = Σ_train f_v for any
    per-node values f — the raw unbiased-estimator guarantee — and
    E[Σ_v w_v] = |train| (the denominator gcn_loss divides by)."""
    s = _sampler(graph, kind, seed=0)
    rng = np.random.default_rng(7)
    f = rng.uniform(0.5, 1.5, graph.num_nodes)
    train = graph.train_mask.astype(np.float64)
    target = float((f * train).sum())
    n_train = float(train.sum())
    draws = 600
    est = np.empty(draws)
    wsum = np.empty(draws)
    for i in range(draws):
        nodes, w = s.draw(rng)
        t = train[nodes]
        est[i] = (w * f[nodes] * t).sum()
        wsum[i] = (w * t).sum()
    assert abs(est.mean() - target) < 0.03 * target, (est.mean(), target)
    assert abs(wsum.mean() - n_train) < 0.03 * n_train


@pytest.mark.parametrize("kind", KINDS)
def test_sampled_loss_estimates_full_graph_loss(graph, kind):
    """The self-normalized batch loss (exactly what gcn_loss computes
    from the emitted loss_mask: Σ w·L / Σ w) estimates the full-graph
    mean training loss. Per-node losses come from FULL-graph logits at
    fixed params so the test isolates the loss-normalization layer from
    subgraph-embedding bias."""
    cfg = GCNConfig(in_dim=graph.features.shape[1], hidden_dim=8,
                    out_dim=int(graph.labels.max()) + 1, num_layers=2,
                    multilabel=False)
    params = init_gcn(jax.random.PRNGKey(0), cfg)
    logits = full_graph_logits(params, graph, cfg)
    logits = logits - logits.max(-1, keepdims=True)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    nll = -logp[np.arange(graph.num_nodes), graph.labels]
    train = graph.train_mask.astype(np.float64)
    full_loss = float((nll * train).sum() / train.sum())

    s = _sampler(graph, kind, seed=0)
    rng = np.random.default_rng(11)
    losses = []
    for _ in range(400):
        nodes, w = s.draw(rng)
        t = train[nodes]
        denom = (w * t).sum()
        if denom > 0:
            losses.append((w * t * nll[nodes]).sum() / denom)
    assert abs(np.mean(losses) - full_loss) < 0.05 * full_loss, (
        np.mean(losses), full_loss)


# ----------------------------------------------------------------------
# ExperimentSpec integration
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", ["ppi_tiny_saint", "reddit_tiny_saint"])
def test_saint_preset_round_trips(name):
    spec = preset(name)
    assert spec.batch.sampler in ("saint_node", "saint_edge")
    assert ExperimentSpec.from_json(spec.to_json()) == spec


def test_sampler_override_round_trips_and_validates():
    spec = preset("ppi_tiny")
    apply_overrides(spec, {"batch.sampler": "saint_edge",
                           "batch.budget": 64,
                           "batch.batches_per_epoch": 3})
    validate(spec)
    again = ExperimentSpec.from_json(spec.to_json())
    assert again.batch.sampler == "saint_edge"
    assert again.batch.budget == 64
    assert again == spec
    with pytest.raises(ValueError, match="batch.sampler"):
        validate(apply_overrides(preset("ppi_tiny"),
                                 {"batch.sampler": "bogus"}))
    with pytest.raises(ValueError, match="batch.budget"):
        validate(apply_overrides(preset("ppi_tiny"),
                                 {"batch.budget": 0}))


def test_default_budget_matches_cluster_batch_size():
    """budget=None derives a q·N/p-sized batch (halved for edges) so
    `--set batch.sampler=saint_node` alone is runnable on any preset."""
    spec = preset("ppi_tiny")
    apply_overrides(spec, {"batch.sampler": "saint_node"})
    exp = build_experiment(spec)
    n = exp.graph.num_nodes
    expect = round(spec.batch.clusters_per_batch * n
                   / spec.partition.num_parts)
    assert exp.batcher.budget == expect
    assert exp.parts is None and exp.partition_stats is None
    apply_overrides(spec, {"batch.sampler": "saint_edge"})
    exp2 = build_experiment(spec)
    assert exp2.batcher.budget == -(-expect // 2)


def _cora_saint_spec(kind, **overrides) -> ExperimentSpec:
    spec = ExperimentSpec(
        name="cora_saint_test",
        data=DataSpec(name="cora", scale=0.3, seed=0),
        partition=PartitionSpec(num_parts=5, method="metis", seed=0),
        batch=BatchSpec(sampler=kind, budget=256, seed=0),
        model=ModelSpec(hidden_dim=16, num_layers=2, dropout=0.2,
                        multilabel=False),
        optim=OptimSpec(name="adamw", lr=1e-2),
        run=RunSpec(epochs=4, seed=0, eval_every=4, eval_split="val"))
    return apply_overrides(spec, overrides)


def _strip_time(history):
    return [{k: v for k, v in h.items()
             if k not in ("time", "flagged_steps")} for h in history]


def _assert_params_equal(a, b):
    same = jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))


@pytest.mark.parametrize("kind,prefetch", [("saint_node", 0),
                                           ("saint_node", 2),
                                           ("saint_edge", 0)])
def test_saint_resume_matches_straight_run(tmp_path, kind, prefetch):
    """Kill mid-epoch, rebuild from the same spec, fit(resume=True):
    history tail and final params bitwise-equal to an unkilled run —
    the resume-exact guarantee extended to both SAINT samplers."""
    over = {"execution.prefetch": prefetch}
    straight = build_experiment(_cora_saint_spec(kind, **over)).fit()
    assert len(straight.history) == 4

    ck = {"run.checkpoint_dir": str(tmp_path / f"ck_{kind}_{prefetch}")}
    killed = build_experiment(_cora_saint_spec(kind, **over, **ck),
                              extra_hooks=[StopAtStepHook(5)])
    r_kill = killed.fit()            # 4 steps/epoch → dies mid-epoch 1
    assert killed.engine.preempted
    assert len(r_kill.history) < 4

    resumed = build_experiment(_cora_saint_spec(kind, **over, **ck))
    r = resumed.fit(resume=True)
    assert not resumed.engine.preempted
    assert _strip_time(r.history) == _strip_time(straight.history)
    _assert_params_equal(r.params, straight.params)


def test_saint_resume_matches_straight_run_dp(run_distributed, tmp_path):
    """Same resume-exactness guarantee on the 2-device shard_map DP
    backend — SAINT payloads flow through _dp_groups stacking and the
    compressed-allreduce step unchanged."""
    out = run_distributed("""
import jax, numpy as np
from repro.core import StopAtStepHook, build_experiment
from repro.core.experiment import (BatchSpec, DataSpec, ExperimentSpec,
                                   ModelSpec, OptimSpec, PartitionSpec,
                                   RunSpec, apply_overrides)

def saint_spec(overrides=None):
    spec = ExperimentSpec(
        name="cora_saint_dp",
        data=DataSpec(name="cora", scale=0.3, seed=0),
        partition=PartitionSpec(num_parts=5, method="metis", seed=0),
        batch=BatchSpec(sampler="saint_node", budget=256, seed=0),
        model=ModelSpec(hidden_dim=16, num_layers=2, dropout=0.2,
                        multilabel=False),
        optim=OptimSpec(name="adamw", lr=1e-2),
        run=RunSpec(epochs=4, seed=0))
    return apply_overrides(spec, overrides or {})

def strip_time(history):
    return [{k: v for k, v in h.items()
             if k not in ("time", "flagged_steps")} for h in history]

base = {"execution.data_shards": 2}
straight = build_experiment(saint_spec(base)).fit()

ck = dict(base, **{"run.checkpoint_dir": r"%s"})
killed = build_experiment(saint_spec(ck), extra_hooks=[StopAtStepHook(3)])
killed.fit()
assert killed.engine.preempted
resumed = build_experiment(saint_spec(ck))
r = resumed.fit(resume=True)
assert strip_time(r.history) == strip_time(straight.history), (
    r.history, straight.history)
eq = jax.tree_util.tree_map(
    lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()),
    r.params, straight.params)
assert all(jax.tree_util.tree_leaves(eq))
print("DP_SAINT_RESUME_OK")
""" % (tmp_path / "dpck"), devices=2)
    assert "DP_SAINT_RESUME_OK" in out


def test_saint_sparse_kslots_auto(graph):
    """The block-ELL path + fill-adaptive K buckets work unchanged on
    SAINT batches (the k_slots planner goes through the sampler-agnostic
    sample_csrs seam)."""
    from repro.kernels import BlockEllAdj
    s = SaintNodeSampler(graph, 128, sparse_adj=True, k_slots="auto",
                         seed=0)
    assert s.k_plan is not None
    assert s.k_plan.buckets[-1] == s.node_cap // s.block_size
    batch = next(iter(s.epoch(0)))
    assert isinstance(batch.adj, BlockEllAdj)
    stats = s.padding_stats()
    assert stats["k_buckets"] == list(s.k_plan.buckets)
    assert stats["k_fwd_mean"] > 0
    # and it trains: one spec-driven epoch on the sparse sampler path
    over = {"batch.sparse_adj": True, "batch.k_slots": "auto",
            "run.epochs": 1, "run.eval_every": 0}
    res = build_experiment(_cora_saint_spec("saint_node", **over)).fit()
    assert len(res.history) == 1 and np.isfinite(res.history[0]["loss"])


def test_cli_saint_override_trains(tmp_path, monkeypatch):
    """Acceptance path: --preset ppi_tiny --set batch.sampler=saint_node
    trains end-to-end through the CLI and writes the artifacts."""
    from repro.launch.run_experiment import main
    # main() keeps the persistent compile cache where this variable says;
    # set after jax started, it leaves the cache off in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    rc = main(["--preset", "ppi_tiny", "--set", "batch.sampler=saint_node",
               "--set", "run.epochs=1",
               "--results-dir", str(tmp_path)])
    assert rc == 0
    out = pathlib.Path(tmp_path) / "ppi_tiny"
    metrics = json.loads((out / "metrics.json").read_text())
    assert len(metrics["history"]) == 1
    spec = ExperimentSpec.from_json((out / "spec.json").read_text())
    assert spec.batch.sampler == "saint_node"
