"""Async batch prefetch (repro.core.prefetch): the background producer
must be a pure latency optimization — identical batch sequence, losses
and final params as the synchronous loop — and must propagate errors
and shut down cleanly on early exit. The consumer is SUPERVISED: a
producer that dies silently or goes quiet raises a diagnosable
PrefetchError (or is rebuilt once) instead of blocking the training
step forever. The 2-device variant proves trajectory equality for the
shard_map DP epoch loop."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClusterBatcher, GCNConfig, prefetch_iter,
                        train_cluster_gcn)
from repro.core.prefetch import PrefetchError
from repro.graph import make_dataset, partition_graph
from repro.nn import adamw
from repro.runtime.faults import FaultPlan, FaultRule, fault_scope


def test_prefetch_iter_preserves_order_and_applies_transfer():
    for size in (0, 1, 2, 7):
        got = list(prefetch_iter(iter(range(100)), size,
                                 transfer=lambda x: x * 2))
        assert got == [2 * i for i in range(100)], size


def test_prefetch_iter_propagates_source_exception():
    def src():
        yield 1
        yield 2
        raise RuntimeError("boom")
    it = prefetch_iter(src(), size=2)
    assert next(it) == 1 and next(it) == 2
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


def test_prefetch_iter_early_exit_stops_producer():
    import threading
    before = threading.active_count()
    for _ in range(3):
        for i in prefetch_iter(iter(range(10 ** 9)), size=2):
            if i == 5:
                break
    # producers notice the closed consumer and die (0.1s put timeout)
    import time
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before + 1


def test_silent_producer_crash_raises_not_hangs():
    """A producer that dies without posting _DONE/_ERR (OOM-killed, a
    bug swallowing BaseException) must surface as PrefetchError within
    ~poll_interval, not block q.get forever."""
    plan = FaultPlan(rules={"prefetch.producer_crash": FaultRule(at=(3,))})
    t0 = time.perf_counter()
    with fault_scope(plan):
        with pytest.raises(PrefetchError, match="producer_crash") as ei:
            list(prefetch_iter(iter(range(10)), 2, poll_interval=0.05))
    assert ei.value.site == "prefetch.producer_crash"
    assert time.perf_counter() - t0 < 10.0


def test_silent_crash_rebuild_resumes_exact_sequence():
    """With a rebuild callback the consumer respawns the producer ONCE
    from the first unconsumed item — the yielded sequence is exactly
    the unfaulted one."""
    plan = FaultPlan(rules={"prefetch.producer_crash": FaultRule(at=(3,))})
    with fault_scope(plan):
        got = list(prefetch_iter(
            iter(range(10)), 2, poll_interval=0.05,
            rebuild=lambda consumed: iter(range(consumed, 10))))
    assert got == list(range(10))


def test_rebuild_is_one_shot():
    """A producer that keeps dying exhausts the single rebuild and then
    raises — no infinite respawn loop."""
    plan = FaultPlan(rules={"prefetch.producer_crash": FaultRule()})
    with fault_scope(plan):
        with pytest.raises(PrefetchError, match="producer_crash"):
            list(prefetch_iter(
                iter(range(10)), 2, poll_interval=0.05,
                rebuild=lambda consumed: iter(range(consumed, 10))))


def test_hung_producer_raises_after_hang_timeout():
    """Alive-but-silent (stuck I/O, deadlock): the heartbeat monitor
    trips after hang_timeout and names the site."""
    plan = FaultPlan(rules={"prefetch.producer_hang": FaultRule(at=(2,))})
    t0 = time.perf_counter()
    with fault_scope(plan):
        with pytest.raises(PrefetchError, match="producer_hang"):
            list(prefetch_iter(iter(range(10)), 2, poll_interval=0.05,
                               hang_timeout=0.5))
    elapsed = time.perf_counter() - t0
    assert 0.4 < elapsed < 10.0


def _setup():
    g = make_dataset("cora", scale=0.3, seed=0)
    parts, _ = partition_graph(g, 5, method="metis", seed=0)
    cfg = GCNConfig(in_dim=g.features.shape[1], hidden_dim=16,
                    out_dim=int(g.labels.max()) + 1, num_layers=2,
                    dropout=0.2)
    return g, parts, cfg


@pytest.mark.parametrize("sparse", [False, True])
def test_trainer_prefetch_identical_to_synchronous(sparse):
    """Same seed, prefetch=0 vs prefetch=2: losses equal exactly (same
    batches, same order, same rng stream — dropout on) and final params
    identical."""
    g, parts, cfg = _setup()
    kw = dict(sparse_adj=True, k_slots="auto") if sparse else {}
    b = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0, **kw)
    r_sync = train_cluster_gcn(g, b, cfg, adamw(1e-2), num_epochs=3,
                               seed=0)
    r_pre = train_cluster_gcn(g, b, cfg, adamw(1e-2), num_epochs=3,
                              seed=0, prefetch=2)
    assert [h["loss"] for h in r_sync.history] == \
        [h["loss"] for h in r_pre.history]
    same = jax.tree_util.tree_map(
        lambda a, b_: bool((np.asarray(a) == np.asarray(b_)).all()),
        r_sync.params, r_pre.params)
    assert all(jax.tree_util.tree_leaves(same))


def test_two_device_dp_prefetch_matches_synchronous(run_distributed):
    """The DP epoch loop (stacking + device_put on the producer thread)
    yields the identical training trajectory on a 2-device mesh."""
    out = run_distributed("""
import jax
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
for pf in (0, 2):
    res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2), num_epochs=3,
                            mesh=mesh, sparse_adj=True, prefetch=pf)
    hist[pf] = [h["loss"] for h in res.history]
assert hist[0] == hist[2], hist
print("DP_PREFETCH_OK")
""", devices=2)
    assert "DP_PREFETCH_OK" in out


def test_prefetch_auto_tunes_and_matches_sync_trajectory():
    """execution.prefetch="auto": the warmup epoch measures the
    host-build/device-step ratio, later epochs run at the picked depth,
    both are logged in history rows — and the final params stay bitwise
    identical to a fully synchronous run (prefetch is a pure latency
    optimization, measured or not)."""
    from repro.core.experiment import build_experiment, preset

    results = {}
    for pf in (0, "auto"):
        spec = preset("ppi_tiny")
        spec.run.epochs = 3
        spec.execution.prefetch = pf
        results[pf] = build_experiment(spec).fit()
    sync, auto = results[0], results["auto"]
    assert [h["loss"] for h in sync.history] == \
        [h["loss"] for h in auto.history]
    same = jax.tree_util.tree_map(
        lambda a, b_: bool((np.asarray(a) == np.asarray(b_)).all()),
        sync.params, auto.params)
    assert all(jax.tree_util.tree_leaves(same))
    # only the auto run carries the tuning diagnostics
    assert all("prefetch_depth" not in h for h in sync.history)
    warm, later = auto.history[0], auto.history[1:]
    assert warm["prefetch_depth"] == 0          # synchronous warmup
    ratio = warm["host_build_over_step"]
    assert np.isfinite(ratio) and ratio >= 0
    from repro.core.engine import AUTO_PREFETCH_MAX, Engine
    expect = Engine._auto_prefetch_depth(ratio)
    for h in later:
        assert h["prefetch_depth"] == expect
        assert "host_build_over_step" not in h
        assert 0 <= h["prefetch_depth"] <= AUTO_PREFETCH_MAX


def test_auto_prefetch_depth_formula():
    from repro.core.engine import AUTO_PREFETCH_MAX, Engine
    assert Engine._auto_prefetch_depth(0.0) == 0
    assert Engine._auto_prefetch_depth(0.049) == 0      # not worth a thread
    assert Engine._auto_prefetch_depth(0.05) == 1
    assert Engine._auto_prefetch_depth(0.5) == 1
    assert Engine._auto_prefetch_depth(0.9) == 2
    assert Engine._auto_prefetch_depth(50.0) == AUTO_PREFETCH_MAX


def test_prefetch_auto_spec_validation():
    from repro.core.experiment import preset, validate
    spec = preset("ppi_tiny")
    spec.execution.prefetch = "auto"
    validate(spec)
    for bad in ("eager", -1, 1.5):
        spec.execution.prefetch = bad
        with pytest.raises(ValueError, match="execution.prefetch"):
            validate(spec)


def test_prefetch_auto_divides_by_the_synced_step(monkeypatch):
    """The warmup epoch's host_build_over_step divides the build time by
    each step's time to its loss (block_until_ready), not by the time to
    dispatch it; later epochs, and runs at a fixed depth, never wait on
    a step."""
    from repro.core.experiment import build_experiment, preset

    waited = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or block(x))
    for pf in (0, 2, "auto"):
        spec = preset("ppi_tiny")
        spec.run.epochs = 3
        spec.run.eval_every = 0
        spec.execution.prefetch = pf
        exp = build_experiment(spec)
        del waited[:]
        res = exp.fit()
        steps = exp.batcher.steps_per_epoch()
        assert len(waited) == (steps if pf == "auto" else 0), pf
        assert all(jnp.shape(x) == () for x in waited)
    assert res.history[0]["host_build_over_step"] > 0
