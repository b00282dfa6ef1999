"""Engine redesign locked against behavioral drift:

* wrapper equivalence — `train_cluster_gcn(...)` and the equivalent
  spec + `Engine.fit()` produce bitwise-identical trajectories (history
  minus wall-clock, and final params) for the dense, sparse_adj and
  2-device shard_map DP paths;
* resume equivalence — train N epochs straight vs. train-to-step-k,
  kill (StopAtStepHook → checkpoint → clean exit), rebuild from the
  same spec and `fit(resume=True)`: identical history tail and final
  params, over prefetch∈{0,2} and the 2-device DP backend.
"""
import jax
import numpy as np
import pytest

from repro.core import (StopAtStepHook, build_experiment, preset,
                        train_cluster_gcn)
from repro.core.experiment import (BatchSpec, DataSpec, ExperimentSpec,
                                   ModelSpec, OptimSpec, PartitionSpec,
                                   RunSpec, apply_overrides)


def _cora_spec(**overrides) -> ExperimentSpec:
    spec = ExperimentSpec(
        name="cora_test",
        data=DataSpec(name="cora", scale=0.3, seed=0),
        partition=PartitionSpec(num_parts=5, method="metis", seed=0),
        batch=BatchSpec(clusters_per_batch=2, seed=0),
        model=ModelSpec(hidden_dim=16, num_layers=2, dropout=0.2,
                        multilabel=False),
        optim=OptimSpec(name="adamw", lr=1e-2),
        run=RunSpec(epochs=3, seed=0, eval_every=3, eval_split="val"))
    return apply_overrides(spec, overrides)


def _strip_time(history):
    return [{k: v for k, v in h.items()
             if k not in ("time", "flagged_steps")} for h in history]


def _assert_params_equal(a, b):
    same = jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))


# ----------------------------------------------------------------------
# wrapper equivalence
# ----------------------------------------------------------------------
@pytest.mark.parametrize("sparse", [False, True])
def test_wrapper_matches_spec_engine(sparse):
    over = ({"batch.sparse_adj": True, "batch.k_slots": "auto"}
            if sparse else {})
    r_spec = build_experiment(_cora_spec(**over)).fit()

    exp = build_experiment(_cora_spec(**over))  # fresh, same seeds
    r_wrap = train_cluster_gcn(exp.graph, exp.batcher, exp.cfg, exp.opt,
                               num_epochs=3, seed=0, eval_every=3)
    assert _strip_time(r_wrap.history) == _strip_time(r_spec.history)
    _assert_params_equal(r_wrap.params, r_spec.params)


_SUBPROCESS_PRELUDE = """
import jax, numpy as np
from repro.core import StopAtStepHook, build_experiment, train_cluster_gcn
from repro.core.experiment import (BatchSpec, DataSpec, ExperimentSpec,
                                   ModelSpec, OptimSpec, PartitionSpec,
                                   RunSpec, apply_overrides)

def cora_spec(overrides=None):
    spec = ExperimentSpec(
        name="cora_test",
        data=DataSpec(name="cora", scale=0.3, seed=0),
        partition=PartitionSpec(num_parts=5, method="metis", seed=0),
        batch=BatchSpec(clusters_per_batch=2, seed=0),
        model=ModelSpec(hidden_dim=16, num_layers=2, dropout=0.2,
                        multilabel=False),
        optim=OptimSpec(name="adamw", lr=1e-2),
        run=RunSpec(epochs=3, seed=0, eval_every=3, eval_split="val"))
    return apply_overrides(spec, overrides or {})

def strip_time(history):
    return [{k: v for k, v in h.items()
             if k not in ("time", "flagged_steps")} for h in history]

def params_equal(a, b):
    eq = jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)
    return all(jax.tree_util.tree_leaves(eq))
"""


def test_wrapper_matches_spec_engine_dp(run_distributed):
    out = run_distributed(_SUBPROCESS_PRELUDE + """
r_spec = build_experiment(cora_spec({"execution.data_shards": 2})).fit()
exp = build_experiment(cora_spec())     # wrapper drives the mesh itself
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("data",))
r_wrap = train_cluster_gcn(exp.graph, exp.batcher, exp.cfg, exp.opt,
                           num_epochs=3, seed=0, eval_every=3, mesh=mesh)
assert strip_time(r_wrap.history) == strip_time(r_spec.history), (
    r_wrap.history, r_spec.history)
assert params_equal(r_wrap.params, r_spec.params)
print("DP_WRAPPER_OK")
""", devices=2)
    assert "DP_WRAPPER_OK" in out


# ----------------------------------------------------------------------
# resume equivalence (kill mid-epoch, restore, finish)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("prefetch", [0, 2])
def test_resume_matches_straight_run(tmp_path, prefetch):
    over = {"execution.prefetch": prefetch, "run.epochs": 4}
    straight = build_experiment(_cora_spec(**over)).fit()

    ck = {"run.checkpoint_dir": str(tmp_path / f"ck{prefetch}")}
    killed = build_experiment(
        _cora_spec(**over, **ck),
        extra_hooks=[StopAtStepHook(5)])  # mid-epoch 1 (3 steps/epoch)
    r_kill = killed.fit()
    assert killed.engine.preempted
    assert len(r_kill.history) < 4

    resumed_exp = build_experiment(_cora_spec(**over, **ck))
    r_resume = resumed_exp.fit(resume=True)
    assert not resumed_exp.engine.preempted
    assert _strip_time(r_resume.history) == _strip_time(straight.history)
    _assert_params_equal(r_resume.params, straight.params)


def test_resume_from_epoch_boundary(tmp_path):
    """Resume from an epoch-boundary checkpoint (written by the
    epoch-cadence hook, zero partial accumulators) — the other resume
    shape."""
    straight = build_experiment(_cora_spec(**{"run.epochs": 4})).fit()
    over = {"run.epochs": 4,
            "run.checkpoint_dir": str(tmp_path / "ck")}
    killed = build_experiment(_cora_spec(**over),
                              extra_hooks=[StopAtStepHook(5)])
    killed.fit()
    # wind the run back to the epoch-0 boundary save (global step 3) by
    # dropping the newer mid-epoch preemption checkpoint
    import shutil
    shutil.rmtree(tmp_path / "ck" / "step_0000000005")
    resumed = build_experiment(_cora_spec(**over))
    r = resumed.fit(resume=True)
    assert _strip_time(r.history) == _strip_time(straight.history)
    _assert_params_equal(r.params, straight.params)


def test_resume_without_checkpoint_warns_and_cold_starts(tmp_path):
    over = {"run.epochs": 2,
            "run.checkpoint_dir": str(tmp_path / "empty")}
    exp = build_experiment(_cora_spec(**over))
    with pytest.warns(UserWarning, match="nothing to restore"):
        res = exp.fit(resume=True)          # nothing on disk yet
    assert [h["epoch"] for h in res.history] == [0, 1]


def test_resume_matches_straight_run_dp(run_distributed, tmp_path):
    out = run_distributed(_SUBPROCESS_PRELUDE + f"""
base = {{"execution.data_shards": 2, "run.epochs": 4}}
straight = build_experiment(cora_spec(base)).fit()

ck = dict(base, **{{"run.checkpoint_dir": r"{tmp_path / 'dpck'}"}})
killed = build_experiment(cora_spec(ck), extra_hooks=[StopAtStepHook(3)])
killed.fit()
assert killed.engine.preempted
resumed = build_experiment(cora_spec(ck))
r = resumed.fit(resume=True)
assert strip_time(r.history) == strip_time(straight.history), (
    r.history, straight.history)
assert params_equal(r.params, straight.params)
print("DP_RESUME_OK")
""", devices=2)
    assert "DP_RESUME_OK" in out


# ----------------------------------------------------------------------
# the start_step fast-forward seam (Sampler.epoch(e, start_step=k))
# ----------------------------------------------------------------------
def _batch_leaves(batch):
    return [np.asarray(l)
            for l in jax.tree_util.tree_leaves(batch.astuple())]


@pytest.mark.parametrize("sampler", ["cluster", "saint_node",
                                     "saint_edge"])
def test_start_step_seam_matches_discard(sampler):
    """epoch(e, start_step=k) must be bitwise-equivalent to building
    the whole epoch and discarding the first k batches — the contract
    Engine resume and prefetch-producer rebuild both depend on. The
    seam may only skip batch CONSTRUCTION, never RNG draws."""
    exp = build_experiment(_cora_spec(**{"batch.sampler": sampler}))
    b = exp.batcher
    n = b.steps_per_epoch()
    for epoch in (0, 1):
        for k in (0, 1, n - 1, n):
            full = list(b.epoch(epoch))[k:]
            seam = list(b.epoch(epoch, start_step=k))
            assert len(seam) == len(full), (sampler, epoch, k)
            for f, s in zip(full, seam):
                fl, sl = _batch_leaves(f), _batch_leaves(s)
                assert len(fl) == len(sl)
                assert all(np.array_equal(x, y)
                           for x, y in zip(fl, sl)), (sampler, epoch, k)


def test_mid_epoch_resume_uses_seam_trajectory(tmp_path):
    """Kill mid-epoch, resume: the seam path (skip construction) must
    land on the identical trajectory as the straight run — this is the
    same lock as test_resume_matches_straight_run but asserting the
    cheap path is actually taken on a single-device run."""
    over = {"run.epochs": 3}
    straight = build_experiment(_cora_spec(**over)).fit()
    ck = {"run.checkpoint_dir": str(tmp_path / "seam_ck"), **over}
    killed = build_experiment(_cora_spec(**ck),
                              extra_hooks=[StopAtStepHook(3)])
    killed.fit()
    assert killed.engine.preempted
    resumed = build_experiment(_cora_spec(**ck))
    assert resumed.engine._start_seam     # the cheap path is available
    r = resumed.fit(resume=True)
    assert _strip_time(r.history) == _strip_time(straight.history)
    _assert_params_equal(r.params, straight.params)


# ----------------------------------------------------------------------
# the straggler detector's clock
# ----------------------------------------------------------------------
def test_straggler_detector_is_fed_the_host_loop_period():
    """flag_step sees each step's whole host loop: the wait for its
    batch, the step's dispatch and the hooks. A hook that stalls one
    step is flagged; the dispatch time alone would not show it."""
    import time

    class Stall:
        def __init__(self):
            self.fed = []

        def on_step(self, engine, info):
            time.sleep(0.3 if info["global_step"] == 11 else 0.01)

    spec = preset("ppi_tiny")
    spec.run.epochs = 3
    spec.run.eval_every = 0
    stall = Stall()
    exp = build_experiment(spec, extra_hooks=[stall])
    flag = exp.engine.straggler.flag_step
    exp.engine.straggler.flag_step = \
        lambda s: stall.fed.append(s) or flag(s)
    res = exp.fit()
    assert len(stall.fed) == exp.engine.global_step == 12
    assert min(stall.fed) >= 0.01 and stall.fed[10] >= 0.3
    assert res.history[2]["flagged_steps"] >= 1
