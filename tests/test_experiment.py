"""ExperimentSpec: JSON round-trip, overrides, preset registry (per-
dataset loss/norm settings), build inference, and the run_experiment
CLI (print-spec round-trip + end-to-end train → checkpoint → resume →
eval on the tiny preset)."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (ClusterBatcher, GCNConfig, train_cluster_gcn,
                        preset, list_presets, build_experiment,
                        apply_overrides, set_override)
from repro.core.experiment import (ExperimentSpec, build_gcn_config,
                                   validate)
from repro.graph import make_dataset, partition_graph
from repro.nn import adamw

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ----------------------------------------------------------------------
# spec mechanics
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", list_presets())
def test_spec_json_round_trip(name):
    spec = preset(name)
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec
    # and dict-level stability (what --print-spec emits)
    assert json.loads(again.to_json()) == json.loads(spec.to_json())


def test_overrides_coerce_json_literals():
    spec = preset("ppi_tiny")
    apply_overrides(spec, {"execution.prefetch": "2",
                           "batch.k_slots": "auto",
                           "run.eval_split": "test",
                           "model.dropout": "0.5",
                           "run.checkpoint_dir": "null",
                           "batch.sparse_adj": "true"})
    assert spec.execution.prefetch == 2
    assert spec.batch.k_slots == "auto"
    assert spec.run.eval_split == "test"
    assert spec.model.dropout == 0.5
    assert spec.run.checkpoint_dir is None
    assert spec.batch.sparse_adj is True


def test_overrides_unknown_field_raises():
    spec = preset("ppi_tiny")
    with pytest.raises(KeyError, match="no field"):
        set_override(spec, "run.epoches", 3)
    with pytest.raises(KeyError, match="no section"):
        set_override(spec, "runn.epochs", 3)


def test_from_dict_unknown_keys_raise():
    d = preset("ppi_tiny").to_dict()
    d["batch"]["qq"] = 1
    with pytest.raises(ValueError, match="unknown field"):
        ExperimentSpec.from_dict(d)
    d2 = preset("ppi_tiny").to_dict()
    d2["extra_section"] = {}
    with pytest.raises(ValueError, match="unknown spec section"):
        ExperimentSpec.from_dict(d2)


def test_validate_rejects_bad_fields():
    spec = preset("ppi_tiny")
    spec.batch.norm = "eq99"
    with pytest.raises(ValueError, match="batch.norm"):
        validate(spec)
    spec = preset("ppi_tiny")
    spec.run.eval_split = "holdout"
    with pytest.raises(ValueError, match="eval_split"):
        validate(spec)
    spec = preset("ppi_tiny")
    spec.execution.compression = 16
    with pytest.raises(ValueError, match="compression"):
        validate(spec)


# ----------------------------------------------------------------------
# preset registry: per-dataset loss / norm / diag settings (the old
# configs/ppi.py gcn_config hardcoded multilabel=True for everything)
# ----------------------------------------------------------------------
def test_presets_set_loss_mode_per_dataset():
    assert preset("ppi").model.multilabel is True
    assert preset("ppi_sota").model.multilabel is True
    for name in ("reddit", "reddit_tiny", "amazon2m", "amazon2m_tiny"):
        assert preset(name).model.multilabel is False, name
    sota = preset("ppi_sota")
    assert (sota.batch.norm, sota.batch.diag_lambda) == ("eq11", 1.0)
    assert (sota.model.num_layers, sota.model.hidden_dim) == (5, 2048)
    # amazon2m's generator has no val split: preset must say so
    assert preset("amazon2m").run.eval_split == "test"
    assert preset("amazon2m_tiny").run.eval_split == "test"


def test_build_gcn_config_infers_from_graph():
    spec = preset("ppi_tiny")
    g = make_dataset("ppi", scale=0.03, seed=0)
    cfg = build_gcn_config(spec, g)
    assert cfg.multilabel and cfg.out_dim == g.labels.shape[1]
    assert cfg.in_dim == g.features.shape[1]
    spec2 = preset("reddit_tiny")
    g2 = make_dataset("reddit", scale=0.01, seed=0)
    cfg2 = build_gcn_config(spec2, g2)
    assert not cfg2.multilabel
    assert cfg2.out_dim == int(g2.labels.max()) + 1


def test_ppi_gcn_config_helper_takes_multilabel():
    from repro.configs.ppi import gcn_config
    assert gcn_config(8, 4).multilabel is True            # PPI default
    assert gcn_config(8, 4, multilabel=False).multilabel is False


@pytest.mark.parametrize("name", ["ppi_tiny", "reddit_tiny",
                                  "amazon2m_tiny"])
def test_tiny_preset_trains_two_epochs(name):
    spec = preset(name)
    apply_overrides(spec, {"run.epochs": 2, "run.eval_every": 1})
    exp = build_experiment(spec)
    res = exp.fit()
    assert len(res.history) == 2
    assert all(np.isfinite(h["loss"]) for h in res.history)
    metric = "train_f1" if exp.cfg.multilabel else "train_acc"
    assert metric in res.history[-1]
    assert res.history[-1]["eval_split"] == spec.run.eval_split
    assert np.isfinite(res.history[-1]["val_score"])


# ----------------------------------------------------------------------
# eval-split fallback (test-set leakage is loud now)
# ----------------------------------------------------------------------
def test_wrapper_warns_once_on_test_fallback_and_records_split():
    g = make_dataset("amazon2m", scale=0.0003, seed=0)  # empty val_mask
    parts, _ = partition_graph(g, 4, method="metis", seed=0)
    cfg = GCNConfig(in_dim=g.features.shape[1], hidden_dim=16,
                    out_dim=int(g.labels.max()) + 1, num_layers=2)
    batcher = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0)
    with pytest.warns(UserWarning, match="fell back to the TEST split"):
        res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2),
                                num_epochs=2, eval_every=1)
    assert all(h["eval_split"] == "test" for h in res.history)


def test_explicit_empty_eval_split_fails_at_build_time():
    spec = preset("amazon2m_tiny")        # generator has empty val_mask
    spec.run.eval_split = "val"
    with pytest.raises(ValueError, match="val_mask is empty"):
        build_experiment(spec)


def test_wrapper_uses_val_split_without_warning(recwarn):
    g = make_dataset("cora", scale=0.3, seed=0)
    parts, _ = partition_graph(g, 4, method="metis", seed=0)
    cfg = GCNConfig(in_dim=g.features.shape[1], hidden_dim=16,
                    out_dim=int(g.labels.max()) + 1, num_layers=2)
    batcher = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0)
    res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2), num_epochs=1,
                            eval_every=1)
    assert res.history[-1]["eval_split"] == "val"
    assert not [w for w in recwarn
                if "fell back" in str(w.message)]


def test_fallback_warning_fires_exactly_once_per_run():
    """eval_every=1 over several epochs: the EvalHook resolves the
    split every epoch but must warn on the FIRST fallback only —
    once per run, not once per eval."""
    import warnings
    g = make_dataset("amazon2m", scale=0.0003, seed=0)  # empty val_mask
    parts, _ = partition_graph(g, 4, method="metis", seed=0)
    cfg = GCNConfig(in_dim=g.features.shape[1], hidden_dim=16,
                    out_dim=int(g.labels.max()) + 1, num_layers=2)
    batcher = ClusterBatcher(g, parts, clusters_per_batch=2, seed=0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = train_cluster_gcn(g, batcher, cfg, adamw(1e-2),
                                num_epochs=3, eval_every=1)
    fell = [w for w in caught if "fell back" in str(w.message)]
    assert len(fell) == 1, [str(w.message) for w in fell]
    assert len(res.history) == 3
    assert all(h["eval_split"] == "test" for h in res.history)


def test_resolved_eval_split_survives_checkpoint_resume(tmp_path):
    """The split 'auto' resolves to is part of the history record; a
    kill + resume must restore the resolved name in the replayed rows
    and keep recording the same one afterwards."""
    import warnings
    from repro.core import StopAtStepHook

    def _spec():
        s = preset("amazon2m_tiny")      # generator has empty val_mask
        return apply_overrides(s, {
            "run.eval_split": "auto", "run.eval_every": 1,
            "run.epochs": 3, "model.hidden_dim": 16,
            "run.checkpoint_dir": str(tmp_path / "ck")})

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        probe = build_experiment(_spec())
        # stop inside epoch 1 so at least one eval'd epoch is replayed
        killed = build_experiment(_spec(), extra_hooks=[
            StopAtStepHook(probe.batcher.steps_per_epoch() + 1)])
        killed.fit()
        assert killed.engine.preempted
        resumed = build_experiment(_spec())
        r = resumed.fit(resume=True)
    assert len(r.history) == 3
    assert all(h["eval_split"] == "test" for h in r.history)


# ----------------------------------------------------------------------
# the CLI driver end-to-end (train → checkpoint → resume → eval)
# ----------------------------------------------------------------------
def _cli(tmp_path, *argv):
    # the CLI's compile cache goes to the test's own directory
    env = dict(os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.run_experiment", *argv],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_cli_print_spec_round_trips(tmp_path):
    text = _cli(tmp_path, "--preset", "ppi_tiny", "--set",
                "run.epochs=2", "--print-spec")
    spec = ExperimentSpec.from_json(text)
    assert spec.run.epochs == 2
    assert json.loads(spec.to_json()) == json.loads(text)


def test_cli_train_checkpoint_resume_eval(tmp_path):
    ck = str(tmp_path / "ck")
    results = str(tmp_path / "results")
    common = ["--preset", "ppi_tiny", "--set", f"run.checkpoint_dir={ck}",
              "--results-dir", results]
    out1 = _cli(tmp_path, *common, "--set", "run.epochs=1")
    assert json.loads(out1.splitlines()[-1])["epochs"] == 1
    assert (pathlib.Path(ck) / "step_0000000004").exists()
    out2 = _cli(tmp_path, *common, "--set", "run.epochs=2", "--resume")
    rec = json.loads(out2.splitlines()[-1])
    assert rec["epochs"] == 2                  # resumed, not restarted
    run_dir = pathlib.Path(results) / "ppi_tiny"
    spec = ExperimentSpec.from_json((run_dir / "spec.json").read_text())
    assert spec.run.epochs == 2                # resolved spec persisted
    metrics = json.loads((run_dir / "metrics.json").read_text())
    assert [h["epoch"] for h in metrics["history"]] == [0, 1]
    assert metrics["final"]["split"] == "val"
