"""The program's spans and named scopes (repro.runtime.tracing): what a
profiler trace of `Engine.fit` holds, the counts its spans carry, that
tracing leaves training bitwise as it was, and that the compiled steps
carry the device scopes."""
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.core.experiment import build_experiment, preset

EPOCHS = 2


def _spec(name="ppi_tiny", prefetch=0):
    spec = preset(name)
    spec.run.epochs = EPOCHS
    spec.run.eval_every = 0
    spec.execution.prefetch = prefetch
    return spec


def _fit(spec, trace_dir=None):
    exp = build_experiment(spec)
    if trace_dir is None:
        return exp, exp.fit()
    with jax.profiler.trace(str(trace_dir)):
        res = exp.fit()
    return exp, res


def _spans(trace_dir):
    """(name, start ns, end ns, stats, thread) of every repro.* span."""
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    profile = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name[len("repro."):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                (plane.name, line.name)))
    return out


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parents):
    return any(p[4] == child[4] and p[1] <= child[1] and child[2] <= p[2]
               for p in parents)


def test_trace_of_fit_holds_one_build_per_step_and_nested_children(
        tmp_path):
    exp, res = _fit(_spec(), tmp_path)
    steps = exp.engine.global_step
    assert steps == EPOCHS * exp.batcher.steps_per_epoch()
    spans = _spans(tmp_path)
    builds = _named(spans, "batch.build")
    assert len(builds) == steps
    for child in ("batch.slice", "batch.adjacency", "batch.gather"):
        found = _named(spans, child)
        assert len(found) == steps
        assert all(_inside(c, builds) for c in found), child
    # the synchronous loop: every build runs while the step waits for it
    assert all(_inside(b, _named(spans, "engine.wait")) for b in builds)
    for name in ("engine.wait", "engine.step"):
        # one wait per step, plus the one that finds each epoch's end
        assert len(_named(spans, name)) == steps + (
            EPOCHS if name == "engine.wait" else 0)
    # on_step for every step, on_epoch for every epoch
    assert len(_named(spans, "engine.hooks")) == steps + EPOCHS
    assert not _named(spans, "prefetch.produce")


@pytest.mark.parametrize("name,per_step", [("ppi_tiny", 4),
                                           ("amazon2m_tiny", 3)])
def test_epoch_end_counts_the_scalars_it_reads_back(tmp_path, name,
                                                    per_step):
    """Loss plus tp/fp/fn for a multilabel graph, loss plus correct/n
    for a single-class one."""
    exp, _ = _fit(_spec(name), tmp_path)
    assert exp.graph.labels.ndim == (2 if per_step == 4 else 1)
    ends = _named(_spans(tmp_path), "engine.epoch_end")
    steps = exp.batcher.steps_per_epoch()
    assert len(ends) == EPOCHS
    for e in ends:
        assert e[3]["steps"] == steps
        assert e[3]["syncs"] == steps * per_step


def test_epoch_end_syncs_count_the_reads_made(tmp_path, monkeypatch):
    """The count comes from the reads themselves: one more read at the
    epoch's end reads as one more sync."""
    import jax.numpy as jnp
    from repro.core import engine as engine_mod
    exp = build_experiment(_spec())
    f1 = engine_mod.micro_f1

    def reads_once_more(tp, fp, fn):
        exp.engine._read_back(jnp.zeros(()))
        return f1(tp, fp, fn)

    monkeypatch.setattr(engine_mod, "micro_f1", reads_once_more)
    with jax.profiler.trace(str(tmp_path)):
        exp.fit()
    ends = _named(_spans(tmp_path), "engine.epoch_end")
    steps = exp.batcher.steps_per_epoch()
    assert [e[3]["syncs"] for e in ends] == [steps * 4 + 1] * EPOCHS


def _same(a, b):
    return all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)))


def test_tracing_and_prefetch_leave_training_bitwise(tmp_path):
    runs = {}
    for prefetch in (0, 2):
        for traced in (False, True):
            d = tmp_path / f"{prefetch}-{traced}" if traced else None
            exp, res = _fit(_spec(prefetch=prefetch), d)
            runs[prefetch, traced] = res
            if traced and prefetch:
                spans = _spans(d)
                steps = exp.engine.global_step
                produce = _named(spans, "prefetch.produce")
                # one per item, and one that finds each epoch's end
                assert len(produce) == steps + EPOCHS
                transfers = _named(spans, "prefetch.transfer")
                assert len(transfers) == steps
                assert all(_inside(t, produce) for t in transfers)
                assert all(_inside(b, produce)
                           for b in _named(spans, "batch.build"))
    base = runs[0, False]
    for key, res in runs.items():
        assert [h["loss"] for h in res.history] == \
            [h["loss"] for h in base.history], key
        assert _same(res.params, base.params), key


@pytest.mark.parametrize("fused", [False, True])
def test_compiled_step_carries_the_device_scopes(fused):
    spec = _spec()
    spec.model.fuse_spmm = fused
    exp = build_experiment(spec)
    engine = exp.engine
    state = engine.init_state()
    payload = next(iter(exp.batcher.epoch(0))).astuple()
    hlo = engine.backend.lower(state, payload).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert "jit(step)/optim.update/mul" in names
    model = ["gcn.dropout", "gcn.activation", "gcn.loss"]
    model += ["gcn.xw_aggregate"] if fused else ["gcn.xw", "gcn.aggregate"]
    for scope in model:
        # backward ops inherit their forward's scope
        for path in (f"jvp({scope})/", f"transpose(jvp({scope}))/"):
            assert any(path in n for n in names), path


def test_entry_points_key_the_compile_cache_on_op_metadata(tmp_path,
                                                           monkeypatch):
    """A cached executable keeps the op names of whoever compiled it
    unless the cache's key covers them."""
    from repro.launch.compile_cache import enable_compile_cache
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update(key, False)
    try:
        build_experiment(_spec()).fit()
        # building and training a model leaves JAX's configuration alone
        assert not getattr(jax.config, key)
        assert enable_compile_cache() == str(tmp_path)
        assert getattr(jax.config, key)
    finally:
        jax.config.update(key, before)


def test_data_parallel_step_carries_its_scopes(run_distributed):
    out = run_distributed("""
import jax, numpy as np
from repro.core.experiment import build_experiment, preset
from repro.dist.steps import make_gcn_train_step
from repro.launch.mesh import make_mesh
from repro.nn.optim import adamw

spec = preset("ppi_tiny")
spec.run.epochs = 1
exp = build_experiment(spec)
cfg, batcher = exp.engine.cfg, exp.batcher
mesh = make_mesh((4,), ("data",))
batch = next(iter(batcher.epoch(0))).astuple()
stacked = jax.tree_util.tree_map(lambda x: np.stack([x] * 4), batch)
from repro.dist.steps import init_gcn_train_state
from repro.core.gcn import init_gcn
params = init_gcn(jax.random.PRNGKey(0), cfg)
for compression in (None, 8):
    opt = adamw(1e-2)
    step = make_gcn_train_step(cfg, opt, mesh, compression=compression)
    state = init_gcn_train_state(params, opt, mesh,
                                 compression=compression)
    hlo = step.__wrapped__.lower(state, jax.random.PRNGKey(1),
                                 stacked).compile().as_text()
    for scope in ("/dp.allreduce/", "/optim.update/", "(gcn.xw))/"):
        assert scope in hlo, (compression, scope)
    assert "all-reduce" in hlo
print("DP_SCOPES_OK")
""", devices=4)
    assert "DP_SCOPES_OK" in out
