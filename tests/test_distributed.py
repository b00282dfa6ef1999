"""Distributed integration tests (subprocess — they need a multi-device
host platform; see the run_distributed fixture in conftest.py)."""
import json

import pytest


@pytest.mark.slow
def test_sharded_train_step_matches_single_device(run_distributed):
    """FSDP×TP pjit step must produce the same loss as 1-device."""
    out = run_distributed("""
import jax, jax.numpy as jnp, json
from jax.sharding import PartitionSpec as P
from repro.configs import get_arch, make_inputs, input_specs
from repro.models.config import ShapeConfig
from repro.dist.sharding import CellPolicy, make_rules, shardings_for, batch_pspec
from repro.dist.steps import make_train_step, spec_train_state
from repro.launch.mesh import make_mesh
from repro.models.spec import init_tree
from repro.nn.optim import adamw

cfg = get_arch("llama3.2-1b", smoke=True)
shape = ShapeConfig("t", "train", 32, 8)
batch = make_inputs(cfg, shape)
losses = {}
for mesh_shape in [(1, 1), (4, 2)]:
    mesh = make_mesh(mesh_shape, ("data", "model"))
    policy = CellPolicy(fsdp=True, microbatches=2, remat=True, loss_chunk=16)
    rules = make_rules(mesh, cfg, shape, policy)
    act = P(rules.get("batch"), None, None)
    st_specs = spec_train_state(cfg)
    st_sh = shardings_for(st_specs, mesh, rules)
    with jax.set_mesh(mesh):
        step = jax.jit(make_train_step(cfg, policy, adamw(1e-3), act_spec=act),
                       in_shardings=(st_sh, batch_pspec(input_specs(cfg, shape), mesh, rules)),
                       out_shardings=(st_sh, None))
        state = init_tree(st_specs, jax.random.PRNGKey(0))
        state = jax.device_put(state, st_sh)
        state, metrics = step(state, batch)
        state, metrics2 = step(state, batch)
        losses[str(mesh_shape)] = [float(metrics["loss"]), float(metrics2["loss"])]
print(json.dumps(losses))
""")
    losses = json.loads(out.strip().splitlines()[-1])
    a, b = losses["(1, 1)"], losses["(4, 2)"]
    assert abs(a[0] - b[0]) / abs(a[0]) < 2e-2, (a, b)
    assert abs(a[1] - b[1]) / abs(a[1]) < 2e-2, (a, b)
    assert b[1] < b[0]   # loss decreases


@pytest.mark.slow
def test_elastic_checkpoint_restore_onto_smaller_mesh(run_distributed):
    out = run_distributed("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro.configs import get_arch
from repro.dist.sharding import CellPolicy, make_rules, shardings_for
from repro.dist.steps import spec_train_state
from repro.models.config import ShapeConfig
from repro.models.spec import init_tree
from repro.runtime import CheckpointManager

cfg = get_arch("llama3.2-1b", smoke=True)
shape = ShapeConfig("t", "train", 32, 8)
st_specs = spec_train_state(cfg)
with tempfile.TemporaryDirectory() as d:
    from repro.launch.mesh import make_mesh
    m8 = make_mesh((4, 2), ("data", "model"))
    rules8 = make_rules(m8, cfg, shape, CellPolicy())
    sh8 = shardings_for(st_specs, m8, rules8)
    state = init_tree(st_specs, jax.random.PRNGKey(0))
    state = jax.device_put(state, sh8)
    ck = CheckpointManager(d, async_save=False)
    ck.save(7, state)
    # restore onto a smaller 2-device mesh (elastic shrink)
    m2 = make_mesh((2, 1), ("data", "model"))
    rules2 = make_rules(m2, cfg, shape, CellPolicy())
    sh2 = shardings_for(st_specs, m2, rules2)
    restored = ck.restore(state, shardings=sh2)
    w0 = np.asarray(jax.device_get(state["params"]["final_norm"]["scale"]))
    w1 = np.asarray(jax.device_get(restored["params"]["final_norm"]["scale"]))
    np.testing.assert_allclose(w0, w1)
    print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_gradient_compression_allreduce(run_distributed):
    """shard_map DP all-reduce with int8 compression + error feedback."""
    out = run_distributed("""
import jax, jax.numpy as jnp, numpy as np
from repro.dist.compression import compressed_psum_mean
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
def f(local, err):
    return compressed_psum_mean(local[0], err[0], axis_name="data", bits=8)
fn = jax.shard_map(lambda l, e: jax.tree_util.tree_map(lambda x: x[None], f(l, e)),
                   mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")))
out, new_err = fn(g, jnp.zeros_like(g))
want = g.mean(0)
got = np.asarray(out[0])
rel = np.abs(got - want).max() / np.abs(want).max()
assert rel < 0.08, rel
print("COMPRESS_OK", float(rel))
""")
    assert "COMPRESS_OK" in out
