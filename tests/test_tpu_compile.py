"""Ahead-of-time compiles of the block-ELL Pallas kernels for a TPU v5e
chip at ppi_sota widths (B=128, hidden 2048, input 50): what the chip's
compiler would refuse — unaligned slices, too much VMEM — fails here
without a chip. The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the one
running this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_spmm import (BlockEllAdj, spmm_block_ell,
                                      spmm_ell, spmm_fused_block_ell)

B, NRB, K = 128, 3, 4          # ppi_sota batch: node_cap 384, K bucket
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described v5e device, with the persistent compilation cache off:
    entries compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operands(sharding, d, f, dtype):
    n = NRB * B
    return {"blocks": _sds(sharding, (NRB, K, B, B), dtype),
            "cols": _sds(sharding, (NRB, K), jnp.int32),
            "row_k": _sds(sharding, (NRB,), jnp.int32),
            "x": _sds(sharding, (n, d), dtype),
            "w": _sds(sharding, (d, f), dtype),
            "b": _sds(sharding, (f,), jnp.float32)}


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("row_k", [False, True], ids=["allk", "rowk"])
def test_block_ell_spmm_compiles_for_v5e(one_chip, row_k, dtype):
    o = _operands(one_chip, 2048, 2048, dtype)
    if row_k:
        fn = lambda b, c, x, rk: spmm_block_ell(b, c, x, row_k=rk)
        text = _compiled_text(fn, o["blocks"], o["cols"], o["x"],
                              o["row_k"])
    else:
        text = _compiled_text(spmm_block_ell, o["blocks"], o["cols"],
                              o["x"])
    assert CUSTOM_CALL in text


@pytest.mark.parametrize("d,dtype", [(50, jnp.float32),
                                     (2048, jnp.bfloat16)],
                         ids=["layer1-fp32", "hidden-bf16"])
def test_fused_spmm_compiles_for_v5e(one_chip, d, dtype):
    o = _operands(one_chip, d, 2048, dtype)
    fn = lambda b, c, x, w, bias, rk: spmm_fused_block_ell(b, c, x, w, bias,
                                                           row_k=rk)
    text = _compiled_text(fn, o["blocks"], o["cols"], o["x"], o["w"],
                          o["b"], o["row_k"])
    assert CUSTOM_CALL in text


def test_spmm_ell_grad_compiles_for_v5e(one_chip):
    """The custom VJP: the backward is the same kernel on the transposed
    tiles, so the gradient program holds the kernel too."""
    o = _operands(one_chip, 2048, 2048, jnp.float32)
    adj = BlockEllAdj(blocks=o["blocks"], block_cols=o["cols"],
                      blocks_t=o["blocks"], block_cols_t=o["cols"],
                      row_k=o["row_k"], row_k_t=o["row_k"])
    loss = lambda a, x: spmm_ell(a, x, impl="pallas").sum()
    text = _compiled_text(jax.grad(loss, argnums=1), adj, o["x"])
    assert text.count(CUSTOM_CALL) >= 1
