"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax device query.

Target hardware: TPU v5e pods, 256 chips / pod (16×16), 2 pods for the
multi-pod dry-run. Axes:
  pod   — inter-pod data parallelism (DCN-connected)
  data  — intra-pod data parallelism / FSDP shard axis (ICI)
  model — tensor / expert / sequence parallelism (ICI)
"""
from __future__ import annotations

import jax

# v5e hardware constants used by the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link (~4 links usable per chip)


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axes on every dimension. The steps place
    their operands through in_shardings / shard_map specs and pin
    activations with with_sharding_constraint, which under jax's default
    Explicit axes would act as an assert; Auto axes also keep sharded
    outputs indexable on the host."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small host mesh for tests (requires xla_force_host_platform_device_count)."""
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n
