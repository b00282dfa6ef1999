import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh)
cell on the production mesh and extract roofline inputs.

MUST be run as its own process (`python -m repro.launch.dryrun …`) — the
XLA_FLAGS line above executes before any other import so jax sees 512
host devices. Never import this module from tests/benches.

Per cell we record (results/dryrun/<arch>__<shape>__<mesh>.json):
  memory_analysis : per-device argument/temp/output/peak bytes
  cost_analysis   : per-device HLO FLOPs and bytes accessed
  collectives     : per-kind count + estimated wire bytes per device,
                    parsed from the post-SPMD HLO text
  policy          : the CellPolicy used (hillclimb iterations change it)
"""
import argparse
import dataclasses
import json
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_NAMES, cell_supported, get_arch,
                           input_specs)
from repro.dist.sharding import (CellPolicy, batch_pspec, make_rules,
                                 shardings_for, replicated)
from repro.dist.steps import (make_decode_step, make_encode_step,
                              make_prefill_step, make_train_step,
                              spec_train_state)
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import axis_size, data_axes, make_production_mesh
from repro.models.config import SHAPES
from repro.models.lm import spec_caches, spec_params
from repro.models.spec import shape_tree
from repro.nn.optim import adamw

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

def default_policy(cfg, shape, mesh) -> CellPolicy:
    dsize = axis_size(mesh, data_axes(mesh))
    micro = 1
    if shape.kind == "train":
        b_dev = max(1, shape.global_batch // dsize)
        # §Perf iteration B2: each microbatch re-all-gathers every FSDP
        # weight shard once per layer — collective bytes scale linearly
        # with the microbatch count. Target the LARGEST microbatch that
        # plausibly fits HBM (remat keeps activations ~ residual-only):
        # ~16k tokens/microbatch for big models, ~32k for small.
        big = cfg.d_model > 2048 or bool(cfg.num_experts)
        rows = max(1, (16384 if big else 32768) // shape.seq_len)
        micro = max(1, b_dev // rows)
        while b_dev % micro:
            micro -= 1
    loss_chunk = 256 if cfg.vocab_size > 131072 else 512
    return CellPolicy(fsdp=True, microbatches=micro, remat=True,
                      loss_chunk=loss_chunk)


def lower_cell(arch_name: str, shape_name: str, mesh, policy: CellPolicy):
    from jax.sharding import PartitionSpec as P
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    rules = make_rules(mesh, cfg, shape, policy)
    bspecs = input_specs(cfg, shape)
    bsh = batch_pspec(bspecs, mesh, rules)
    act_spec = P(rules.get("batch"), None, None)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            st_specs = spec_train_state(cfg)
            st_sh = shardings_for(st_specs, mesh, rules)
            step = make_train_step(cfg, policy, adamw(3e-4, clip_norm=1.0),
                                   act_spec=act_spec)
            jitted = jax.jit(step, in_shardings=(st_sh, bsh),
                             out_shardings=(st_sh, None),
                             donate_argnums=(0,))
            return jitted.lower(shape_tree(st_specs), bspecs)
        p_specs = spec_params(cfg)
        p_sh = shardings_for(p_specs, mesh, rules)
        if shape.kind == "prefill":
            if cfg.is_encoder:
                step = make_encode_step(cfg, policy, act_spec=act_spec)
                jitted = jax.jit(step, in_shardings=(p_sh, bsh))
                return jitted.lower(shape_tree(p_specs), bspecs)
            c_specs = spec_caches(cfg, shape.global_batch, shape.seq_len)
            c_sh = shardings_for(c_specs, mesh, rules)
            step = make_prefill_step(cfg, policy, act_spec=act_spec)
            jitted = jax.jit(step, in_shardings=(p_sh, bsh, c_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(2,))
            return jitted.lower(shape_tree(p_specs), bspecs,
                                shape_tree(c_specs))
        # decode: one new token against a seq_len-deep cache
        c_specs = spec_caches(cfg, shape.global_batch, shape.seq_len)
        c_sh = shardings_for(c_specs, mesh, rules)
        step = make_decode_step(cfg, policy, act_spec=act_spec)
        tok_sh = batch_pspec(bspecs, mesh, rules)["tokens"]
        jitted = jax.jit(step,
                         in_shardings=(p_sh, tok_sh, c_sh, replicated(mesh)),
                         out_shardings=(tok_sh, None, c_sh),
                         donate_argnums=(2,))
        return jitted.lower(shape_tree(p_specs), bspecs["tokens"],
                            shape_tree(c_specs),
                            jax.ShapeDtypeStruct((), jnp.int32))


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             policy: CellPolicy | None = None, tag: str = "baseline",
             save: bool = True) -> dict:
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
           "tag": tag}
    if not ok:
        rec.update(status="skip", reason=reason)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        policy = policy or default_policy(cfg, shape, mesh)
        rec["policy"] = dataclasses.asdict(policy)
        t0 = time.perf_counter()
        try:
            lowered = lower_cell(arch_name, shape_name, mesh, policy)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            walked = analyze_hlo(hlo)   # loop-aware (see hlo_analysis.py)
            rec.update(
                status="ok", lower_s=round(t_lower, 1),
                compile_s=round(t_compile, 1),
                memory={k: int(getattr(ma, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "peak_memory_in_bytes",
                    "alias_size_in_bytes")},
                flops_per_device=float(walked["flops"]),
                bytes_accessed_per_device=float(walked["bytes"]),
                collectives=walked["collectives"],
                xla_raw_flops=float(ca.get("flops", 0.0)),
                xla_raw_bytes=float(ca.get("bytes accessed", 0.0)),
                num_devices=int(np.prod(list(mesh.shape.values()))),
                mesh_shape={k: int(v) for k, v in mesh.shape.items()},
            )
        except Exception as e:  # record failures — they are bugs to fix
            rec.update(status="error", error=f"{type(e).__name__}: {e}"[:2000])
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        name = f"{arch_name}__{shape_name}__{mesh_kind}__{tag}.json"
        (RESULTS / name).write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id or 'all'")
    ap.add_argument("--shape", default=None, help="shape name or 'all'")
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod",
                                                      "both"))
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=None)
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape in (None, "all") else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shp in shapes:
            for mk in meshes:
                out = RESULTS / f"{arch}__{shp}__{mk}__{args.tag}.json"
                if out.exists() and not args.force:
                    prev = json.loads(out.read_text())
                    if prev.get("status") in ("ok", "skip"):
                        print(f"[cached] {arch} × {shp} × {mk}: "
                              f"{prev['status']}")
                        continue
                policy = None
                if any(v is not None for v in (args.microbatches,
                                               args.loss_chunk)) \
                        or args.no_fsdp or args.no_remat:
                    cfg = get_arch(arch)
                    shape = SHAPES[shp]
                    mesh = make_production_mesh(multi_pod=(mk == "multipod"))
                    base = default_policy(cfg, shape, mesh)
                    policy = dataclasses.replace(
                        base,
                        fsdp=not args.no_fsdp,
                        remat=not args.no_remat,
                        microbatches=args.microbatches or base.microbatches,
                        loss_chunk=args.loss_chunk or base.loss_chunk)
                t0 = time.perf_counter()
                rec = run_cell(arch, shp, mk, policy, tag=args.tag)
                dt = time.perf_counter() - t0
                if rec["status"] == "ok":
                    mem = rec["memory"]["peak_memory_in_bytes"] / 2**30
                    print(f"[ok {dt:6.1f}s] {arch} × {shp} × {mk}: "
                          f"peak {mem:.2f} GiB/dev, "
                          f"{rec['flops_per_device']:.3g} FLOP/dev")
                elif rec["status"] == "skip":
                    print(f"[skip] {arch} × {shp} × {mk}: {rec['reason']}")
                else:
                    print(f"[ERROR {dt:6.1f}s] {arch} × {shp} × {mk}: "
                          f"{rec['error'][:200]}")


if __name__ == "__main__":
    main()
