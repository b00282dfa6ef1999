import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Dry-run for the PAPER'S OWN training step: distributed Cluster-GCN
(PPI-SOTA recipe: 5 layers × 2048 hidden, multilabel) on the production
mesh. Clusters are the data-parallel unit (each data-shard consumes its
own q-cluster batch — the block-diagonal objective of Eq. 6/7 decomposes
exactly); hidden layers optionally tensor-parallel over 'model'.

Run as its own process:  python -m repro.launch.dryrun_gcn [--variant V]

Variants (the §Perf hillclimb surface for target C):
  base   — paper-faithful: fp32, dense Â, weights replicated over model
  bf16   — C1: bf16 compute for Â·(XW) and X·W
  ax     — C2: + paper §6.2 A'X precompute (first propagation hoisted
           to the (cheap, host) batch builder)
  tp     — C3: + tensor-parallel hidden (alternating col/row sharding)
  sparse — C5: Â as a BlockEllAdj (block-ELL tiles + transpose), every
           Â·(XW) fwd AND bwd through the differentiable block-ELL spmm
           instead of a dense (cap, cap) matmul. K at the lossless worst
           case cap/B
  sparsek— C6: the fill-adaptive K-bucket shape (repro.core.kslots):
           same sparse step compiled at K=4 ≪ cap/B=10, the bucket a
           clustered PPI batch actually needs — the per-step FLOP and
           tile-memory saving of ISSUE 3 measured on the production mesh
"""
import argparse
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.gcn import GCNConfig, gcn_loss, init_gcn
from repro.kernels import BlockEllAdj
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import axis_size, data_axes, make_production_mesh
from repro.nn.optim import adamw, apply_updates

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# PPI-SOTA shape (paper §4.3 Table 10): node_cap from p=50 partitions of
# the 56944-node PPI graph (avg cluster ~1139 -> cap 1280 = 10×128)
CFG = dict(in_dim=50, hidden_dim=2048, out_dim=121, num_layers=5,
           node_cap=1280)


def build(variant: str, mesh):
    dax = data_axes(mesh)
    G = axis_size(mesh, dax)          # one cluster batch per data shard
    cap = CFG["node_cap"]
    bf16 = variant in ("bf16", "ax", "tp", "q4")
    precompute_ax = variant in ("ax", "tp", "q4")
    tp = variant in ("tp", "q4")
    if variant == "q4":               # §Perf C4: q=4 clusters per shard
        cap = 4 * CFG["node_cap"]     # batch (paper §3.2) — amortizes
                                      # the fixed collective cost 16×
    dt = jnp.bfloat16 if bf16 else jnp.float32

    cfg = GCNConfig(in_dim=CFG["in_dim"], hidden_dim=CFG["hidden_dim"],
                    out_dim=CFG["out_dim"], num_layers=CFG["num_layers"],
                    dropout=0.0, multilabel=True, layernorm=False,
                    precompute_ax=precompute_ax)

    # batch specs: stacked over the data axis
    sd = jax.ShapeDtypeStruct
    if variant in ("sparse", "sparsek"):
        # block-ELL Â at the shape the batcher emits: K = cap/B for
        # "sparse" (lossless worst case), K = 4 for "sparsek" (the
        # fill-adaptive bucket a clustered batch actually needs —
        # ClusterBatcher(k_slots="auto") emits these shapes)
        nrb = cap // 128
        K = 4 if variant == "sparsek" else nrb
        adj_spec = BlockEllAdj(
            blocks=sd((G, nrb, K, 128, 128), dt),
            block_cols=sd((G, nrb, K), jnp.int32),
            blocks_t=sd((G, nrb, K, 128, 128), dt),
            block_cols_t=sd((G, nrb, K), jnp.int32))
    else:
        adj_spec = sd((G, cap, cap), dt)
    batch = (
        adj_spec,                                    # adj (normalized)
        sd((G, cap, CFG["in_dim"]), dt),             # features
        sd((G, cap, CFG["out_dim"]), jnp.float32),   # labels (multilabel)
        sd((G, cap), jnp.bool_),                     # node mask
        sd((G, cap), jnp.float32),                   # loss mask
        sd((G,), jnp.int32),                         # num real
    )
    bsh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(dax, *([None] * (len(s.shape) - 1)))),
        batch)

    # shapes only — concrete inits are pathologically slow with 512 fake
    # host devices, and the AOT lower needs ShapeDtypeStructs anyway
    params = jax.eval_shape(lambda: init_gcn(jax.random.PRNGKey(0), cfg))
    # parameter shardings: replicated (base) or alternating col/row TP
    # (dims not divisible by the model axis stay replicated)
    msize = mesh.shape["model"]
    dims = cfg.dims

    def wspec(i):
        din, dout = dims[i]
        if not tp:
            return P(None, None), P(None)
        if i % 2 == 0 and dout % msize == 0:
            return P(None, "model"), P("model")
        if i % 2 == 1 and din % msize == 0:
            return P("model", None), P(None)
        return P(None, None), P(None)

    psh = {"layers": [
        {"w": NamedSharding(mesh, wspec(i)[0]),
         "b": NamedSharding(mesh, wspec(i)[1])}
        for i in range(cfg.num_layers)]}
    opt = adamw(1e-2)
    state_sh = {"params": psh, "mu": psh, "nu": psh}

    def loss_one(p, batch_tuple):
        if bf16:
            p = jax.tree_util.tree_map(lambda x: x.astype(dt), p)
        loss, aux = gcn_loss(p, batch_tuple, cfg, train=False)
        return loss, aux

    def train_step(state, batch):
        def mean_loss(p):
            losses, _ = jax.vmap(lambda bt: loss_one(p, bt))(batch)
            return losses.mean()
        loss, grads = jax.value_and_grad(mean_loss)(state["params"])
        from repro.nn.optim import AdamState
        upd, ost = opt.update(grads, AdamState(
            jnp.zeros((), jnp.int32), state["mu"], state["nu"]),
            state["params"])
        return {"params": apply_updates(state["params"], upd),
                "mu": ost.mu, "nu": ost.nu}, loss

    zeros = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), params)
    st_shapes = {"params": zeros, "mu": zeros, "nu": zeros}
    jitted = jax.jit(train_step, in_shardings=(state_sh, bsh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    return jitted, st_shapes, batch


def run(variant: str, multi_pod: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.set_mesh(mesh):
        jitted, st_shapes, batch = build(variant, mesh)
        t0 = time.perf_counter()
        lowered = jitted.lower(st_shapes, batch)
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        # this jaxlib's CPU CompiledMemoryStats has no peak_memory_in_bytes
        # — fall back to the arg+out+temp upper bound
        peak = getattr(ma, "peak_memory_in_bytes", None)
        if peak is None:
            peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes)
        walked = analyze_hlo(compiled.as_text())
    rec = dict(arch="clustergcn-ppi-sota", shape="train_cluster",
               mesh="multipod" if multi_pod else "pod", tag=variant,
               status="ok", compile_s=round(dt, 1),
               flops_per_device=walked["flops"],
               bytes_accessed_per_device=walked["bytes"],
               collectives=walked["collectives"],
               memory={"peak_memory_in_bytes": int(peak),
                       "argument_size_in_bytes":
                           int(ma.argument_size_in_bytes),
                       "temp_size_in_bytes": int(ma.temp_size_in_bytes)},
               num_devices=int(np.prod(list(mesh.shape.values()))))
    RESULTS.mkdir(parents=True, exist_ok=True)
    name = f"clustergcn-ppi-sota__train_cluster__{rec['mesh']}__{variant}.json"
    (RESULTS / name).write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="all",
                    choices=("base", "bf16", "ax", "tp", "q4", "sparse",
                             "sparsek", "all"))
    ap.add_argument("--multipod", action="store_true")
    args = ap.parse_args()
    variants = ("base", "bf16", "ax", "tp", "q4", "sparse", "sparsek") \
        if args.variant == "all" else (args.variant,)
    for v in variants:
        r = run(v, args.multipod)
        coll = sum(c["bytes"] for c in r["collectives"].values())
        print(f"[{v:5s}] flops/dev {r['flops_per_device']:.3g}  "
              f"bytes/dev {r['bytes_accessed_per_device']:.3g}  "
              f"coll {coll / 1e9:.2f} GB  "
              f"peak {r['memory']['peak_memory_in_bytes'] / 2**30:.2f} GiB")


if __name__ == "__main__":
    main()
