"""Training launcher.

Runs the same pjit train step the dry-run lowers, with the full
production runtime around it: sharded state init, deterministic sharded
data, async checkpointing + restore (elastic), preemption handling, and
straggler monitoring.

CPU smoke run (1 device):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ck
Production (TPU pod): same entry point; the mesh comes from --mesh.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.data.tokens import Prefetcher, TokenPipeline
from repro.dist.sharding import CellPolicy, batch_pspec, make_rules, \
    shardings_for
from repro.dist.steps import make_train_step, spec_train_state
from repro.launch.mesh import (axis_size, data_axes, make_mesh,
                               make_production_mesh)
from repro.models.config import ShapeConfig
from repro.models.spec import init_tree, shape_tree, spec_params as count_p
from repro.nn.optim import adamw, warmup_cosine_schedule
from repro.runtime import (CheckpointManager, PreemptionHandler,
                           StragglerDetector)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="host",
                    choices=("host", "pod", "multipod"))
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=args.smoke)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)

    if args.mesh == "host":
        ndev = len(jax.devices())
        mesh = make_mesh((ndev, 1), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))

    policy = CellPolicy(fsdp=args.mesh != "host",
                        microbatches=args.microbatches, remat=True,
                        loss_chunk=min(512, args.seq))
    rules = make_rules(mesh, cfg, shape, policy)
    act_spec = P(rules.get("batch"), None, None)

    opt = adamw(warmup_cosine_schedule(args.lr, 10, args.steps),
                weight_decay=0.01, clip_norm=1.0)
    step_fn = make_train_step(cfg, policy, opt, act_spec=act_spec)

    st_specs = spec_train_state(cfg)
    st_sh = shardings_for(st_specs, mesh, rules)
    print(f"[train] {cfg.name}: {count_p(st_specs['params']):,} params, "
          f"mesh {dict(mesh.shape)}")

    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=(st_sh, None),
                         out_shardings=(st_sh, None), donate_argnums=(0,))
        state = init_tree(st_specs, jax.random.PRNGKey(args.seed))

        ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
        start_step = 0
        if ckpt and ckpt.latest_step() is not None:
            state = ckpt.restore(state, shardings=st_sh)
            start_step = int(np.asarray(state["step"]))
            print(f"[train] restored checkpoint at step {start_step}")

        dsize = axis_size(mesh, data_axes(mesh))
        pipe = TokenPipeline(cfg.vocab_size, args.batch, args.seq,
                             seed=args.seed)
        straggler = StragglerDetector()
        t_last = time.perf_counter()

        with PreemptionHandler() as pre:
            for step in range(start_step, args.steps):
                batch = pipe.batch_at(step)
                state, metrics = jitted(state, batch)
                if (step + 1) % args.log_every == 0 or step == start_step:
                    dt = time.perf_counter() - t_last
                    t_last = time.perf_counter()
                    flagged = straggler.record({0: dt})
                    print(json.dumps({
                        "step": step + 1,
                        "loss": round(float(metrics["loss"]), 4),
                        "acc": round(float(metrics["acc"]), 4),
                        "s_per_step": round(dt / args.log_every, 3),
                        **({"stragglers": flagged} if flagged else {}),
                    }))
                if ckpt and (step + 1) % args.ckpt_every == 0:
                    ckpt.save(step + 1, state)
                if pre.should_stop:
                    print("[train] preemption signal — checkpoint + exit")
                    if ckpt:
                        ckpt.save(step + 1, state, blocking=True)
                    return
        if ckpt:
            ckpt.save(args.steps, state, blocking=True)
        print("[train] done")


if __name__ == "__main__":
    main()
