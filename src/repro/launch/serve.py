"""LANGUAGE-MODEL serving demo: batched prefill + greedy decode loop
over the transformer stack (repro.models.lm) — NOT the Cluster-GCN
serving layer. GCN predictions are served by `repro.launch.serve_gcn`
(per-cluster embedding cache + jit'd query path, docs/serving.md);
this module is the KV-cache prefill/decode demo kept from the
sharding-infrastructure PRs and exercised by examples/serve_lm.py.

CPU smoke run:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.dist.sharding import CellPolicy, make_rules, shardings_for
from repro.dist.steps import make_decode_step, make_prefill_step
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.config import ShapeConfig
from repro.models.lm import spec_caches, spec_params
from repro.models.spec import init_tree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="host",
                    choices=("host", "pod", "multipod"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=args.smoke)
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only — no decode serving")
    max_seq = args.prompt_len + args.gen
    shape = ShapeConfig("cli", "decode", max_seq, args.batch)

    if args.mesh == "host":
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
    policy = CellPolicy(fsdp=False, remat=False)
    rules = make_rules(mesh, cfg, shape, policy)
    act_spec = P(rules.get("batch"), None, None)

    with jax.set_mesh(mesh):
        p_specs = spec_params(cfg)
        c_specs = spec_caches(cfg, args.batch, max_seq)
        p_sh = shardings_for(p_specs, mesh, rules)
        c_sh = shardings_for(c_specs, mesh, rules)
        params = init_tree(p_specs, jax.random.PRNGKey(args.seed))
        caches = init_tree(c_specs, jax.random.PRNGKey(1))

        prefill_fn = jax.jit(make_prefill_step(cfg, policy, act_spec),
                             in_shardings=(p_sh, None, c_sh),
                             out_shardings=(None, c_sh))
        decode_fn = jax.jit(make_decode_step(cfg, policy, act_spec),
                            in_shardings=(p_sh, None, c_sh, None),
                            out_shardings=(None, None, c_sh),
                            donate_argnums=(2,))

        rng = np.random.default_rng(args.seed)
        prompts = rng.integers(0, cfg.vocab_size,
                               size=(args.batch, args.prompt_len),
                               dtype=np.int32)
        batch = {"tokens": jnp.asarray(prompts)}
        if cfg.num_prefix_embeddings:
            batch["prefix_embeddings"] = jnp.asarray(rng.normal(size=(
                args.batch, cfg.num_prefix_embeddings,
                cfg.d_model)).astype(np.float32))

        t0 = time.perf_counter()
        logits, caches = prefill_fn(params, batch, caches)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]

        generated = [tok]
        t0 = time.perf_counter()
        npfx = cfg.num_prefix_embeddings
        for i in range(args.gen - 1):
            pos = jnp.asarray(args.prompt_len + npfx + i, jnp.int32)
            tok, logits, caches = decode_fn(params, tok, caches, pos)
            generated.append(tok)
        jax.block_until_ready(generated[-1])
        t_decode = time.perf_counter() - t0
        out = np.concatenate([np.asarray(t) for t in generated], axis=1)

        toks_s = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
        print(f"[serve] {cfg.name}: prefill {args.batch}×{args.prompt_len} "
              f"in {t_prefill:.2f}s; decode {args.gen - 1} steps "
              f"@ {toks_s:.1f} tok/s")
        print("[serve] sample generation (first row):", out[0][:16])


if __name__ == "__main__":
    main()
