"""Smoke test of Cluster-GCN on a TPU: the paper's §4.3 deep recipe
(preset `ppi_sota`: 5 layers, hidden 2048, Eq. 11 diagonal enhancement,
the seeded 14,000-node PPI-shaped graph in 50 clusters) trained and
served through the normal entry points.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: phase C only

A. Training, one epoch from one seed, three ways: (a) the preset as
   shipped (dense Â, XLA matmul), (b) block-ELL Â through the Pallas
   kernel and its custom VJP, (c) as (b) with the fused Â·(XW+b)
   kernel. All three train at "highest" matmul precision, so they do
   the same fp32 math up to summation order and their per-step losses
   are held to (a)'s: at the TPU's default precision (bf16 operands)
   rounding flips the sign of small gradients, and Adam's first steps
   turn that into trajectories that part within the epoch. The kernels
   at the default precision users run are checked on the first batch
   instead: ops.spmm / ops.spmm_xw against the XLA reference, forward
   and VJP.
B. Serving: a ServeEngine on the checkpoint (c) wrote; warm() runs the
   forward kernel over the whole graph, every request bucket answers a
   few queries, and the served logits are held to the host fp32
   full-graph forward (trainer.full_graph_logits).
C. (--four-chips) The same recipe data-parallel over four devices with
   an exact all-reduce: the first step against one chip computing the
   mean of the four per-batch gradients through the same optimizer, then
   the rest of the epoch.

Every result goes to earlier lines of stdout; the last line is
{"ok": true, "device": {...}} only when every check passed. With no TPU,
or without the repository's src/ next to this file, it exits non-zero
and prints no result. Caches (partitions, serving, checkpoints) live
under --out. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
SRC = REPO / "src"

PRESET = "ppi_sota"
VARIANTS = {
    "a_dense": {},
    "b_sparse": {"batch.sparse_adj": True, "batch.k_slots": "auto"},
    "c_fused": {"batch.sparse_adj": True, "batch.k_slots": "auto",
                "model.fuse_spmm": True},
}
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

TRAIN_PRECISION = "highest"
# Tolerances, each max|got - want| / max|want| unless noted; the
# reference side of every check runs at "highest" matmul precision.
# Kernels at default precision round both operands to bf16 (2^-9
# relative each): measured 2.0e-3..4.1e-3 on a v5e chip.
KERNEL_TOL = 1e-2        # Pallas (default precision) vs XLA ref
# per-step loss of (b), (c) vs (a), relative. The first step sees the
# same params and batch, so only fp32 summation order differs. Later
# steps inherit that difference through Adam, whose early steps move
# each weight by about lr·sign(g): on v5e the gap was <1e-8 at step 1
# and at most 1.24e-2 (sparse) and 1.36e-2 (fused) over the epoch.
LOSS_FIRST_TOL = 1e-5
LOSS_TOL = 5e-2
# serving: max |served - host fp32 logit|, absolute on the CPU (the
# docs/serving.md contract), relative to max |logit| on a TPU, whose
# forward kernel runs at default precision (measured 3.4e-3 on v5e)
SERVE_PARITY_CPU = 1e-5
SERVE_PARITY_TPU_REL = 1e-2
# four-chip step vs one chip, same precision on both sides: measured
# loss 0, mean gradient 1.7e-7, 3.9e-7 of the weights past 1e-5 (v5e)
DP_LOSS_TOL = 1e-5       # loss, relative
DP_GRAD_TOL = 1e-5       # mean gradient (Adam first moment)
DP_PARAM_ATOL = 1e-5     # updated params: |Δ| that counts as a gap,
DP_PARAM_FRAC = 1e-5     # and the share of weights allowed such a gap


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Checks:
    """Named pass/fail results; the run fails if any one failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> bool:
        log(f"check {name}: {detail} [{'OK' if ok else 'FAIL'}]")
        if not ok:
            self.failed.append(name)
        return ok


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def tree_rel_err(got, want) -> float:
    import jax
    return max(rel_err(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                            jax.tree_util.tree_leaves(want)))


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_spec(preset_name: str, out: pathlib.Path, overrides: dict):
    """The preset with --set-style overrides, one epoch, and every cache
    under `out`."""
    from repro.core.experiment import apply_overrides, preset, validate
    spec = preset(preset_name)
    apply_overrides(spec, {"run.epochs": 1,
                           "partition.cache_dir": str(out / "partitions"),
                           "serve.cache_dir": str(out / "serving"),
                           **overrides})
    return validate(spec)


def spmm_fns(mode: str):
    """(spmm, spmm_xw) for a kernel mode; "auto" is what users run."""
    from repro.kernels import ops
    return (functools.partial(ops.spmm, mode=mode),
            functools.partial(ops.spmm_xw, mode=mode))


def use_mode(exp, mode: str):
    """Point the experiment's step at `mode` ("interpret" rehearses the
    Pallas kernels on a CPU); "auto" keeps the backend as built."""
    from repro.core.engine import ShardMapBackend, SingleDeviceBackend
    if mode == "auto":
        return exp.engine.backend
    spmm, spmm_xw = spmm_fns(mode)
    if exp.mesh is None:
        backend = SingleDeviceBackend(exp.cfg, exp.opt, spmm, spmm_xw)
    else:
        backend = ShardMapBackend(exp.cfg, exp.opt, exp.mesh,
                                  dp_axis=exp.spec.execution.dp_axis,
                                  spmm=spmm, spmm_xw=spmm_xw)
    exp.engine.backend = backend
    return backend


class StepLosses:
    """Engine hook: the loss of every step, kept on the device until the
    end of the run."""

    def __init__(self):
        self.losses = []

    def on_step(self, engine, info) -> None:
        self.losses.append(info["loss"])


def train_variant(name: str, out: pathlib.Path, *, preset_name: str = PRESET,
                  mode: str = "auto", extra: dict | None = None) -> dict:
    """Phase A, one variant: build the experiment from the preset, AOT
    compile its first step (compile seconds, tpu_custom_call count), then
    Engine.fit one epoch, all at TRAIN_PRECISION."""
    import jax
    import numpy as np
    from repro.core.experiment import build_experiment
    spec = make_spec(preset_name, out, {**VARIANTS[name], **(extra or {})})
    rec = StepLosses()
    exp = build_experiment(spec, extra_hooks=[rec])
    backend = use_mode(exp, mode)
    with jax.default_matmul_precision(TRAIN_PRECISION):
        first = next(iter(exp.batcher.epoch(0))).astuple()
        t0 = time.perf_counter()
        compiled = backend.lower(exp.engine.init_state(), first).compile()
        compile_s = time.perf_counter() - t0
        result = exp.fit()
    losses = np.asarray([float(l) for l in rec.losses])
    return {"name": name, "exp": exp, "result": result, "losses": losses,
            "compile_s": compile_s,
            "custom_calls": compiled.as_text().count(CUSTOM_CALL),
            "steps": exp.engine.global_step,
            "diverged": exp.engine.diverged,
            "stop_reason": exp.engine.stop_reason}


def report_training(v: dict, checks: Checks) -> None:
    import numpy as np
    losses = v["losses"]
    log(f"train {v['name']}: compile {v['compile_s']:.3f}s, {v['steps']} "
        f"steps in {v['result'].seconds:.3f}s, loss {losses[0]:.6f} -> "
        f"{losses[-1]:.6f}, tpu_custom_calls {v['custom_calls']}")
    checks(f"train_{v['name']}_healthy",
           bool(len(losses) and np.isfinite(losses).all())
           and not v["diverged"] and v["stop_reason"] is None,
           f"finite={bool(np.isfinite(losses).all())} "
           f"diverged={v['diverged']} stop_reason={v['stop_reason']}")


def kernel_check(exp, mode: str, checks: Checks) -> None:
    """On the first sparse batch: ops.spmm / ops.spmm_xw in `mode` against
    mode="ref" at "highest" precision, forward and VJP, at the model's
    widths (hidden, output; input → hidden and hidden → hidden)."""
    import jax
    from repro.kernels import ops
    batch = next(iter(exp.batcher.epoch(0)))
    adj, cap = batch.adj, batch.features.shape[0]
    cfg = exp.cfg
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(*shape):
        return jax.random.normal(next(keys), shape)

    cases = [(f"spmm_F{f}", lambda m: functools.partial(ops.spmm, adj,
                                                        mode=m),
              (normal(cap, f),), f)
             for f in (cfg.hidden_dim, cfg.out_dim)]
    cases += [(f"spmm_xw_D{d}_F{f}",
               lambda m: functools.partial(ops.spmm_xw, adj, mode=m),
               (normal(cap, d), normal(d, f) * d ** -0.5, normal(f)), f)
              for d, f in ((cfg.in_dim, cfg.hidden_dim),
                           (cfg.hidden_dim, cfg.hidden_dim))]
    for label, fn, args, f in cases:
        g = normal(cap, f)
        y, vjp = jax.vjp(fn(mode), *args)
        with jax.default_matmul_precision("highest"):
            y_ref, vjp_ref = jax.vjp(fn("ref"), *args)
            grads_ref = vjp_ref(g)
        fwd, bwd = rel_err(y, y_ref), tree_rel_err(vjp(g), grads_ref)
        checks(f"kernel_{label}", max(fwd, bwd) <= KERNEL_TOL,
               f"{mode} vs ref: fwd {fwd:.3e}, vjp {bwd:.3e} "
               f"(tol {KERNEL_TOL:g})")


def loss_check(v: dict, ref: dict, checks: Checks) -> None:
    import numpy as np
    a, b = ref["losses"], v["losses"]
    if len(a) != len(b) or not len(a):
        checks(f"losses_{v['name']}_vs_{ref['name']}", False,
               f"{len(b)} vs {len(a)} steps")
        return
    gap = np.abs(b - a) / np.abs(a)
    by_step = ", ".join(f"{s}:{gap[s - 1]:.2e}"
                        for s in (1, 2, 5, 10, 25, 50) if s <= len(gap))
    checks(f"losses_{v['name']}_vs_{ref['name']}",
           gap[0] <= LOSS_FIRST_TOL and gap.max() <= LOSS_TOL,
           f"{len(b)} steps, relative step-loss gap first {gap[0]:.3e} "
           f"(tol {LOSS_FIRST_TOL:g}), max {gap.max():.3e} (tol "
           f"{LOSS_TOL:g}); by step {by_step}")


@contextlib.contextmanager
def count_compiles():
    """Yields a list that grows by one per jit lowering (a new shape)
    inside the block, fed by JAX's own monitoring events."""
    import jax
    events = []

    def listener(event, duration, **kw):
        if event == LOWERING_EVENT:
            events.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def serve_phase(exp, checks: Checks, platform: str,
                per_bucket: int = 3) -> None:
    """Phase B: ServeEngine.from_checkpoint on the run's checkpoint,
    warm(), a few queries per request bucket, parity against the host
    fp32 full-graph forward."""
    import numpy as np
    from repro.core.trainer import full_graph_logits
    from repro.serve import ServeEngine
    spec = exp.spec
    engine = ServeEngine.from_checkpoint(spec, spec.run.checkpoint_dir,
                                         graph=exp.graph)
    with count_compiles() as compiles:
        t0 = time.perf_counter()
        warmed = engine.warm()
        precompute_s = time.perf_counter() - t0
    log(f"serve: precomputed {warmed} clusters in {precompute_s:.3f}s "
        f"with {len(compiles)} compiles; buckets {engine.buckets}")
    rng = np.random.default_rng(0)
    results = []
    for b in engine.buckets:
        for _ in range(per_bucket):
            r = engine.query(rng.integers(0, engine.graph.num_nodes, size=b))
            results.append(r)
        lat = sorted(r.latency_s for r in results[-per_bucket:])
        log(f"serve: bucket {b}: {per_bucket} queries, latency "
            f"{[f'{x * 1e3:.3f}ms' for x in lat]}")
    ref = full_graph_logits(engine.params, engine.graph, engine.cfg,
                            norm=engine.norm, diag_lambda=engine.diag_lambda)
    worst = max(float(np.abs(r.logits - ref[r.node_ids]).max())
                for r in results)
    scale = float(np.abs(ref).max())
    finite = all(np.isfinite(r.probs).all() and np.isfinite(r.logits).all()
                 for r in results)
    bound = (SERVE_PARITY_TPU_REL * scale if platform == "tpu"
             else SERVE_PARITY_CPU)
    checks("serve_parity", finite and worst <= bound,
           f"{len(results)} queries, max |served - full_graph_logits| "
           f"{worst:.3e} (max |logit| {scale:.3e}, bound {bound:g})")


def one_chip_phases(out: pathlib.Path, checks: Checks, *,
                    preset_name: str = PRESET, mode: str = "auto",
                    platform: str = "tpu") -> dict:
    """Phases A and B. Returns the three training variants by name."""
    runs = {}
    for name in VARIANTS:
        extra = ({"run.checkpoint_dir": str(out / "ckpt")}
                 if name == "c_fused" else None)
        runs[name] = train_variant(name, out, preset_name=preset_name,
                                   mode=mode, extra=extra)
        report_training(runs[name], checks)
    if platform == "tpu":
        # the dense path runs no kernel; the sparse ones must, or the
        # "auto" dispatch fell back to the XLA reference
        checks("custom_calls", runs["a_dense"]["custom_calls"] == 0
               and runs["b_sparse"]["custom_calls"] > 0
               and runs["c_fused"]["custom_calls"] > 0,
               "tpu_custom_call counts " + ", ".join(
                   f"{n}={r['custom_calls']}" for n, r in runs.items()))
    kernel_check(runs["b_sparse"]["exp"],
                 "pallas" if mode == "auto" else mode, checks)
    for name in ("b_sparse", "c_fused"):
        loss_check(runs[name], runs["a_dense"], checks)
    serve_phase(runs["c_fused"]["exp"], checks, platform)
    return runs


def one_chip_update(exp, state, payload, mode: str):
    """The update the first DP step should make, on one device: the mean
    of the per-batch gradients (each shard's dropout key derived as the
    DP step derives it) through the same optimizer. Returns (mean loss,
    params, first moment)."""
    import jax
    import jax.numpy as jnp
    from repro.core.gcn import gcn_loss
    from repro.nn.optim import apply_updates
    spmm, spmm_xw = spmm_fns(mode)
    cfg, opt = exp.cfg, exp.opt
    n = jax.tree_util.tree_leaves(payload)[0].shape[0]
    _, sub = jax.random.split(state["rng"])
    dist = jax.device_get(state["dist"])

    @jax.jit
    def update(params, opt_state, batches):
        losses, grads = [], []
        for i in range(n):
            key = jax.random.split(jax.random.fold_in(sub, i), 1)[0]
            bt = jax.tree_util.tree_map(lambda x: x[i], batches)
            (loss, _), g = jax.value_and_grad(gcn_loss, has_aux=True)(
                params, bt, cfg, train=True, rng=key, spmm=spmm,
                spmm_xw=spmm_xw)
            losses.append(loss)
            grads.append(g)
        mean_g = jax.tree_util.tree_map(lambda *gs: sum(gs) / n, *grads)
        updates, new_opt = opt.update(mean_g, opt_state, params)
        return jnp.mean(jnp.stack(losses)), apply_updates(params,
                                                          updates), new_opt
    with jax.default_device(jax.devices()[0]):
        loss, params, new_opt = update(dist["params"], dist["opt"],
                                       jax.device_get(payload))
    return float(loss), jax.device_get(params), jax.device_get(new_opt.mu)


def four_chip_phase(out: pathlib.Path, checks: Checks, *,
                    preset_name: str = PRESET, mode: str = "auto",
                    shards: int = 4) -> None:
    """Phase C: ppi_sota sparse, execution.data_shards=4, exact psum."""
    import jax
    import numpy as np
    from repro.core.experiment import build_experiment
    spec = make_spec(preset_name, out, {**VARIANTS["b_sparse"],
                                        "execution.data_shards": shards,
                                        "execution.compression": None})
    exp = build_experiment(spec)
    backend = use_mode(exp, mode)
    payload = next(backend.stream(b.astuple()
                                  for b in exp.batcher.epoch(0)))
    placed = jax.device_put(payload, backend.batch_sharding)
    feats = placed[1]
    log(f"dp: features {feats.shape} on {len(feats.addressable_shards)} "
        "devices: " + ", ".join(f"{s.device.id}:{tuple(s.data.shape)}"
                                for s in feats.addressable_shards))
    state = exp.engine.init_state()
    ref_loss, ref_params, ref_mu = one_chip_update(exp, state, payload, mode)
    t0 = time.perf_counter()
    new, loss, _ = backend.step(state, placed)
    loss = float(loss)
    log(f"dp: first step (compile included) {time.perf_counter() - t0:.3f}s,"
        f" loss {loss:.6f} vs one chip {ref_loss:.6f}")
    dist = jax.device_get(new["dist"])
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    grad_err = tree_rel_err(dist["opt"].mu, ref_mu)
    # Adam's first step moves each weight by about lr·sign(g): a weight
    # whose mean gradient is within rounding of zero may step the other
    # way, so count such weights instead of bounding the largest gap
    gaps = np.concatenate([
        np.abs(np.asarray(a) - np.asarray(b)).ravel()
        for a, b in zip(jax.tree_util.tree_leaves(dist["params"]),
                        jax.tree_util.tree_leaves(ref_params))])
    moved = float((gaps > DP_PARAM_ATOL).mean())
    checks("dp_vs_one_chip",
           loss_err <= DP_LOSS_TOL and grad_err <= DP_GRAD_TOL
           and moved <= DP_PARAM_FRAC,
           f"loss rel {loss_err:.3e} (tol {DP_LOSS_TOL:g}), mean grad rel "
           f"{grad_err:.3e} (tol {DP_GRAD_TOL:g}), params: max|Δ| "
           f"{gaps.max():.3e}, share above {DP_PARAM_ATOL:g} {moved:.3e} "
           f"of {gaps.size} (tol {DP_PARAM_FRAC:g})")
    rec = StepLosses()
    exp.engine.hooks.append(rec)
    result = exp.fit()
    losses = np.asarray([float(l) for l in rec.losses])
    log(f"dp: epoch of {exp.engine.global_step} steps in "
        f"{result.seconds:.3f}s, loss {losses[0]:.6f} -> {losses[-1]:.6f}")
    checks("dp_train_healthy",
           bool(np.isfinite(losses).all()) and not exp.engine.diverged
           and exp.engine.stop_reason is None,
           f"finite={bool(np.isfinite(losses).all())} "
           f"diverged={exp.engine.diverged} "
           f"stop_reason={exp.engine.stop_reason}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device data-parallel phase")
    ap.add_argument("--out", default=str(REPO / "results" / "chip_smoke"),
                    help="run directory (emptied first) for the caches")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    dev = device_info()
    log(f"devices: {dev}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's device platform is "
              f"{dev['platform']!r}", file=sys.stderr)
        return 1
    if args.four_chips and dev["count"] < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, JAX sees "
              f"{dev['count']}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    out = pathlib.Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    checks = Checks()
    if args.four_chips:
        four_chip_phase(out, checks)
    else:
        one_chip_phases(out, checks)
    if checks.failed:
        print(f"chip_smoke: failed checks: {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
