"""GCN serving driver — checkpoint → per-cluster embedding cache →
latency-measured query loop.

    python -m repro.launch.serve_gcn --preset ppi_tiny --queries 1024
    python -m repro.launch.serve_gcn --preset ppi_tiny \
        --checkpoint-dir /tmp/ck --queries 256 --verify-parity \
        --bench-out BENCH_serve.json
    python -m repro.launch.serve_gcn --spec results/.../spec.json \
        --queries 4096 --top-k 3

Loads the spec exactly like run_experiment (--preset/--spec + --set),
restores params from the newest intact checkpoint
(CheckpointManager.restore_params — the same corrupt-newest walk-back
as training resume), precomputes the per-cluster embedding cache
(skipped on a warm cache: the directory is keyed on checkpoint step +
partition fingerprint), then answers `--queries` random lookups in
mixed-size batches drawn across the padding-bucket ladder and reports
per-bucket p50/p99 latency and overall QPS.

With no checkpoint on disk the driver TRAINS the preset first (the
spec's run section says how) so the acceptance one-liner above works
from a blank tree. `--verify-parity` cross-checks every served logit
against the one-shot dense full-graph forward (trainer.
full_graph_logits) at 1e-5 — the serving/training parity contract.
`--bench-out` writes the latency rows in the BENCH_*.json format that
benchmarks/check_regression.py gates (metric: p50_s, lower is better).

This is the GCN serving path; `launch/serve.py` is the unrelated LM
inference demo (prefill/decode KV-cache) kept from the language-model
PRs — see its docstring.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

from repro.launch.run_experiment import DEFAULT_RESULTS, load_spec


def _percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _train_if_needed(spec, ckpt_dir: str) -> None:
    """Cold start: no usable checkpoint under ckpt_dir → run the spec's
    training loop to produce one (the serve CLI stays a one-liner)."""
    from repro.runtime.checkpoint import CheckpointManager
    if CheckpointManager(ckpt_dir).latest_valid_step() is not None:
        return
    print(f"[serve_gcn] no checkpoint in {ckpt_dir} — training "
          f"{spec.name} for {spec.run.epochs} epoch(s) first",
          file=sys.stderr)
    from repro.core.experiment import build_experiment
    train_spec = spec.copy()
    train_spec.run.checkpoint_dir = ckpt_dir
    build_experiment(train_spec).fit()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve_gcn",
        description="serve GCN predictions from a training checkpoint "
                    "via the per-cluster embedding cache")
    ap.add_argument("--preset", help="registered preset name")
    ap.add_argument("--spec", help="path to a spec JSON file")
    ap.add_argument("--set", action="append", metavar="PATH=VALUE",
                    help="override a spec field (repeatable), e.g. "
                         "serve.max_batch=64")
    ap.add_argument("--queries", type=int, default=1024,
                    help="total node lookups to serve")
    ap.add_argument("--checkpoint-dir",
                    help="checkpoint directory (default: the spec's "
                         "run.checkpoint_dir, falling back to "
                         "<results-dir>/<name>/checkpoints); trains "
                         "first when empty")
    ap.add_argument("--results-dir", default=str(DEFAULT_RESULTS))
    ap.add_argument("--step", type=int, default=None,
                    help="serve this checkpoint step instead of the "
                         "newest intact one")
    ap.add_argument("--seed", type=int, default=0,
                    help="query-sampling RNG seed")
    ap.add_argument("--verify-parity", action="store_true",
                    help="check every served logit against the dense "
                         "full-graph forward at 1e-5")
    ap.add_argument("--bench-out", metavar="PATH",
                    help="also write the latency rows as BENCH json "
                         "(benchmarks/check_regression.py format)")
    args = ap.parse_args(argv)

    spec = load_spec(args)
    if spec.model.arch != "gcn":
        ap.error(f"model.arch={spec.model.arch!r}: serving runs the GCN "
                 f"propagation only; graph attention is trained, not "
                 f"served")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ckpt_dir = (args.checkpoint_dir or spec.run.checkpoint_dir
                or str(pathlib.Path(args.results_dir) / spec.name
                       / "checkpoints"))
    _train_if_needed(spec, ckpt_dir)

    from repro.serve import ServeEngine
    engine = ServeEngine.from_checkpoint(spec, ckpt_dir, step=args.step)
    n_nodes = engine.graph.num_nodes
    print(f"[serve_gcn] {spec.name}: step "
          f"{engine.cache.checkpoint_step}, {n_nodes} nodes, "
          f"{engine.num_parts} clusters, buckets {engine.buckets}, "
          f"cache {engine.cache.dir}", file=sys.stderr)
    t0 = time.perf_counter()
    warmed = engine.warm()
    precompute_s = time.perf_counter() - t0
    print(f"[serve_gcn] precompute: {warmed} cluster(s) in "
          f"{precompute_s:.3f}s "
          f"({'cold' if warmed else 'warm cache'})", file=sys.stderr)

    # mixed-size batches cycling through the bucket ladder, so every
    # compiled shape is exercised; first touch of each bucket compiles
    # and is excluded from latencies (standard jit warmup)
    rng = np.random.default_rng(args.seed)
    sizes, left, i = [], args.queries, 0
    while left > 0:
        b = engine.buckets[i % len(engine.buckets)]
        sizes.append(min(b, left))
        left -= sizes[-1]
        i += 1
    for b in engine.buckets:           # compile outside the timed loop
        engine.query(rng.integers(0, n_nodes, size=b))

    per_bucket: dict = {}
    results = []
    t0 = time.perf_counter()
    for sz in sizes:
        ids = rng.integers(0, n_nodes, size=sz)
        r = engine.query(ids)
        results.append(r)
        per_bucket.setdefault(r.bucket, []).append(r.latency_s)
    wall = time.perf_counter() - t0
    qps = args.queries / wall

    bench_rows = []
    for b in sorted(per_bucket):
        lats = per_bucket[b]
        p50, p99 = _percentile(lats, 50), _percentile(lats, 99)
        bench_rows.append({
            "name": f"serve/{spec.name}/bucket{b}",
            "p50_s": p50, "p50_ms": p50 * 1e3, "p99_ms": p99 * 1e3,
            "requests": len(lats)})
        print(f"[serve_gcn] bucket {b:>5}: {len(lats):>5} req  "
              f"p50 {p50 * 1e3:8.3f} ms  p99 {p99 * 1e3:8.3f} ms",
              file=sys.stderr)
    print(f"[serve_gcn] served {args.queries} lookups in {wall:.3f}s "
          f"= {qps:,.0f} QPS", file=sys.stderr)

    if args.verify_parity:
        from repro.core.trainer import full_graph_logits
        ref = np.asarray(full_graph_logits(
            engine.params, engine.graph, engine.cfg, norm=engine.norm,
            diag_lambda=engine.diag_lambda))
        worst = max(float(np.abs(r.logits - ref[r.node_ids]).max())
                    for r in results)
        status = "OK" if worst <= 1e-5 else "FAIL"
        print(f"[serve_gcn] parity vs dense full-graph forward: "
              f"max |Δ| = {worst:.2e} [{status}]", file=sys.stderr)
        if worst > 1e-5:
            return 1

    if args.bench_out:
        # the same {"rows": [{"name": ...}]} shape bench_spmm emits, so
        # benchmarks/check_regression.py gates serve latency unchanged
        # (bucket rows compare on p50_s, the precompute row on seconds)
        bench_rows.append({"name": f"serve/{spec.name}/precompute",
                           "seconds": precompute_s,
                           "warmed_clusters": warmed})
        record = {"bench": "serve", "preset": spec.name,
                  "checkpoint_step": engine.cache.checkpoint_step,
                  "queries": args.queries, "qps": qps,
                  "buckets": list(engine.buckets), "rows": bench_rows}
        pathlib.Path(args.bench_out).write_text(
            json.dumps(record, indent=2, sort_keys=True) + "\n")
        print(f"[serve_gcn] wrote {args.bench_out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
