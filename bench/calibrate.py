"""Readings for setting a cell's comparison limits: the numbers that
decide `correct`, for many seeds in one process, for sound runs of the
program, for the lower-precision control, and for planted faults.

    python3 bench/calibrate.py --workload ppi_sota.train \
        --seeds 11,12,13 --variant sound --variant control \
        --variant half_batch --seconds 1

A variant is `sound` (the configuration as stated), `control` (the
configuration's float32 at "highest" computed one step lower, at matmul
precision `high`: three bf16 passes), or a fault of `bench/faults.py`. Each reading is one JSON line on standard output. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run  # noqa: F401  (sets sys.path like bench/run.py)


def control_overrides(config: dict) -> dict:
    stated = config.get("matmul_precision", "default")
    if stated != "highest":
        raise ValueError(f"no control for matmul precision {stated!r}: "
                         f"the cells state float32 at 'highest'")
    return {"matmul_precision": "high"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--variant", action="append", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for p in (str(bench_run.ROOT), str(bench_run.ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.suite import Suite
    bench_run.enable_compile_cache()
    suite = Suite(bench_run.ROOT)
    config = suite.config(suite.workload(args.workload)["config"])
    for variant in args.variant:
        for seed in (int(s) for s in args.seeds.split(",")):
            plant, overrides = None, {}
            if variant == "control":
                overrides.update(control_overrides(config))
            elif variant != "sound":
                plant = variant
            t = time.perf_counter()
            r = bench_run.run_cell(args.workload, seed, args.seconds, False,
                                   plant=plant, overrides=overrides,
                                   t_process_start=t, all_numbers=True)
            print(json.dumps({
                "workload": args.workload, "variant": variant,
                "precision": config.get("matmul_precision", "default"),
                "seed": seed, "correct": r["correct"],
                "numbers": r["numbers"],
                "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
