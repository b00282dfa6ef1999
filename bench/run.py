"""Run one benchmark cell once, in this process, and print its result.

    python3 bench/run.py --workload ppi_sota.train --seed 7 --seconds 10 \
        --trace 0

The cell, its configuration, traffic mix, limits and metrics are found by
name from `BENCHMARK.json` (see `bench/suite.py`). With `--trace 0` the
result carries the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics, read from a profiler trace of the window. The last
line of standard output is one JSON object; the numbers that decided
`correct` are the last lines of standard error and the last key of that
object. Without an accelerator JAX can see, or with fewer chips than the
cell asks for, or outside a checkout that holds the program's `src/`, it
exits non-zero and prints no result.

Caches (JAX's compiled programs unless `JAX_COMPILATION_CACHE_DIR` says
otherwise, partitions, the last trace) live under `bench/.cache/`, at a
fixed path, so that only the first run of a cell in a checkout compiles
and partitions.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse                                         # noqa: E402
import dataclasses                                      # noqa: E402
import json                                             # noqa: E402
import os                                               # noqa: E402
import pathlib                                          # noqa: E402
import sys                                              # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
# run as a script, this directory heads sys.path and bench/trace.py would
# shadow the standard library's trace; the package is imported from ROOT
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != BENCH]
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoAccelerator(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Context:
    """What a traffic mix's driver is given."""
    workload: str
    config: Dict
    traffic: Dict
    chips: int
    seeds: Dict[str, int]
    seconds: float
    trace: bool
    devices: List[Any]
    cache_dir: pathlib.Path
    t_process_start: float
    plant: Optional[str] = None
    overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)


def derive_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for the weights, the dropout stream and
    the batch order, from any non-negative `--seed`."""
    import numpy as np
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    w, d, b = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"weights": int(w) & 0x7FFFFFFF, "dropout": int(d) & 0x7FFFFFFF,
            "batches": int(b) & 0x7FFFFFFF}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT,
             search: Sequence[pathlib.Path] = (BENCH,),
             cache_dir: pathlib.Path = CACHE,
             require_accelerator: bool = True, plant: Optional[str] = None,
             overrides: Optional[Dict[str, Any]] = None,
             t_process_start: Optional[float] = None,
             all_numbers: bool = False) -> Dict:
    """One run of one cell; returns the result object. Tests pass
    `require_accelerator=False` to drive the same path on the CPU, and
    with `bench/calibrate.py` a fault to `plant` or spec `overrides` (the
    key `matmul_precision` included) for the lower-precision control;
    `all_numbers` adds every number worked out, compared or not."""
    from bench import compare
    from bench.suite import Suite, peaks

    suite = Suite(root, search)
    cell = suite.workload(workload)
    limits = suite.limits(workload)
    readers = [(m, suite.reader(m["name"]))
               for m in suite.metrics(workload, trace)]
    chips = int(cell["chips"])

    import jax
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform == "cpu":
            raise NoAccelerator("JAX found no accelerator (platform cpu)")
        if len(devices) < chips:
            raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                                f"{len(devices)}")
    kind = devices[0].device_kind
    peak = peaks(kind) if require_accelerator else None
    ctx = Context(workload=workload, config=suite.config(cell["config"]),
                  traffic=suite.traffic(cell["traffic"]), chips=chips,
                  seeds=derive_seeds(seed), seconds=float(seconds),
                  trace=bool(trace), devices=devices[:chips],
                  cache_dir=pathlib.Path(cache_dir),
                  t_process_start=(T_PROCESS_START if t_process_start is None
                                   else t_process_start),
                  plant=plant, overrides=dict(overrides or {}))
    run = suite.driver(ctx.traffic["driver"])(ctx)
    run.peaks = peak
    correct, table = compare.judge(run.numbers, limits)

    metrics = {}
    for m, read in readers:
        value = read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    if all_numbers:
        result["numbers"] = dict(run.numbers)
    result["checks"] = table
    return result


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one; every program is cached, however fast it
    compiled, so that a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    enable_compile_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, row in result["checks"].items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
