"""`correct` on the CPU at a size a test run can hold: true for the
program as configured, false for a lower-precision control and for each
fault a training cell can have (bench/faults.py). The fixture's limits
are set the same way as the chip cells', from CPU readings: sound runs
read below 1e-6, the control and the faults 1e-4 and more; the
partition's chance ratio reads 0.23-0.27 for the program's partition and
0.99-1.04 for a stale one, against the limit 0.5. The CPU
ignores the matmul precision, so the control here is the program's own
bf16 path (`model.precision=bf16`); on the chip it is `high`."""
import pathlib

import pytest

from bench import run as bench_run
from bench import suite as S

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixture"
SEED = 3_000_000_017            # above 2**31, as the driver's seeds are


def run_small(workload, tmp_path, **kw):
    return bench_run.run_cell(workload, SEED, 0.3, False, root=FIXTURE,
                              search=[FIXTURE, S.BENCH_DIR],
                              cache_dir=tmp_path,
                              require_accelerator=False, **kw)


@pytest.mark.parametrize("workload", ["ppi_small.train",
                                      "amazon_small.train"])
def test_sound_run_is_correct(workload, tmp_path):
    r = run_small(workload, tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["train_nodes_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", ["ppi_small.train",
                                      "amazon_small.train"])
@pytest.mark.parametrize("variant", ["control", "unchanged", "half_batch",
                                     "stale_partition"])
def test_control_and_faults_are_not_correct(workload, variant, tmp_path):
    if variant == "control":
        r = run_small(workload, tmp_path,
                      overrides={"model.precision": "bf16"})
    else:
        r = run_small(workload, tmp_path, plant=variant)
    assert not r["correct"], r["checks"]
