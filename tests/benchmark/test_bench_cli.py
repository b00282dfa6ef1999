"""The command's refusals, and the seeds it derives."""
import os
import pathlib
import subprocess
import sys

import pytest

from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3_000_000_017


def test_no_accelerator_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "ppi_sota.train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in ("run.py", "__init__.py"):
        (tmp_path / "bench" / f).write_text(
            (ROOT / "bench" / f).read_text())
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ppi_sota.train", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_seeds_are_independent_and_accept_large_values():
    a = bench_run.derive_seeds(SEED)
    assert a == bench_run.derive_seeds(SEED)
    assert a != bench_run.derive_seeds(SEED + 1)
    assert all(0 <= v < 2 ** 31 for v in a.values())
    with pytest.raises(ValueError):
        bench_run.derive_seeds(-1)
