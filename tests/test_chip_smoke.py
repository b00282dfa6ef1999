"""CPU rehearsal of chip_smoke.py: its phases on the ppi_tiny preset with
the Pallas kernels in interpret mode, the four-device data-parallel
phase on virtual CPU devices, and its refusal to report a result
without a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np

REPO = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_rehearse_on_cpu(tmp_path):
    """Training variants (a)-(c), the kernel-vs-ref checks, the loss
    comparison and serve parity, all passing at ppi_tiny size."""
    cs = _load()
    checks = cs.Checks()
    runs = cs.one_chip_phases(tmp_path, checks, preset_name="ppi_tiny",
                              mode="interpret", platform="cpu")
    assert checks.failed == []
    assert set(runs) == {"a_dense", "b_sparse", "c_fused"}
    steps = {r["steps"] for r in runs.values()}
    assert len(steps) == 1 and steps.pop() > 0
    for r in runs.values():
        assert np.isfinite(r["losses"]).all() and r["compile_s"] > 0
    # interpret mode lowers the kernels to plain HLO: no TPU custom calls
    assert all(r["custom_calls"] == 0 for r in runs.values())
    # the caches stay inside the run directory
    assert (tmp_path / "partitions").is_dir()
    assert any((tmp_path / "serving").iterdir())


def test_four_chip_phase_on_virtual_devices(run_distributed, tmp_path):
    out = run_distributed(f"""
import importlib.util, pathlib
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)
checks = cs.Checks()
cs.four_chip_phase(pathlib.Path({str(tmp_path)!r}), checks,
                   preset_name="ppi_tiny", mode="interpret")
assert checks.failed == [], checks.failed
print("DP_PHASE_OK")
""", devices=4)
    assert "DP_PHASE_OK" in out
    # one stacked batch per device
    assert "on 4 devices: 0:(1," in out and "3:(1," in out
    assert "check dp_vs_one_chip" in out


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_without_tpu(tmp_path):
    out = _run_script(tmp_path, SCRIPT)
    assert out.returncode != 0
    assert "platform is 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script has nothing to run."""
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run_script(tmp_path, alone)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
