"""The graph attention cell's harness on the CPU: its driver end to end
(`correct` true for the program as configured, false for the bf16
control and each fault), on one device and data-parallel on two; its
operation and byte counts against hand counts; and its three readers on
a synthesized program trace. The fixture's limits are the chip cell's
kind, set from CPU readings: sound runs read below 1e-6 (update_gap
7e-6), the control and the faults 2e-5 and more (bench/faults.py)."""
import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import gat_flops, gat_trace, reference
from bench import program_trace as P
from bench import run as bench_run
from bench import suite as S

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIXTURE = pathlib.Path(__file__).resolve().parent / "fixture_gat"
SEED = 3_000_000_017            # above 2**31: --seed may be that large
READERS = ["gat_attention_ms", "gat_aggregate_ms",
           "gat_attention_roofline_pct"]


def run_small(workload, tmp_path, **kw):
    return bench_run.run_cell(workload, SEED, 0.3, False, root=FIXTURE,
                              search=[FIXTURE, S.BENCH_DIR],
                              cache_dir=tmp_path,
                              require_accelerator=False, **kw)


def test_sound_gat_run_is_correct(tmp_path):
    r = run_small("gat_small.train", tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["train_nodes_per_s"]["value"] > 0


@pytest.mark.parametrize("variant", ["control", "unchanged", "half_batch",
                                     "stale_partition"])
def test_gat_control_and_faults_are_not_correct(variant, tmp_path):
    if variant == "control":
        r = run_small("gat_small.train", tmp_path,
                      overrides={"model.precision": "bf16"})
    else:
        r = run_small("gat_small.train", tmp_path, plant=variant)
    assert not r["correct"], r["checks"]


def test_gat_data_parallel_run_is_correct(tmp_path):
    code = f"""
import json, pathlib, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
from bench import run as R, suite as S
fx = pathlib.Path({str(FIXTURE)!r})
r = R.run_cell("gat_small.train.dp2", {SEED}, 0.3, False, root=fx,
               search=[fx, S.BENCH_DIR], cache_dir=pathlib.Path({str(tmp_path)!r}),
               require_accelerator=False)
print("RESULT", json.dumps(r))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.split("RESULT", 1)[1])
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 2


def _path3():
    # path 0-1-2: 4 edge slots, plus a self-loop on each node
    indptr = np.array([0, 1, 3, 4])
    indices = np.array([1, 0, 2, 1])
    return reference.Graph.from_arrays(indptr, indices, np.ones(4),
                                       np.zeros((3, 3)), np.zeros((3, 2)))


def test_gat_flops_hand_count():
    nnz = reference.block_nnz(_path3(), np.arange(3))
    assert nnz == 7
    shapes = [(3, 2, 4), (8, 1, 2)]
    # layer 1: X·W 2*3*3*8 = 144, s and t 4*3*2*4 = 96, α·Wh 2*7*8 = 112
    # layer 2: 2*3*8*2 = 96, 4*3*1*2 = 24, 2*7*2 = 28
    assert gat_flops.forward_flops(3, nnz, shapes) == 352 + 148
    assert gat_flops.train_flops(3, nnz, shapes) == 3 * 500


def test_dense_attention_hand_count():
    shapes = [(3, 2, 4), (8, 1, 2)]
    # cap 4: 16 entries; 3 products of 2*16*F' a head: 96*(2*4 + 1*2)
    got = gat_flops.dense_attention(4, shapes, "highest")
    assert got == {"flops": 960 * 6, "bytes": (26 * 3 + 5) * 16}
    assert gat_flops.dense_attention(4, shapes, "default")["flops"] == 960


def test_gat_scope_is_the_innermost_scope_of_the_program():
    assert gat_trace.scope_of(
        "jit(step)/transpose(jvp(gat.aggregate))/dot_general") \
        == "gat.aggregate"
    assert gat_trace.scope_of("jit(step)/jvp(gat.attention)/exp") \
        == "gat.attention"
    assert gat_trace.scope_of("", "jit(step)/gat.xw/dot_general") \
        == "gat.xw"
    # innermost is another scope of the program, or none at all
    assert gat_trace.scope_of("jit(step)/jvp(gcn.activation)/elu") is None
    assert gat_trace.scope_of("jit(step)/gat.xw/gcn.loss/mul") is None
    assert gat_trace.scope_of("jit(step)/jit(_threefry_split)/add") is None
    assert gat_trace.scope_of("/src/test_gat.py:12") is None


def _run(reduced, **kw):
    run = types.SimpleNamespace(
        chips=1, steps=4, trace=types.SimpleNamespace(window_s=10.0),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        gat_attention={"flops": 6e9, "bytes": 1e9})
    vars(run).update(kw)
    return run


def test_readers_on_a_synthesized_trace(monkeypatch):
    ops = {0: [(0.0, 1.0, "gat.attention"), (1.0, 3.0, "gat.aggregate"),
               (3.0, 3.5, "gat.xw"), (4.0, 5.0, None),
               (5.0, 6.0, "gat.attention"), (6.0, 8.0, "gat.aggregate")]}
    program = P.Program(window=(0.0, 10.0), spans=[], ops=ops)
    reduced = P.reduce_program(program, device_ids=[0], steps=4)
    monkeypatch.setattr(gat_trace, "read", lambda run: reduced)
    suite = S.Suite(ROOT)
    got = {n: suite.reader(n)(_run(reduced)) for n in READERS}
    assert got["gat_attention_ms"] == pytest.approx(1e3 * 2.0 / 4)
    assert got["gat_aggregate_ms"] == pytest.approx(1e3 * 4.0 / 4)
    # least time max(6e9 / 1e12, 1e9 / 1e11) = 10 ms a step, over 1.5 s
    assert got["gat_attention_roofline_pct"] == pytest.approx(
        100 * 0.010 / 1.5)
    # no peaks (a CPU run), or no count: no share
    assert suite.reader(READERS[2])(_run(reduced, peaks=None)) is None
    assert suite.reader(READERS[2])(_run(reduced,
                                         gat_attention=None)) is None


def test_readers_on_a_trace_without_gat_scopes_read_nothing(monkeypatch):
    program = P.Program(window=(0.0, 10.0), spans=[],
                        ops={0: [(0.0, 1.0, None)]})
    reduced = P.reduce_program(program, device_ids=[0], steps=4)
    monkeypatch.setattr(gat_trace, "read", lambda run: reduced)
    suite = S.Suite(ROOT)
    assert all(suite.reader(n)(_run(reduced)) is None for n in READERS)
    monkeypatch.undo()
    untraced = types.SimpleNamespace(chips=1, steps=4, trace=None)
    assert gat_trace.read(untraced) is None
    assert all(suite.reader(n)(_run(None, trace=None)) is None
               for n in READERS)


def test_new_cells_are_listed_where_they_are_read():
    spec = S.Suite(ROOT).spec
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert per_layer[name]["workloads"] == ["ppi_gat.train"]
        assert per_layer[name]["moves"] == "train_nodes_per_s"
    for name in ("graph_setup_s", "compile_s", "window_compile_s",
                 "host_build_ms", "h2d_mb_per_step", "device_idle_pct",
                 "train_mfu_pct", "peak_hbm_mb"):
        assert per_layer[name]["workloads"][2:] == ["ppi_gat.train"]
    cells = {c["name"]: c for c in spec["workloads"]}
    assert cells["ppi_gat.train"]["chips"] == 1
    assert S.Suite(ROOT).traffic(cells["ppi_gat.train"]["traffic"])[
        "driver"] == "train_gat"
