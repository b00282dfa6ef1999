"""Discovery by name, the shipped benchmark's completeness, and refusal
of a device the peaks table does not know."""
import json
import pathlib

import pytest

from bench import suite as S

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIXTURE = pathlib.Path(__file__).resolve().parent / "fixture"


def fixture_suite():
    return S.Suite(FIXTURE, [FIXTURE, S.BENCH_DIR])


def test_fixture_entries_are_found_by_name():
    s = fixture_suite()
    assert s.workload("ppi_small.train")["config"] == "ppi_small"
    assert s.config("ppi_small")["preset"] == "ppi_tiny"
    # the traffic mix and the metric readers come from bench/ itself,
    # the fixture's own metric from the fixture directory
    assert s.traffic("train")["driver"] == "train"
    assert callable(s.driver("train"))

    class Run:
        steps = 7
    assert s.reader("fixture_steps")(Run()) == 7


def test_metrics_follow_their_workloads_key():
    s = fixture_suite()
    traced = [m["name"] for m in s.metrics("ppi_small.train", trace=True)]
    assert traced == ["host_build_ms", "fixture_steps"]
    traced = [m["name"] for m in s.metrics("amazon_small.train",
                                           trace=True)]
    assert traced == ["host_build_ms"]
    e2e = [m["name"] for m in s.metrics("amazon_small.train", trace=False)]
    assert e2e == ["train_nodes_per_s", "setup_s"]


def test_unknown_names_are_refused():
    s = fixture_suite()
    with pytest.raises(KeyError, match="no workload named"):
        s.workload("nope.train")
    with pytest.raises(KeyError, match="metrics/nope.py"):
        s.reader("nope")


def test_unknown_device_kind_is_an_error():
    assert S.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in the peaks table"):
        S.peaks("cpu")


def test_every_shipped_cell_has_its_files():
    s = S.Suite(ROOT)
    spec = s.spec
    assert spec["command"] == ["python3", "bench/run.py"]
    for cell in spec["workloads"]:
        assert s.config(cell["config"])["preset"]
        assert s.traffic(cell["traffic"])["driver"]
        limits = s.limits(cell["name"])
        assert limits and all(v >= 0 for v in limits.values())
        assert limits["partition.invalid"] == 0
        assert {"loss_gap.1", "update_gap", "partition.chance_ratio"} <= set(limits)
        for trace in (False, True):
            assert s.metrics(cell["name"], trace)
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert callable(s.reader(m["name"]))
    names = [c["name"] for c in spec["configs"]]
    assert {c["config"] for c in spec["workloads"]} == set(names)
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg), c["name"]
