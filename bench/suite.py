"""Finds everything a cell needs by the names in `BENCHMARK.json`.

A cell names a configuration and a traffic mix. The configuration's file
is the one `BENCHMARK.json` gives it. The traffic mix is
`traffic/<traffic>.json`, the comparison limits of a cell are
`limits/<cell>.json`, every metric is read by `metrics/<metric>.py` (a
module with `read(run)`), and a traffic mix's `driver` is
`drivers/<driver>.py` (a module with `run(ctx)`), each looked up in the
search directories in order (`bench/` alone, unless a test puts its
fixtures first). Adding a cell, a configuration or a metric therefore
means adding files and entries, never editing one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Sequence

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class Suite:
    def __init__(self, root: pathlib.Path,
                 search: Sequence[pathlib.Path] = (BENCH_DIR,)):
        self.root = pathlib.Path(root)
        self.search = [pathlib.Path(d) for d in search]
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    @staticmethod
    def _named(entries: List[Dict], name: str, what: str) -> Dict:
        for e in entries:
            if e["name"] == name:
                return e
        known = sorted(e["name"] for e in entries)
        raise KeyError(f"no {what} named {name!r} (known: {known})")

    def workload(self, name: str) -> Dict:
        return self._named(self.spec["workloads"], name, "workload")

    def config(self, name: str) -> Dict:
        entry = self._named(self.spec["configs"], name, "configuration")
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> Dict:
        return self._load_json("traffic", name)

    def limits(self, workload: str) -> Dict[str, float]:
        return self._load_json("limits", workload)

    def _find(self, kind: str, filename: str) -> pathlib.Path:
        for d in self.search:
            if (d / kind / filename).is_file():
                return d / kind / filename
        raise KeyError(f"no {kind}/{filename} under {self.search}")

    def _load_json(self, kind: str, name: str) -> Dict:
        return json.loads(self._find(kind, f"{name}.json").read_text())

    def metrics(self, workload: str, trace: bool) -> List[Dict]:
        """The cell's end-to-end metrics, or with `trace` its per-layer
        ones: those that list the cell, or list no cells."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]

    def _module(self, kind: str, name: str):
        path = self._find(kind, f"{name}.py")
        mod_name = f"_bench_{kind}_" + name.replace(".", "_").replace(
            "-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str) -> Callable:
        return self._module("metrics", metric).read

    def driver(self, name: str) -> Callable:
        return self._module("drivers", name).run


def peaks(device_kind: str,
          path: pathlib.Path = BENCH_DIR / "peaks.json") -> Dict:
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table {path} (known: {sorted(table)})")
    return table[device_kind]
