#!/usr/bin/env python
"""Generate the ExperimentSpec reference and preset catalog from code.

docs/experiment-spec.md and docs/presets.md are NOT written by hand:
this script renders them from the live `repro.core.experiment`
dataclasses (field names, types, defaults, and the per-field ``doc``
metadata attached next to each default) and from the preset registry —
so the reference cannot drift from what the code accepts. CI's
docs-freshness job (and tests/test_docs.py) rerun it with ``--check``
and fail on any difference.

Usage:
    python docs/gen_spec_reference.py            # (re)write both files
    python docs/gen_spec_reference.py --check    # exit 2 on drift
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.core import experiment as E  # noqa: E402

GENERATED_NOTE = ("<!-- GENERATED by docs/gen_spec_reference.py — do "
                  "not edit by hand; run `python "
                  "docs/gen_spec_reference.py` to regenerate. -->")


def _default_repr(f: dataclasses.Field) -> str:
    if f.default is dataclasses.MISSING:
        return "—"
    return "`" + ("None" if f.default is None else repr(f.default)) + "`"


def _doc(f: dataclasses.Field) -> str:
    return f.metadata.get("doc", "")


def _section_table(cls) -> str:
    rows = ["| Field | Type | Default | Description |",
            "|---|---|---|---|"]
    for f in dataclasses.fields(cls):
        rows.append(f"| `{f.name}` | `{f.type}` | {_default_repr(f)} "
                    f"| {_doc(f)} |")
    return "\n".join(rows)


def spec_reference_md() -> str:
    parts = [
        "# ExperimentSpec reference",
        "",
        GENERATED_NOTE,
        "",
        "One JSON-round-trippable `ExperimentSpec` describes everything "
        "from dataset preset to resumable training run; "
        "`repro.core.experiment.build_experiment(spec)` materializes "
        "graph → partition/sampler → batcher → model config → optimizer "
        "→ backend → hooked Engine from it. Everything is seeded by the "
        "spec, so **one spec = one bitwise training trajectory**.",
        "",
        "## Using specs",
        "",
        "```bash",
        "# start from a preset, layer overrides, print the resolved spec",
        "python -m repro.launch.run_experiment --preset ppi_tiny \\",
        "    --set batch.sampler=saint_node --set run.epochs=2 "
        "--print-spec",
        "# train; the resolved spec.json + metrics.json land under "
        "results/experiments/<name>/",
        "python -m repro.launch.run_experiment --preset ppi_tiny \\",
        "    --set run.checkpoint_dir=/tmp/ck",
        "# continue a killed run on the exact same trajectory",
        "python -m repro.launch.run_experiment "
        "--spec results/experiments/ppi_tiny/spec.json --resume",
        "```",
        "",
        "### `--set` override semantics",
        "",
        "`--set section.field=value` (repeatable) sets one dotted-path "
        "field on the spec. Values are parsed as JSON literals — `2`, "
        "`0.5`, `true`, `null`, `[1,2]` — with a plain-string fallback, "
        "so `--set batch.k_slots=auto` and `--set run.epochs=2` both do "
        "the obvious thing. Unknown paths raise (`spec has no field "
        "...`); the same parsing is exposed programmatically as "
        "`apply_overrides(spec, {\"run.epochs\": 2})`.",
        "",
        "### JSON round trip",
        "",
        "`spec.to_json()` ↔ `ExperimentSpec.from_json(text)` round-trip "
        "exactly (unknown sections or fields in the JSON raise, so "
        "stale artifacts fail loudly). The CLI writes the resolved spec "
        "next to every run's metrics as the reproducibility artifact — "
        "feeding it back via `--spec` rebuilds the identical run.",
        "",
        "## Top-level fields",
        "",
        "| Field | Type | Default | Description |",
        "|---|---|---|---|",
        "| `name` | `str` | `'experiment'` | run name; the CLI writes "
        "artifacts under `results/experiments/<name>/` |",
        "",
    ]
    for key, cls in E._SECTIONS.items():
        doc = " ".join((cls.__doc__ or "").split())
        parts += [f"## `{key}` — {cls.__name__}", "", doc, "",
                  _section_table(cls), ""]
    parts += [
        "## Validation",
        "",
        "`validate(spec)` (run by `build_experiment` and the CLI) "
        "checks the cheap structural constraints before any expensive "
        "materialization; every error names the offending field. "
        "Current enumerations: `batch.sampler` ∈ "
        f"`{list(E._SAMPLERS)}`, `batch.norm` ∈ `{list(E._NORMS)}`, "
        f"`partition.method` ∈ `{list(E._PARTITION_METHODS)}`, "
        f"`optim.name` ∈ `{list(E._OPTIMIZERS)}`, "
        f"`execution.compression` ∈ `{list(E._COMPRESSIONS)}`, "
        "`run.eval_split` ∈ `['auto', 'train', 'val', 'test']`.",
        "",
    ]
    return "\n".join(parts)


def _fmt_scale(x: float) -> str:
    return f"{x:g}"


def _preset_row(name: str) -> str:
    s = E.preset(name)
    d, p, b, m, r = s.data, s.partition, s.batch, s.model, s.run
    if b.sampler == "cluster":
        sampler = f"cluster, p={p.num_parts}, q={b.clusters_per_batch}"
    else:
        sampler = (f"{b.sampler}, budget="
                   f"{b.budget if b.budget is not None else 'auto'}")
    model = f"{m.num_layers}×{m.hidden_dim}"
    if m.arch == "gat":
        model = f"GAT {m.num_layers}×{m.hidden_dim}, heads {m.heads}"
    norm = b.norm + (f" (λ={b.diag_lambda:g})" if b.diag_lambda else "")
    loss = ("BCE" if m.multilabel else
            "CE" if m.multilabel is not None else "inferred")
    return (f"| `{name}` | {d.name} ×{_fmt_scale(d.scale)} | {sampler} "
            f"| {model} | {norm} | {loss} | {r.epochs} | "
            f"{r.eval_split} |")


def presets_md() -> str:
    parts = [
        "# Preset catalog",
        "",
        GENERATED_NOTE,
        "",
        "Every preset is a factory returning a fresh, fully-resolved "
        "`ExperimentSpec` (see the [spec reference](experiment-spec.md))"
        " — run one with `python -m repro.launch.run_experiment "
        "--preset <name>`, list them with `--list-presets`, and layer "
        "`--set` overrides on top.",
        "",
        "The paper-named presets carry the **Table 4** hyperparameters "
        "of Cluster-GCN (Chiang et al., KDD 2019) — partitions *p*, "
        "clusters-per-batch *q*, hidden width, per-dataset loss mode — "
        "by default on this repo's statistics-matched synthetic "
        "stand-ins for the real datasets, so every preset runs "
        "offline (see `repro.graph.generators`). The `*_real` presets "
        "point the SAME recipes at the actual published datasets — "
        "`ppi_real`, `reddit_real`, and `amazon2m_real` (ogbn-products, "
        "the modern distribution of the paper's Amazon2M graph) — "
        "downloaded once into a checksummed local cache; see "
        "[datasets.md](datasets.md). `ppi_sota` is the §4.3 "
        "state-of-the-art deep recipe (5×2048, Eq. 11 diagonal "
        "enhancement λ=1). `*_tiny` variants are CPU-sized smoke "
        "versions of the same recipe shapes (`ppi_real_tiny` is the "
        "real-data smoke lane CI trains nightly); `*_saint` variants "
        "swap the cluster batcher for the GraphSAINT-style samplers "
        "(`repro.core.samplers`) — the partition-free end of the "
        "subgraph-sampling family.",
        "",
        "| Preset | Dataset (scale) | Sampler | Layers×Hidden | Norm | "
        "Loss | Epochs | Eval split |",
        "|---|---|---|---|---|---|---|---|",
    ]
    parts += [_preset_row(name) for name in E.list_presets()]
    parts += [
        "",
        "Paper Table 4 ↔ preset mapping: `ppi` (p=50, q=1, 512 hidden, "
        "multilabel BCE), `reddit` (p=1500, q=20, 128 hidden, softmax "
        "CE), `amazon2m` (p=15000, q=10, 400 hidden, CE; its generator "
        "has no validation split, so eval is explicitly the test "
        "split; `amazon2m_real` has OGB's official splits and "
        "evaluates on val like the others). Loss mode `inferred` "
        "means the preset leaves "
        "`model.multilabel=None` and `build_gcn_config` reads it off "
        "the label array's rank.",
        "",
    ]
    return "\n".join(parts)


FILES = {
    "experiment-spec.md": spec_reference_md,
    "presets.md": presets_md,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="verify the committed files match the "
                         "generated output; exit 2 on drift")
    args = ap.parse_args(argv)
    stale = []
    for fname, render in FILES.items():
        path = ROOT / "docs" / fname
        text = render()
        if args.check:
            on_disk = path.read_text() if path.exists() else None
            if on_disk != text:
                stale.append(fname)
        else:
            path.write_text(text)
            print(f"wrote {path.relative_to(ROOT)}")
    if stale:
        print(f"STALE: docs/{', docs/'.join(stale)} — rerun "
              f"`python docs/gen_spec_reference.py` and commit",
              file=sys.stderr)
        return 2
    if args.check:
        print(f"docs fresh: {', '.join(FILES)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
