"""The program's spans, on the profiler's clock.

A span is a `jax.profiler.TraceAnnotation` named "repro.<name>": the
profiler writes it into its host plane, on the same clock as the
device's operations, so a trace of `Engine.fit` (docs/tracing.md) shows
what the host was doing while the device ran or idled. Counts ride on
the span as keyword stats, taken from values already at hand. The
installed jaxlib encodes them only while a trace is active (its TraceMe
takes them through a callback that it runs only then), so outside a
trace a span costs one TraceMe construction and no guard is needed.

Host spans (parent: the span that encloses it on the same thread):

| Span | Parent | Stats | Read by |
| --- | --- | --- | --- |
| `batch.build` | `engine.wait`, or `prefetch.produce` | | `idle_gaps_by_span` |
| `batch.slice` | `batch.build` | | `build_slice_ms` |
| `batch.adjacency` | `batch.build` | | `build_adjacency_ms` |
| `batch.gather` | `batch.build` | | `build_gather_ms` |
| `engine.wait` | | | `idle_gaps_by_span` |
| `engine.step` | | | `idle_gaps_by_span` |
| `engine.hooks` | | | `idle_gaps_by_span` |
| `engine.epoch_end` | | `steps`, `syncs` | `epoch_end_ms_per_step`, `host_syncs_per_step` |
| `prefetch.produce` | | | docs/tracing.md (producer time under prefetch) |
| `prefetch.transfer` | `prefetch.produce` | | docs/tracing.md (host-to-device copy under prefetch) |

Every host span also counts as attributed time for `idle_unattributed_pct`.
`batch.slice`, `batch.adjacency` and `batch.gather` come from
`core.batching.subgraph_payload`, so every sampler emits them.

Device scopes (`jax.named_scope`: op metadata only, no change to the
numbers or the fusion; the backward ops inherit the scope of their
forward). XLA fuses across scopes, and the profiler names a fused
kernel by one op of it (on TPU its matmul, where it holds one), so a
time read by scope is the time of the kernels led by that scope's ops,
with whatever XLA fused into them:

| Scope | Where | Read by |
| --- | --- | --- |
| `gcn.xw` | X·W plus bias (`core/gcn.py`) | `xw_fusions_ms` |
| `gcn.xw_aggregate` | the fused Â·(XW+b) call | `xw_fusions_ms` |
| `gcn.aggregate` | Â·(XW) | `aggregate_fusions_ms` |
| `gcn.dropout`, `gcn.activation`, `gcn.loss` | the rest of the model | docs/tracing.md (the scopes each kernel holds) |
| `optim.update` | optimizer update and apply (`core/engine.py`, `dist/steps.py`) | docs/tracing.md (the scopes each kernel holds) |
| `dp.allreduce` | the data-parallel gradient all-reduce (`dist/steps.py`) | docs/tracing.md (four-chip step) |
| `gat.xw` | graph attention's W·h and its s, t projections (`core/gat.py`) | — |
| `gat.attention` | graph attention's mask, logits, LeakyReLU and softmax | `gat_attention_ms`, `gat_attention_roofline_pct` |
| `gat.aggregate` | graph attention's α·(W·h), bias and head concat or mean | `gat_aggregate_ms`, `gat_attention_roofline_pct` |

JAX's persistent compilation cache leaves op metadata out of its key
unless told otherwise (`repro.launch.compile_cache`): a cached
executable keeps the op names, and so the scopes, of the program that
compiled it.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """A host span named `PREFIX + name`, carrying `counts` as stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)

