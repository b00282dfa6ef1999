"""Operations and bytes of a graph attention training step, from its
shapes. A layer is (din, K heads, F' per head); a batch has n real nodes
and nnz attention edges (the induced edges plus a self-loop per node).

Required operations (`train_flops`, for `train_mfu_pct`): per layer the
forward needs X·W (2·n·din·K·F'), the two attention projections s and t
(2·2·n·K·F') and the aggregation Σ_j α_ij W h_j over the edges
(2·nnz·K·F'); training needs three times the forward. The logits and the
softmax (a few operations an edge) and padding do not count, as in
`bench.flops`.

The dense masked attention as the program runs it (`dense_attention`,
for `gat_attention_roofline_pct`): every head of every layer works on
the whole (cap, cap) block, padding included.
  operations  the aggregation α·(W h) and its two backward products
              (dα = dZ·(W h)ᵀ and d(W h) = αᵀ·dZ): 3 · 2·cap²·F' a head,
              times the bf16 passes a float32 matmul takes at the stated
              precision (6 at "highest", 3 at "high", 1 at "default"),
              since the chip's peak is bf16;
  bytes       what must cross HBM at least, per cap² entry of a head:
              forward the mask (1 byte), α written and read by the
              aggregation (4 + 4); backward dα written by its matmul and
              read by the softmax's backward (4 + 4), α read by the
              softmax's backward and by the d(W h) matmul (4 + 4) and
              the mask again (1): 26 bytes. Once a step, the mask is
              made from the dense Â: Â read (4 bytes an entry), the mask
              written (1).
The logits are recomputed from s and t inside the kernels that need
them, so they add no cap² traffic.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

PASSES = {"highest": 6, "high": 3, "default": 1}
ENTRY_BYTES_PER_HEAD = 26
MASK_BUILD_BYTES = 5

Shapes = Sequence[Tuple[int, int, int]]


def forward_flops(n: int, nnz: int, shapes: Shapes) -> int:
    total = 0
    for din, k, f in shapes:
        total += 2 * n * din * k * f + 4 * n * k * f + 2 * nnz * k * f
    return total


def train_flops(n: int, nnz: int, shapes: Shapes) -> int:
    return 3 * forward_flops(n, nnz, shapes)


def dense_attention(cap: int, shapes: Shapes,
                    precision: str = "highest") -> Dict[str, int]:
    """Operations (bf16 passes) and HBM bytes one training step of the
    dense masked attention needs on a (cap, cap) block."""
    entries = cap * cap
    ops = sum(3 * 2 * entries * f * k for _, k, f in shapes)
    heads = sum(k for _, k, _ in shapes)
    return {"flops": ops * PASSES[precision],
            "bytes": (ENTRY_BYTES_PER_HEAD * heads + MASK_BUILD_BYTES)
            * entries}
