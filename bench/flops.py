"""Operations a Cluster-GCN training step requires, from its shapes.

Per layer of width din -> dout over a batch of n real nodes whose
normalised adjacency has nnz non-zeros, the forward pass needs the
feature transform X·W (2·n·din·dout) and the aggregation Â·(XW)
(2·nnz·dout). Training needs three times the forward (the backward pass
computes a gradient for the input and one for the weights). Padding rows,
the dense cap×cap product that the dense path runs in place of the sparse
one, and recomputation do not count.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def forward_flops(n: int, nnz: int, dims: Sequence[Tuple[int, int]],
                  precompute_ax: bool = False) -> int:
    total = 0
    for i, (din, dout) in enumerate(dims):
        total += 2 * n * din * dout
        if not (i == 0 and precompute_ax):
            total += 2 * nnz * dout
    return total


def train_flops(n: int, nnz: int, dims: Sequence[Tuple[int, int]],
                precompute_ax: bool = False) -> int:
    return 3 * forward_flops(n, nnz, dims, precompute_ax)
