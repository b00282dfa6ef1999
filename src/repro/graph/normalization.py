"""Adjacency normalization variants from the paper.

Every variant is a per-row (or per-row-and-column) scaling plus a
diagonal, so each comes in three forms: `normalize_dense` on a dense
(b, b) block, `normalize_csr` on a CSR matrix (full-graph baselines and
the block-ELL batch path), and `normalized_dense_block`, which
normalizes a batch's CSR non-zeros in O(nnz) and scatters them into the
zero-padded (cap, cap) block the dense training path ships to the device
(that is where Cluster-GCN does its compute).

  eq1   : A' = D^{-1} A            (mean aggregator used in §4.1)
  sym   : D^{-1/2}(A+I)D^{-1/2}    (Kipf & Welling; for reference)
  eq10  : Ã = (D+I)^{-1}(A+I)      (paper Eq. 10)
  eq9   : A' + I                   (paper Eq. 9 — unnormalized identity add)
  eq11  : Ã + λ·diag(Ã)            (paper Eq. 11 — diagonal enhancement)

Batches built from q>1 clusters re-add between-cluster links and must be
RE-normalized on the combined subgraph (paper §6.2) — normalization is
therefore applied per batch, on the batch adjacency.
"""
from __future__ import annotations

import numpy as np

_EPS = 1e-9


def normalize_dense(adj: np.ndarray, method: str = "eq10",
                    diag_lambda: float = 0.0) -> np.ndarray:
    """Normalize a dense (b, b) adjacency block. numpy in, numpy out."""
    a = np.asarray(adj, dtype=np.float32)
    n = a.shape[0]
    eye = np.eye(n, dtype=np.float32)
    if method == "eq1":
        deg = a.sum(1)
        out = a / np.maximum(deg, _EPS)[:, None]
    elif method == "sym":
        ai = a + eye
        d = ai.sum(1)
        dinv = 1.0 / np.sqrt(np.maximum(d, _EPS))
        out = dinv[:, None] * ai * dinv[None, :]
    elif method in ("eq10", "eq9", "eq11"):
        # Ã = (D+I)^{-1}(A+I); D from A (degree), +I regularizer
        deg = a.sum(1)
        ai = a + eye
        out = ai / (deg + 1.0)[:, None]
        if method == "eq9":
            out = out + eye
        elif method == "eq11":
            out = out + diag_lambda * np.diag(np.diag(out))
    else:
        raise ValueError(f"unknown normalization {method!r}")
    return out.astype(np.float32)


def normalized_dense_block(indptr, indices, data, cap: int,
                           method: str = "eq10",
                           diag_lambda: float = 0.0) -> np.ndarray:
    """Normalize a (b, b) CSR batch adjacency and return it densified
    into a fresh, zero-padded (cap, cap) float32 block, b = len(indptr)-1.

    The same block as densifying the CSR and calling `normalize_dense` on
    its (b, b) corner, bit for bit when the weights are integer-valued
    (their float32 row sums are exact), at O(nnz) plus one memset of the
    block instead of several passes over b^2. Each entry repeats
    `normalize_dense`'s float32 operations in its order; the diagonal of
    the +I methods is (self-loop weight + 1), one rounding, like `a + eye`.
    The (row, col) slots must be unique: `CSRGraph.from_edges` and
    `append_graph` dedupe them, `CSRGraph.subgraph` keeps them unique, and
    the block-ELL batch path assumes the same.
    """
    if method not in ("eq1", "sym", "eq10", "eq9", "eq11"):
        raise ValueError(f"unknown normalization {method!r}")
    b = len(indptr) - 1
    out = np.zeros((cap, cap), np.float32)
    out_flat = out.reshape(-1)
    row = np.repeat(np.arange(b, dtype=np.intp), np.diff(indptr))
    col = np.asarray(indices, dtype=np.intp)
    val = np.asarray(data, dtype=np.float32)
    flat = row * cap + col
    deg = np.bincount(row, weights=val, minlength=b).astype(np.float32)
    one = np.float32(1.0)
    if method == "eq1":
        out_flat[flat] = val / np.maximum(deg, np.float32(_EPS))[row]
        return out
    diag_a = np.ones(b, np.float32)          # diagonal of A + I
    loop = row == col
    diag_a[row[loop]] = val[loop] + one
    if method == "sym":
        dinv = one / np.sqrt(np.maximum(deg + one, np.float32(_EPS)))
        out_flat[flat] = dinv[row] * val * dinv[col]
        diag = dinv * diag_a * dinv
    else:
        scale = deg + one
        out_flat[flat] = val / scale[row]
        diag = diag_a / scale
        if method == "eq9":
            diag = diag + one
        elif method == "eq11":
            diag = diag + np.float32(diag_lambda) * diag
    # written after the edges, so it replaces any self-loop slot
    out_flat[np.arange(b) * (cap + 1)] = diag
    return out


def normalize_csr(indptr, indices, data, method: str = "eq10",
                  diag_lambda: float = 0.0):
    """CSR normalization for full-graph baselines. Returns new
    (indptr, indices, data) WITH self loops appended where the method
    requires them."""
    import scipy.sparse as sp
    n = len(indptr) - 1
    a = sp.csr_matrix((data, indices, indptr), shape=(n, n))
    if method == "eq1":
        deg = np.asarray(a.sum(1)).ravel()
        dinv = sp.diags(1.0 / np.maximum(deg, _EPS))
        out = dinv @ a
    elif method == "sym":
        ai = a + sp.eye(n, format="csr")
        deg = np.asarray(ai.sum(1)).ravel()
        dh = sp.diags(1.0 / np.sqrt(np.maximum(deg, _EPS)))
        out = dh @ ai @ dh
    elif method in ("eq10", "eq9", "eq11"):
        deg = np.asarray(a.sum(1)).ravel()
        ai = a + sp.eye(n, format="csr")
        dinv = sp.diags(1.0 / (deg + 1.0))
        out = dinv @ ai
        if method == "eq9":
            out = out + sp.eye(n, format="csr")
        elif method == "eq11":
            out = out + diag_lambda * sp.diags(out.diagonal())
    else:
        raise ValueError(f"unknown normalization {method!r}")
    out = out.tocsr().astype(np.float32)
    out.sort_indices()
    return out.indptr.astype(np.int64), out.indices.astype(np.int32), out.data
