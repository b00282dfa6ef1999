"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------------------
# block-ELL SpMM
# ----------------------------------------------------------------------
def spmm_block_ell_ref(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                       x: jnp.ndarray) -> jnp.ndarray:
    """y[i*B:(i+1)*B] = Σ_k blocks[i,k] @ x[block_cols[i,k]*B : +B].

    The K slot sum is folded into the contraction dim — per row-block one
    (B, K·B) @ (K·B, F) matmul instead of K tiny (B,B)@(B,F) products —
    so the XLA CPU/GPU path runs at near-dense matmul efficiency while
    doing only the block-sparse FLOPs (the lever that puts the fwd+bwd
    sparse path above 1× dense in BENCH_spmm.json)."""
    nrb, K, B, _ = blocks.shape
    F = x.shape[1]
    # precision contract (repro.core.precision): operands in x's dtype
    # (fp32 x keeps the exact pre-policy fp32 casts), accumulator fp32
    # via preferred_element_type, result cast back to x's dtype
    op_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.float32
    xb = x.reshape(-1, B, F)                      # (ncb, B, F)
    gathered = xb[block_cols].reshape(nrb, K * B, F)
    # jnp.transpose, not the method: outside jit a custom-VJP residual
    # arrives as a host array type that has no .transpose
    a = jnp.transpose(jnp.asarray(blocks), (0, 2, 1, 3)).reshape(nrb, B,
                                                                 K * B)
    y = jax.lax.dot_general(a.astype(op_dtype),
                            gathered.astype(op_dtype),
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    return y.reshape(nrb * B, F).astype(x.dtype)


def spmm_fused_ref(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                   x: jnp.ndarray, w: jnp.ndarray,
                   b: jnp.ndarray | None = None) -> jnp.ndarray:
    """Oracle for the fused y = Â (X W + 1 bᵀ) kernel.

    Same math contract as the fused Pallas kernel AND the unfused
    gcn_forward layer: XW in the operand dtype with an fp32 accumulator,
    fp32 bias add, cast back to x's dtype, then the block-ELL
    aggregation. Deliberately ignores `row_k` (it multiplies every slot,
    padding tiles included) — that makes it the differential oracle for
    the K specialization, which must be value-identical."""
    op_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.float32
    xw = jax.lax.dot_general(x.astype(op_dtype), w.astype(op_dtype),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if b is not None:
        xw = xw + b.astype(jnp.float32)
    return spmm_block_ell_ref(blocks, block_cols, xw.astype(x.dtype))


def dense_from_block_ell(blocks: np.ndarray, block_cols: np.ndarray,
                         n_cols: int) -> np.ndarray:
    """Reconstruct the dense matrix (testing only)."""
    nrb, K, B, _ = blocks.shape
    out = np.zeros((nrb * B, n_cols), blocks.dtype)
    for i in range(nrb):
        for k in range(K):
            c = int(block_cols[i, k])
            out[i * B:(i + 1) * B, c * B:(c + 1) * B] += blocks[i, k]
    return out


# ----------------------------------------------------------------------
# blocked attention — pure-XLA flash-style (scan over q chunks, logits
# never materialized for the full sequence; jax.checkpoint per chunk so
# the backward recomputes them). This is the default attention on
# non-TPU backends AND the roofline-honest XLA path: FLOPs identical to
# the Pallas kernel, memory O(B·H·chunk·Tk) instead of O(B·H·Tq·Tk).
# ----------------------------------------------------------------------
def blocked_attention(q, k, v, *, causal: bool = True,
                      window: int | None = None,
                      softcap: float | None = None,
                      scale: float | None = None,
                      q_chunk: int = 256):
    """q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D) with Hq % Hkv == 0.

    §Perf A1: sliding-window layers only touch a (window+q_chunk)-wide kv
    slice per q chunk (dynamic_slice) instead of the full Tk.
    §Perf A2: GQA via grouped einsum (bgrqd·bgkd) — kv is NEVER
    materialized Hq/Hkv-fold.
    """
    import jax as _jax
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    cq = min(q_chunk, Tq)
    pad = (-Tq) % cq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nq = q.shape[2] // cq
    qs = q.reshape(B, Hkv, rep, nq, cq, D).transpose(3, 0, 1, 2, 4, 5)
    starts = jnp.arange(nq) * cq
    offset = Tk - Tq

    # kv slice width per q chunk: full for global attention, window-bounded
    # for sliding-window layers (REPRO_NO_WINDOW_SLICE=1 restores the
    # paper-faithful baseline path for §Perf before/after measurements)
    import os as _os
    if _os.environ.get("REPRO_NO_WINDOW_SLICE"):
        kw = Tk
    else:
        kw = Tk if window is None else min(Tk, window + cq)

    def chunk(carry, xs):
        qc, start = xs                             # (B,Hkv,rep,cq,D), ()
        if kw == Tk:
            kc, vc = k, v
            k0 = 0
        else:
            # first visible key for this chunk: start+offset-window+1
            k0 = jnp.clip(start + offset - window + 1, 0, Tk - kw)
            kc = _jax.lax.dynamic_slice_in_dim(k, k0, kw, axis=2)
            vc = _jax.lax.dynamic_slice_in_dim(v, k0, kw, axis=2)
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qc.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = (start + jnp.arange(cq))[:, None] + offset
        kpos = k0 + jnp.arange(kw)[None, :]
        mask = kpos < Tk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bgkd->bgrqd", p, vc.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    _, outs = _jax.lax.scan(_jax.checkpoint(chunk), (), (qs, starts))
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hq, nq * cq, D)
    return out[:, :, :Tq]


# ----------------------------------------------------------------------
# full attention (testing oracle)
# ----------------------------------------------------------------------
def mha_ref(q, k, v, *, causal: bool = True, window: int | None = None,
            softcap: float | None = None, scale: float | None = None):
    """Reference attention. q: (B, Hq, Tq, D), k/v: (B, Hkv, Tk, D).
    GQA: Hq % Hkv == 0 (kv heads broadcast). window = sliding-window size
    (keys within [i-window+1, i] attend). Returns (B, Hq, Tq, D)."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(Tq)[:, None] + (Tk - Tq)   # align ends (decode-style)
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
