"""Transformer building blocks: norms, RoPE, GQA attention (+KV caches,
sliding-window ring buffers), dense MLP, MoE FFN with sort-based dispatch.

All `spec_*` functions return TensorSpec trees (see models/spec.py);
matching `*_apply` functions consume materialized params. Logical axes:
  embed   — d_model            (FSDP-shards over 'data' for big models)
  heads   — q-head × head_dim flattened projections
  kv      — kv-head × head_dim
  ffn     — MLP hidden
  experts — MoE expert dim     (expert-parallel over 'model')
  vocab   — embedding rows
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.spec import TensorSpec

PyTree = Any


# ----------------------------------------------------------------------
# norms / activations
# ----------------------------------------------------------------------
def spec_rmsnorm(d: int) -> Dict[str, TensorSpec]:
    return {"scale": TensorSpec((d,), ("embed",), init="zeros")}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(
        jax.nn.gelu, approximate=True)}[name]


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, D) with D even; positions: (T,) or broadcastable."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (T, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# attention (GQA) + caches
# ----------------------------------------------------------------------
def spec_attention(cfg: ArchConfig) -> Dict[str, TensorSpec]:
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    sp = {
        "wq": TensorSpec((d, nq * hd), ("embed", "heads"), init="normal",
                         scale=d ** -0.5),
        "wk": TensorSpec((d, nkv * hd), ("embed", "kv"), init="normal",
                         scale=d ** -0.5),
        "wv": TensorSpec((d, nkv * hd), ("embed", "kv"), init="normal",
                         scale=d ** -0.5),
        "wo": TensorSpec((nq * hd, d), ("heads", "embed"), init="normal",
                         scale=(nq * hd) ** -0.5),
        "norm": spec_rmsnorm(d),
    }
    if cfg.qk_norm:
        sp["q_norm"] = {"scale": TensorSpec((hd,), (None,), init="zeros")}
        sp["k_norm"] = {"scale": TensorSpec((hd,), (None,), init="zeros")}
    if cfg.post_norm:
        sp["post"] = spec_rmsnorm(d)
    return sp


def attn_cache_spec(cfg: ArchConfig, batch: int, max_seq: int,
                    kind: str) -> Dict[str, TensorSpec]:
    """KV cache for one attention layer. Sliding-window ('local') layers
    get a ring buffer of `window` slots with per-slot absolute positions."""
    slots = max_seq
    if kind == "local" and cfg.sliding_window is not None:
        slots = min(max_seq, cfg.sliding_window)
    nkv, hd = cfg.num_kv_heads, cfg.hd
    return {
        "k": TensorSpec((batch, nkv, slots, hd),
                        ("batch", "kv_heads", "kv_seq", None), init="zeros",
                        dtype=cfg.dtype),
        "v": TensorSpec((batch, nkv, slots, hd),
                        ("batch", "kv_heads", "kv_seq", None), init="zeros",
                        dtype=cfg.dtype),
        "pos": TensorSpec((slots,), (None,), init="zeros", dtype=jnp.int32),
    }


def _qkv(params, cfg: ArchConfig, x, positions, kind: str):
    B, T, d = x.shape
    hd, nq, nkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, T, nq, hd)
    k = (x @ params["wk"].astype(x.dtype)).reshape(B, T, nkv, hd)
    v = (x @ params["wv"].astype(x.dtype)).reshape(B, T, nkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    theta = cfg.rope_theta
    if kind in ("attn", "moe") and cfg.rope_theta_global is not None:
        theta = cfg.rope_theta_global
    q = rope(q.swapaxes(1, 2), positions, theta)     # (B, H, T, hd)
    k = rope(k.swapaxes(1, 2), positions, theta)
    v = v.swapaxes(1, 2)
    return q, k, v


def attention_apply(params, cfg: ArchConfig, x, *, kind: str,
                    positions: jnp.ndarray,
                    attn_fn,
                    cache: Optional[PyTree] = None,
                    decode_pos: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, Optional[PyTree]]:
    """Pre-norm attention block (residual applied by caller's block fn).

    Training/prefill: cache None -> self-attention over x (writes cache if
    `cache` is a dict — prefill). Decode: x is (B, 1, d), decode_pos () —
    read/write ring or linear cache.
    """
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    B, T, d = h.shape
    window = cfg.sliding_window if kind == "local" else None
    causal = kind != "enc"

    q, k, v = _qkv(params, cfg, h, positions, kind)

    new_cache = None
    if cache is None or decode_pos is None:
        # training / prefill path: full self-attention on x
        out = attn_fn(q, k, v, causal=causal, window=window,
                      softcap=cfg.attn_softcap)
        if cache is not None:
            slots = cache["k"].shape[2]
            if slots < T and not (kind == "local"
                                  and cfg.sliding_window is not None):
                raise ValueError(
                    f"global-attention cache has {slots} slots < prompt "
                    f"length {T}; size caches to the full context")
            if slots >= T:
                kpad = jnp.zeros_like(cache["k"]).at[:, :, :T].set(k)
                vpad = jnp.zeros_like(cache["v"]).at[:, :, :T].set(v)
                pos = jnp.full((slots,), -1, jnp.int32).at[:T].set(
                    positions.astype(jnp.int32))
                new_cache = {"k": kpad, "v": vpad, "pos": pos}
            else:  # ring: keep last `slots` entries
                kk = k[:, :, T - slots:]
                vv = v[:, :, T - slots:]
                pp = positions[T - slots:].astype(jnp.int32)
                idx = pp % slots
                kr = jnp.zeros_like(cache["k"]).at[:, :, idx].set(kk)
                vr = jnp.zeros_like(cache["v"]).at[:, :, idx].set(vv)
                pos = jnp.full((slots,), -1, jnp.int32).at[idx].set(pp)
                new_cache = {"k": kr, "v": vr, "pos": pos}
    else:
        # decode path: write one token, attend over cache
        slots = cache["k"].shape[2]
        widx = (decode_pos % slots).astype(jnp.int32)
        kc = jax.lax.dynamic_update_index_in_dim(cache["k"], k[:, :, 0],
                                                 widx, axis=2)
        vc = jax.lax.dynamic_update_index_in_dim(cache["v"], v[:, :, 0],
                                                 widx, axis=2)
        pos = jax.lax.dynamic_update_index_in_dim(
            cache["pos"], decode_pos.astype(jnp.int32), widx, axis=0)
        new_cache = {"k": kc, "v": vc, "pos": pos}
        out = decode_attention(q, kc, vc, pos, decode_pos,
                               window=window, softcap=cfg.attn_softcap)

    out = out.swapaxes(1, 2).reshape(B, T, cfg.num_heads * cfg.hd)
    out = out @ params["wo"].astype(out.dtype)
    if cfg.post_norm:
        out = rmsnorm(params["post"], out, cfg.norm_eps)
    return out, new_cache


def decode_attention(q, kc, vc, kpos, qpos, *, window=None, softcap=None):
    """Single-token attention over a (possibly ring) cache.
    q: (B, Hq, 1, D); kc/vc: (B, Hkv, S, D); kpos: (S,) absolute positions
    (-1 = empty); qpos: () current position. Memory-bound matvec — XLA
    handles this well; no custom kernel needed (DESIGN.md)."""
    B, Hq, _, D = q.shape
    Hkv = kc.shape[1]
    rep = Hq // Hkv
    kcr = jnp.repeat(kc, rep, axis=1)
    vcr = jnp.repeat(vc, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kcr.astype(jnp.float32)) * (D ** -0.5)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window is not None:
        valid &= kpos > qpos - window
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vcr.astype(jnp.float32))
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# dense MLP (SwiGLU / GeGLU)
# ----------------------------------------------------------------------
def spec_mlp(cfg: ArchConfig) -> Dict[str, TensorSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "norm": spec_rmsnorm(d),
        "wg": TensorSpec((d, f), ("embed", "ffn"), init="normal",
                         scale=d ** -0.5),
        "wu": TensorSpec((d, f), ("embed", "ffn"), init="normal",
                         scale=d ** -0.5),
        "wd": TensorSpec((f, d), ("ffn", "embed"), init="normal",
                         scale=f ** -0.5),
        **({"post": spec_rmsnorm(d)} if cfg.post_norm else {}),
    }


def mlp_apply(params, cfg: ArchConfig, x):
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    act = _act(cfg.act)
    g = act(h @ params["wg"].astype(h.dtype))
    u = h @ params["wu"].astype(h.dtype)
    out = (g * u) @ params["wd"].astype(h.dtype)
    if cfg.post_norm:
        out = rmsnorm(params["post"], out, cfg.norm_eps)
    return out


# ----------------------------------------------------------------------
# MoE FFN: top-k routing, sort-based dispatch with capacity (static
# shapes — GShard/Switch style, expert dim shards over 'model')
# ----------------------------------------------------------------------
def spec_moe(cfg: ArchConfig) -> Dict[str, TensorSpec]:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    # Expert weights stay FSDP-sharded in the STATE ('embed' over data —
    # replicating them is untenable: dbrx experts ARE 127 of 132 B
    # params). §Perf B3 forces ZeRO-3 semantics at COMPUTE time instead:
    # moe_apply constrains the bf16 weight copies to P('model', None,
    # None) right before the einsums, so SPMD all-gathers the ~254 MB
    # weight instead of partial-sum all-reducing 3.4 GB activations.
    return {
        "norm": spec_rmsnorm(d),
        "router": TensorSpec((d, e), ("embed", None), init="normal",
                             scale=d ** -0.5),
        "wg": TensorSpec((e, d, f), ("experts", "embed", "moe_ffn"),
                         init="normal", scale=d ** -0.5),
        "wu": TensorSpec((e, d, f), ("experts", "embed", "moe_ffn"),
                         init="normal", scale=d ** -0.5),
        "wd": TensorSpec((e, f, d), ("experts", "moe_ffn", "embed"),
                         init="normal", scale=f ** -0.5),
    }


def moe_capacity(cfg: ArchConfig, tokens: int) -> int:
    c = int(tokens * cfg.experts_per_token * cfg.moe_capacity_factor
            / cfg.num_experts)
    return max(8, -(-c // 8) * 8)


def ambient_axes():
    """Mesh (data, model) axes from the ambient jax.set_mesh context.
    (None, None) when tracing without a mesh — plain CPU tests. Also used
    by repro.dist.steps to decide whether activation constraints apply."""
    names = tuple(jax.sharding.get_abstract_mesh().axis_names)
    data = tuple(a for a in ("pod", "data") if a in names) or None
    model = "model" if "model" in names else None
    return data, model


def _moe_constrain(x, spec_axes):
    """with_sharding_constraint against the ambient mesh; no-op without
    one. §Perf B3b: the (E, C, ·) dispatch buffers MUST be pinned to
    (model=experts, data=capacity) — otherwise SPMD either partial-sums
    the expert einsums (when weights are FSDP-sharded) or replicates the
    whole global dispatch per data shard (when they are not)."""
    data, model = ambient_axes()
    if data is None and model is None:
        return x
    from jax.sharding import PartitionSpec as P
    resolved = [model if a == "model" else (data if a == "data" else None)
                for a in spec_axes]
    try:
        return jax.lax.with_sharding_constraint(x, P(*resolved))
    except Exception:
        return x


def moe_apply(params, cfg: ArchConfig, x):
    """x: (B, T, d) -> (y, aux_loss).

    ROW-LOCAL sort-based dispatch (§Perf iteration B4): every batch row
    sorts/dispatches its own T·k assignments into its own (E, C_row, d)
    buffer. The batch dim stays leading everywhere, so under the
    (data × model) mesh the dispatch is embarrassingly data-parallel
    (sorts are per-row, no global argsort) and the buffer shards
    (B=data, E=model) with NO communication — x is already replicated
    across 'model'. A global-sort formulation forces XLA to gather the
    whole token buffer per layer (measured: 11 TB/step on dbrx).

    Small batches (B·T ≤ 512 — decode steps) use C = T·k (provably
    dropless: an expert appears at most once per token's top-k), so
    decode is exact.
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    h = rmsnorm(params["norm"], x, cfg.norm_eps)         # (B, T, d)

    logits = (h @ params["router"].astype(h.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)                   # (B, T, E)
    topv, topi = jax.lax.top_k(probs, k)                 # (B, T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    if B * T <= 512:
        # decode / tiny batches: flatten to ONE dispatch row with C = n —
        # provably dropless (exact decode) and 17× less expert-buffer
        # padding than per-row dispatch at these sizes
        Bd, Td, C = 1, B * T, B * T
    else:
        Bd, Td = B, T
        c = int(T * k * cfg.moe_capacity_factor / E)
        C = max(8, -(-c // 8) * 8)
    h = h.reshape(Bd, Td, d)

    flat_e = topi.reshape(Bd, Td * k)     # token-major assignment order
    flat_w = topv.reshape(Bd, Td * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    inv = jnp.argsort(order, axis=-1, stable=True)       # inverse perm
    e_s = jnp.take_along_axis(flat_e, order, -1)
    starts = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E)))(e_s)   # (B, E)
    pos_in_e = jnp.arange(Td * k)[None] \
        - jnp.take_along_axis(starts, e_s, -1)
    keep_s = pos_in_e < C
    dst_e_s = jnp.where(keep_s, e_s, E)                  # overflow row
    dst_c_s = jnp.where(keep_s, pos_in_e, 0)
    # §Perf B5: map destinations back to token-major order (small int
    # gathers). The token VALUES are then dispatched with a structured
    # jnp.repeat — NO data-dependent gather of the (B, T·k, d) tokens —
    # and collected with a reshape-sum — NO scatter-add. The only
    # data-dependent ops left touch the (E, C, d) expert buffer (the
    # true expert-parallel traffic).
    de_o = jnp.take_along_axis(dst_e_s, inv, -1)         # (B, T·k)
    dc_o = jnp.take_along_axis(dst_c_s, inv, -1)
    updates = jnp.repeat(h, k, axis=1)                   # (B, T·k, d)

    # vmap keeps B a REAL batch dim in the HLO scatter/gather
    # (operand_batching_dims) — explicit b-coordinate advanced indexing
    # defeats GSPMD and replicates 24 GB token buffers (measured).
    def _dispatch_row(up, de, dc):
        return jnp.zeros((E + 1, C, d), h.dtype).at[de, dc].set(up)

    buf = jax.vmap(_dispatch_row)(updates, de_o, dc_o)
    buf = _moe_constrain(buf[:, :E], ("data", "model", None, None))

    # ZeRO-3 weight gather (§Perf B3): unshard the bf16 expert weights'
    # data (FSDP) dims before use so contractions are local — SPMD
    # otherwise partial-sum all-reduces the (B, E, C, f) activations.
    # ONLY when activations outweigh weights (training/prefill): at
    # decode sizes the partial-sum all-reduce of a ~4 MB activation
    # beats gathering ~254 MB of weights — the optimum flips.
    if Bd * Td > 512:
        wg = _moe_constrain(params["wg"].astype(h.dtype),
                            ("model", None, None))
        wu = _moe_constrain(params["wu"].astype(h.dtype),
                            ("model", None, None))
        wd = _moe_constrain(params["wd"].astype(h.dtype),
                            ("model", None, None))
    else:
        wg = params["wg"].astype(h.dtype)
        wu = params["wu"].astype(h.dtype)
        wd = params["wd"].astype(h.dtype)

    act = _act(cfg.act)
    g = act(jnp.einsum("becd,edf->becf", buf, wg))
    u = jnp.einsum("becd,edf->becf", buf, wu)
    out = jnp.einsum("becf,efd->becd", g * u, wd)        # (B, E, C, d)

    def _collect_row(o_row, de, dc):
        return o_row[jnp.minimum(de, E - 1), dc]         # (T·k, d)

    gathered = jax.vmap(_collect_row)(out, de_o, dc_o)
    w_keep = (flat_w * (de_o < E)).astype(gathered.dtype)
    y = (gathered * w_keep[..., None]).reshape(B, T, k, d).sum(2)

    # Switch-style load-balancing aux loss
    frac = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32),
                    axis=(0, 1, 2))
    mean_p = probs.mean((0, 1))
    aux = E * jnp.sum(frac * mean_p)
    return y, aux
