"""Attention: GQA with qk-norm / logit softcap / sliding window, in several
mathematically equivalent implementations (the autotune variant site), plus
KV-cache decode.

Variants (all produce identical outputs up to fp reassociation — exactly the
paper's "equivalent algorithms" regime). Each keeps one precision contract:
q·k and p·v multiply the stored dtype and accumulate in f32, so scores and
softmax are f32, and p is rounded to the stored dtype for p·v:

* ``reference``  — materialises [.., sq, skv] scores. Minimal HLO ops; O(s²)
  memory. Used for small sequences and as the correctness oracle.
* ``chunked``    — blockwise online-softmax (flash formulation) as nested
  ``lax.scan``; O(s·block) memory. For causal masks the rectangular scan
  computes masked blocks too (≈2x attention-score FLOPs); the triangle-
  split optimisation and the Pallas kernel remove that waste.
* ``grouped`` vs ``broadcast`` GQA contraction order — equal FLOPs, different
  memory traffic (K/V repeated to H heads or kept grouped).

Decode attends one query against a (possibly sequence-sharded) cache; XLA
inserts the partial-softmax collectives when the cache's seq dim is sharded
(flash-decoding on TPU).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import (
    P,
    Params,
    apply_rope,
    normal_init,
    ones_init,
    param_dtype,
    rms_head_norm,
    softcap,
)

NEG_INF = -2.0e38  # f32-safe mask value
F32 = jnp.float32


# ---------------------------------------------------------------- params ---

def init_attention(cfg: ModelConfig, key: jax.Array, fused_qkv: bool = False) -> Params:
    dt = param_dtype(cfg)
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    out_std = 0.02 / np.sqrt(2 * cfg.n_layers)
    params: Params = {
        "wq": normal_init(k1, (cfg.d_model, cfg.n_heads, hd), ("embed", "q_heads", "head_dim"), dt),
        "wk": normal_init(k2, (cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wv": normal_init(k3, (cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wo": normal_init(k4, (cfg.n_heads, hd, cfg.d_model), ("q_heads", "head_dim", "embed"), dt, out_std),
    }
    if cfg.qk_norm:
        params["q_norm"] = ones_init((hd,), (None,), dt)
        params["k_norm"] = ones_init((hd,), (None,), dt)
    return params


def project_qkv(
    cfg: ModelConfig, params: Params, x: jax.Array, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [b, s, d] -> q [b, s, H, hd], k/v [b, s, K, hd] with RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_head_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_head_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def project_out(params: Params, attn: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn, params["wo"].astype(attn.dtype))


# ------------------------------------------------------------ mask logic ---

def _mask_bias(
    q_pos: jax.Array,      # [sq]
    kv_pos: jax.Array,     # [skv]
    causal: bool,
    window: Optional[int],
    kv_len: Optional[jax.Array] = None,  # scalar: valid cache length
) -> jax.Array:
    """Additive bias [sq, skv]: 0 where allowed, NEG_INF where masked."""
    allowed = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), dtype=bool)
    if causal:
        allowed &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        allowed &= kv_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        allowed &= kv_pos[None, :] < kv_len
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


# -------------------------------------------------------------- variants ---

def attention_reference(
    q: jax.Array,          # [b, sq, H, hd]
    k: jax.Array,          # [b, skv, K, hd]
    v: jax.Array,          # [b, skv, K, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    gqa: str = "grouped",  # "grouped" | "broadcast"
) -> jax.Array:
    """Full-scores attention. O(sq*skv) memory; correctness oracle."""
    b, sq, h, hd = q.shape
    kheads = k.shape[2]
    g = h // kheads
    scale = 1.0 / np.sqrt(hd)
    q_pos = jnp.arange(sq) + q_offset
    kv_pos = jnp.arange(k.shape[1])
    bias = _mask_bias(q_pos, kv_pos, causal, window)

    if gqa == "broadcast":
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k, preferred_element_type=F32) * scale
        scores = softcap(scores, logit_cap) + bias[None, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqs,bshk->bqhk", probs, v, preferred_element_type=F32).astype(q.dtype)
    # grouped: keep K/V at kv-head granularity
    qg = q.reshape(b, sq, kheads, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=F32) * scale
    scores = softcap(scores, logit_cap) + bias[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, preferred_element_type=F32).astype(q.dtype)
    return out.reshape(b, sq, h, hd)


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_block: int = 512,
    kv_block: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Blockwise online-softmax attention (flash formulation, pure JAX).

    Outer scan over q blocks, inner scan over kv blocks, carrying
    (m, l, acc) running max / normaliser / weighted accumulator. Memory is
    O(q_block * kv_block) per step. Masked (future) blocks are computed and
    discarded — see module docstring for the FLOPs note.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kheads = k.shape[2]
    g = h // kheads
    if sq % q_block != 0 or skv % kv_block != 0:
        raise ValueError(f"seq ({sq},{skv}) not divisible by blocks ({q_block},{kv_block})")
    nq, nk = sq // q_block, skv // kv_block
    scale = 1.0 / np.sqrt(hd)

    qb = q.reshape(b, nq, q_block, kheads, g, hd)
    kb = k.reshape(b, nk, kv_block, kheads, hd)
    vb = v.reshape(b, nk, kv_block, kheads, hd)

    def q_step(_, qi_idx):
        qi, i = qi_idx  # qi: [b, q_block, K, g, hd]
        q_pos = jnp.arange(q_block) + i * q_block + q_offset

        def kv_step(carry, kj_vj_j):
            m, l, acc = carry
            kj, vj, j = kj_vj_j
            kv_pos = jnp.arange(kv_block) + j * kv_block
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj, preferred_element_type=F32) * scale
            s = softcap(s, logit_cap)
            allowed = jnp.ones((q_block, kv_block), dtype=bool)
            if causal:
                allowed &= kv_pos[None, :] <= q_pos[:, None]
            if window is not None:
                allowed &= kv_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(allowed[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows (m_new == NEG_INF)
            m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(allowed[None, None, None], p, 0.0)
            alpha = jnp.where(m <= NEG_INF * 0.5, 0.0, jnp.exp(m - m_safe))
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(qi.dtype), vj, preferred_element_type=F32)
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kheads, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kheads, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, kheads, g, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nk)),
        )
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l_safe[..., None]).astype(q.dtype)  # [b, K, g, qb, hd]
        return None, jnp.moveaxis(out, 3, 1)  # [b, qb, K, g, hd]

    _, blocks = jax.lax.scan(q_step, None, (jnp.moveaxis(qb, 1, 0), jnp.arange(nq)))
    # blocks: [nq, b, q_block, K, g, hd]
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sq, kheads, g, hd)
    return out.reshape(b, sq, h, hd)


def attention_local_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    logit_cap: Optional[float] = None,
    q_block: int = 512,
    q_offset: int = 0,
) -> jax.Array:
    """Sliding-window attention with true FLOPs savings: each q block slices
    only the kv span it can see (length window + q_block), so cost is
    O(s * window) instead of O(s²). Causal by construction."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kheads = k.shape[2]
    g = h // kheads
    if sq % q_block != 0:
        raise ValueError(f"sq {sq} % q_block {q_block} != 0")
    span = window + q_block  # static slice length
    if span >= skv:
        return attention_chunked(
            q, k, v, causal=True, window=window, logit_cap=logit_cap,
            q_block=q_block, kv_block=min(skv, 1024), q_offset=q_offset,
        )
    nq = sq // q_block
    scale = 1.0 / np.sqrt(hd)
    qb = q.reshape(b, nq, q_block, kheads, g, hd)

    def q_step(_, qi_idx):
        qi, i = qi_idx
        q_start = i * q_block
        # kv span [q_start - window + 1, q_start + q_block); clamp to >= 0.
        start = jnp.maximum(q_start + q_block - span, 0)
        kj = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        q_pos = jnp.arange(q_block) + q_start + q_offset
        kv_pos = jnp.arange(span) + start + q_offset
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj, preferred_element_type=F32) * scale
        s = softcap(s, logit_cap)
        allowed = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] > q_pos[:, None] - window
        )
        s = jnp.where(allowed[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(qi.dtype), vj,
                         preferred_element_type=F32).astype(qi.dtype)
        return None, jnp.moveaxis(out, 3, 1)  # [b, qb, K, g, hd]

    _, blocks = jax.lax.scan(q_step, None, (jnp.moveaxis(qb, 1, 0), jnp.arange(nq)))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sq, kheads, g, hd)
    return out.reshape(b, sq, h, hd)


def decode_attention(
    q: jax.Array,            # [b, 1, H, hd] — single new query
    k_cache: jax.Array,      # [b, S, K, hd]
    v_cache: jax.Array,      # [b, S, K, hd]
    cache_len: jax.Array,    # scalar or [b]: number of valid positions
    *,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    kv_positions: Optional[jax.Array] = None,  # [S] absolute positions (ring)
) -> jax.Array:
    """One-token attention over the cache; O(S) per step.

    When the cache seq dim is sharded, XLA inserts the max/sum all-reduces of
    the partial softmax (flash-decoding). ``kv_positions`` supports
    ring-buffer caches (windowed layers): slot -> absolute position, negative
    for unwritten slots.
    """
    b, s, kheads, hd = k_cache.shape
    h = q.shape[2]
    g = h // kheads
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(b, 1, kheads, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32) * scale
    scores = softcap(scores, logit_cap)
    kv_pos = kv_positions if kv_positions is not None else jnp.arange(s)
    q_pos = jnp.asarray(cache_len) - 1  # query sits at position cache_len - 1
    allowed = (kv_pos[None, :] <= jnp.reshape(q_pos, (-1, 1))) & (kv_pos[None, :] >= 0)
    if window is not None:
        allowed &= kv_pos[None, :] > jnp.reshape(q_pos, (-1, 1)) - window
    bias = jnp.where(allowed, 0.0, NEG_INF)  # [b or 1, S]
    scores = scores + bias[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    return out.reshape(b, 1, h, hd)


# --------------------------------------------------------------- KV cache --

def init_kv_cache(
    batch: int, max_len: int, n_kv_heads: int, head_dim: int, dtype: jnp.dtype
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
    }


def update_kv_cache(
    cache: Dict[str, jax.Array],
    k_new: jax.Array,          # [b, s_new, K, hd]
    v_new: jax.Array,
    position: jax.Array,       # scalar write offset
) -> Dict[str, jax.Array]:
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), position, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), position, axis=1)
    return {"k": k, "v": v}


# ------------------------------------------------------------- dispatcher --

def attention(
    cfg: ModelConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    local: bool = False,
    impl: str = "auto",
    q_block: int = 512,
    kv_block: int = 1024,
) -> jax.Array:
    """Select implementation by sequence length / layer kind / config."""
    window = cfg.sliding_window if local else None
    cap = cfg.attn_logit_softcap
    sq = q.shape[1]
    if impl == "auto":
        impl = "reference" if sq <= 1024 else "chunked"
    if impl == "reference":
        return attention_reference(q, k, v, causal=True, window=window, logit_cap=cap)
    if impl == "chunked":
        if window is not None and window + q_block < k.shape[1]:
            return attention_local_chunked(
                q, k, v, window=window, logit_cap=cap, q_block=min(q_block, sq)
            )
        return attention_chunked(
            q, k, v, causal=True, window=window, logit_cap=cap,
            q_block=min(q_block, sq), kv_block=min(kv_block, k.shape[1]),
        )
    raise ValueError(f"unknown attention impl {impl!r}")
