"""Jit'd public wrapper for the flash-attention kernel.

Handles model-layout plumbing: the [b, s, h, d] <-> [bh, s, d] flattening
and GQA (the kernel reads kv head ``h // group`` by index; the oracle gets
the kv heads repeated to the q heads).
``use_kernel=False`` routes to the pure-jnp oracle — both paths share this
wrapper so tests sweep them identically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel
from .ref import flash_attention_ref


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "logit_cap", "window",
        "block_q", "block_k", "use_kernel", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,                 # [b, sq, h, d]
    k: jax.Array,                 # [b, skv, kv_heads, d]
    v: jax.Array,                 # [b, skv, kv_heads, d]
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 512,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    kh = k.shape[2]
    if not use_kernel and h != kh:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(-1, k.shape[1], d)
    vf = v.transpose(0, 2, 1, 3).reshape(-1, v.shape[1], d)

    if use_kernel:
        of = flash_attention_kernel(
            qf, kf, vf,
            causal=causal, sm_scale=sm_scale, logit_cap=logit_cap,
            window=window, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    else:
        of = flash_attention_ref(
            qf, kf, vf,
            causal=causal, sm_scale=sm_scale, logit_cap=logit_cap,
            window=window,
        )
    return of.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
