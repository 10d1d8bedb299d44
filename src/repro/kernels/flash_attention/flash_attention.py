"""Flash attention as a Pallas TPU kernel.

Design (TPU-native, not a CUDA port — DESIGN.md §2):

* grid = (batch*heads, n_q_blocks, n_kv_blocks) with the kv axis innermost:
  TPU grids execute minor-most sequentially per core, so the online-softmax
  state (m, l, acc) lives in VMEM scratch that persists across kv steps.
* BlockSpecs tile q/o to [block_q, d] and k/v to [block_k, d] in VMEM —
  block sizes default to 128/512, multiples of the 128-lane MXU dimension.
* causal masking skips fully-masked kv blocks via ``pl.when`` — unlike the
  pure-JAX chunked scan, masked blocks cost ZERO flops (the dry-run's
  masked-block waste disappears on the kernel path). A skipped (dead) grid
  step still fetches its k/v blocks; :func:`grid_steps` counts live and
  total steps with the kernel's own predicate (:func:`block_live`).
* GQA by index: k/v hold ``bh / group`` rows and q row ``i`` reads k/v row
  ``i // group``, so kv heads are never repeated in memory.
* q @ k^T and p @ v multiply the stored dtype (bf16 products are exact in
  f32) and accumulate in f32; scores, softmax and the accumulator are f32,
  and p is rounded to the stored dtype for p @ v.
* the Pallas call is named ``flash_<mask>_<bq>x<bk>`` (:func:`kernel_name`),
  so a device trace tells the masks and tilings apart.

Validated in interpret mode against ``ref.flash_attention_ref`` over shape /
dtype / blocksize sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *,
    sm_scale: float,
    causal: bool,
    logit_cap: Optional[float],
    window: Optional[int],
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    q_offset: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal / windowed block-level skip: kv block strictly in the future
    # (or entirely outside the window) does no work at all.
    q_lo = iq * block_q + q_offset           # first absolute q position
    k_lo = ik * block_k
    live = block_live(iq, ik, block_q=block_q, block_k=block_k, causal=causal,
                      window=window, q_offset=q_offset)

    @pl.when(jnp.asarray(live))
    def _compute():
        q = q_ref[0]                           # [bq, d]
        k = k_ref[0]                           # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                           # [bq, bk]
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kv_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kv_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, kv_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)        # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                      # [bq, d]
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def block_live(iq, ik, *, block_q: int, block_k: int, causal: bool,
               window: Optional[int], q_offset: int):
    """Whether grid step (q block ``iq``, kv block ``ik``) does work: some
    kv position of the block is visible to some q position of the block.
    Python ints give a bool, traced ints a traced bool."""
    q_lo = iq * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = True
    if causal:
        live = live & (k_lo <= q_hi)
    if window is not None:
        live = live & (k_hi > q_lo - window)
    return live


def grid_steps(sq: int, skv: int, *, block_q: int = 128, block_k: int = 512,
               causal: bool = True, window: Optional[int] = None) -> Tuple[int, int]:
    """(live, total) grid steps of one (batch, head) row of the kernel, with
    the blocks capped at the sequence as the kernel caps them. Each live step
    multiplies a [block_q, d] by a [d, block_k] and a [block_q, block_k] by a
    [block_k, d] block; a dead step does no arithmetic."""
    block_q, block_k = min(block_q, sq), min(block_k, skv)
    nq, nk = sq // block_q, skv // block_k
    q_offset = skv - sq if causal else 0
    live = sum(bool(block_live(iq, ik, block_q=block_q, block_k=block_k, causal=causal,
                               window=window, q_offset=q_offset))
               for iq in range(nq) for ik in range(nk))
    return live, nq * nk


def kernel_name(causal: bool, window: Optional[int], block_q: int, block_k: int) -> str:
    """The Pallas call's name: ``flash_swa2048_128x512``, ``flash_causal_256x512``."""
    mask = f"swa{window}" if window is not None else ("causal" if causal else "nomask")
    return f"flash_{mask}_{block_q}x{block_k}"


def flash_attention_kernel(
    q: jax.Array,                 # [bh, sq, d]
    k: jax.Array,                 # [bh / group, skv, d]
    v: jax.Array,                 # [bh / group, skv, d]
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    skv = k.shape[1]
    if bh % k.shape[0]:
        raise ValueError(f"q rows {bh} must be a multiple of k/v rows {k.shape[0]}")
    group = bh // k.shape[0]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq ({sq},{skv}) must divide blocks ({block_q},{block_k})")
    nq, nk = sq // block_q, skv // block_k
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=scale,
        causal=causal,
        logit_cap=logit_cap,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=nk,
        q_offset=skv - sq if causal else 0,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, iq, ik: (i, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, iq, ik: (i // group, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, iq, ik: (i // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, iq, ik: (i, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            _vmem((block_q, d), jnp.float32),   # acc
            _vmem((block_q, 1), jnp.float32),   # m (running max)
            _vmem((block_q, 1), jnp.float32),   # l (normaliser)
        ],
        interpret=interpret,
        name=kernel_name(causal, window, block_q, block_k),
    )(q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
