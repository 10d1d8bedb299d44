"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the chunk axis is
the innermost (sequential) grid dimension, so the inter-chunk recurrent
state [p, n] lives in VMEM scratch that persists across chunk steps — the
Pallas analogue of the ``lax.scan`` carry, with the intra-chunk dual
(quadratic) form evaluated on the MXU:

  per chunk Q:   cum    = cumsum(logda)                       [Q]
                 L      = exp(cum_i - cum_j) (i >= j)         [Q, Q]
                 y      = ((C Bᵀ) ⊙ L) x̄  +  exp(cum) (C · state)
                 state <- exp(cum_Q) * state + (exp(cum_Q - cum) x̄)ᵀ B

Chunk length is a VMEM/MXU tile choice (multiple of 128 recommended); it is
mathematically inert — equal-FLOPs variants ranked by the autotuner.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, l_ref, b_ref, c_ref, y_ref, state_ref, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xb = x_ref[0].astype(jnp.float32)          # [Q, p]
    ld = l_ref[0].astype(jnp.float32)          # [1, Q]
    bm = b_ref[0].astype(jnp.float32)          # [Q, n]
    cm = c_ref[0].astype(jnp.float32)          # [Q, n]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ltri = ii >= jj
    # cumsum as a product with the lower-triangular ones matrix (the TPU
    # kernel compiler has no cumsum), once as a column and once as a row;
    # HIGHEST keeps f32 sums off the bf16 MXU passes, since exp() below
    # amplifies their error
    ones = jnp.where(ltri, 1.0, 0.0)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(ones, ld, nt, precision=hi)       # [Q, 1]
    cum_row = jax.lax.dot_general(ld, ones, nt, precision=hi)   # [1, Q]
    # intra-chunk decay matrix L[i, j] = exp(cum_i - cum_j), lower-tri
    decay = jnp.where(ltri, jnp.exp(cum - cum_row), 0.0)

    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # [Q, Q]
    y_intra = jax.lax.dot_general(
        cb * decay, xb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [Q, p]

    state = state_ref[...]                     # [p, n]
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # [Q, p]

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    total = jnp.sum(ld, axis=1, keepdims=True)  # [1, 1]
    decay_to_end = jnp.exp(total - cum)        # [Q, 1]
    s_chunk = jax.lax.dot_general(
        xb * decay_to_end, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [p, n]
    state_ref[...] = state * jnp.exp(total) + s_chunk


def ssd_scan_kernel(
    xbar: jax.Array,     # [bh, s, p]
    logda: jax.Array,    # [bh, s]
    b_mat: jax.Array,    # [bh, s, n]
    c_mat: jax.Array,    # [bh, s, n]
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bh, s, p = xbar.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk} != 0")
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    # logda rides as [bh, 1, s]: a (1, chunk) block of [bh, s] breaks the
    # TPU rule that a block's last two dims be (8, 128)-divisible or whole
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, ic: (i, 0, ic)),
            pl.BlockSpec((1, chunk, n), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ic: (i, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, ic: (i, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), xbar.dtype),
        scratch_shapes=[_vmem((p, n), jnp.float32)],
        interpret=interpret,
    )(xbar, logda[:, None, :], b_mat, c_mat)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
