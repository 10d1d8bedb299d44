"""Blocked GEMM as a Pallas TPU kernel — the paper's compute substrate.

Every algorithm the ranking methodology compares (matrix-chain
parenthesizations, expression variants) bottoms out in GEMM; this kernel is
the TPU-native building block:

* grid = (M/bm, N/bn, K/bk), K innermost (sequential on TPU) with an f32
  VMEM accumulator persisting across K steps;
* block sizes default to 256x256x512 — MXU-aligned (multiples of 128) and
  sized so 3 tiles (A, B, acc) fit VMEM with headroom:
  256*512*2 + 512*256*2 + 256*256*4 bytes = 0.8 MB;
* mixed precision: bf16/f32 inputs, f32 accumulation, output cast.

ops.py exposes ``matmul`` and ``chain_matmul`` (executes a ChainAlgorithm's
GEMM sequence with this kernel). ref.py is ``jnp.dot``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_blocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_kernel(
    a: jax.Array,                 # [m, k]
    b: jax.Array,                 # [k, n]
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # pad to block multiples (zero padding is exact for matmul)
    mp, np_, kp = _ceil(m, bm) * bm, _ceil(n, bn) * bn, _ceil(k, bk) * bk
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp != m or kp != k) else a
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp != k or np_ != n) else b

    kernel = functools.partial(_matmul_kernel, n_k_blocks=kp // bk)
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bk, bn), lambda im, jn, ik: (ik, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[_vmem((bm, bn), jnp.float32)],
        interpret=interpret,
        name=f"matmul_{bm}x{bn}x{bk}",
    )(a_p, b_p)
    return out[:m, :n]


def _ceil(x: int, m: int) -> int:
    return (x + m - 1) // m


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
