"""Grouped matrix multiplication as a Pallas TPU kernel, under a name.

``lhs[offsets[i]:offsets[i+1]] @ rhs[i]`` for each group ``i`` of a run of
rows sorted by group: the expert GEMMs of a mixture of experts over its
assignments sorted by expert. This is JAX's megablox ``gmm``
(``jax.experimental.pallas.ops.tpu.megablox.gmm``, Apache-2.0, The JAX
Authors) cut to the forward product with no group offset, no existing output
and no transposed ``rhs``, with one change: the Pallas call is named
``gmm_<tm>x<tk>x<tn>`` (:func:`kernel_name`), so a device trace tells the
tilings apart. The tile metadata is megablox's own ``make_group_metadata``.

The grid is (n tiles, m tiles visited, k tiles). A group computes every
m tile that holds one of its rows, whole, and keeps its own rows of it, so
a tile that two groups share is computed twice and an empty group is not
visited: :func:`tiles_visited` counts the visits from the group sizes as
``make_group_metadata`` does, and ``tm`` times that count is the rows the
kernel executes.
"""

from __future__ import annotations

import functools
import importlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

#: the module, not the like-named function its package exports
_megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
make_group_metadata = _megablox.make_group_metadata


def kernel_name(tm: int, tk: int, tn: int) -> str:
    """The Pallas call's name at a tiling (the tiles as run)."""
    return f"gmm_{tm}x{tk}x{tn}"


def tiles_visited(group_sizes: np.ndarray, tm: int) -> int:
    """m tiles the kernel visits for these group sizes: each non-empty
    group's rows rounded out to whole tiles, as ``make_group_metadata``
    counts them with ``visit_empty_groups=False``."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return int(np.where(sizes > 0, -(-ends // tm) - starts // tm, 0).sum())


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        tiling: Tuple[int, int, int] = (128, 128, 128),
        interpret: bool = False) -> jax.Array:
    """lhs [m, k] (rows sorted by group), rhs [groups, k, n], group_sizes
    [groups] int32 summing to m -> [m, n] float32. ``m`` is a multiple of
    ``tm``; ``tk`` and ``tn`` divide ``k`` and ``n``. Operands multiply in
    their dtype and accumulate in float32."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"gmm [{m}, {k}] x [{k}, {n}] is not a multiple of the tiling {tiling}")
    tiles_k, tiles_n = k // tk, n // tn
    metadata, num_active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.zeros((), jnp.int32),
        num_nonzero_groups=rhs.shape[0], visit_empty_groups=False)
    dtype = lhs.dtype

    def kernel(metadata, group_offset, lhs_ref, rhs_ref, out_ref, acc_ref):
        del group_offset
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            lhs_ref[...].astype(dtype), rhs_ref[...].astype(dtype),
            dimension_numbers=(((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            offsets, group_ids, m_tile_ids = metadata
            group = group_ids[grid_id]
            row = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + m_tile_ids[grid_id] * tm
            mine = (row >= offsets[group]) & (row < offsets[group + 1])
            out_ref[...] = lax.select(mine, acc_ref[...], out_ref[...])

    def lhs_index(n_i, grid_id, k_i, metadata, group_offset):
        return metadata[2][grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, metadata, group_offset):
        return metadata[1][grid_id], k_i, n_i

    def out_index(n_i, grid_id, k_i, metadata, group_offset):
        return metadata[2][grid_id], n_i

    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=kernel_name(tm, tk, tn),
    )
    return call(metadata, jnp.zeros((1,), jnp.int32), lhs, rhs)
