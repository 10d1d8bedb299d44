"""Pipeline parallelism: GPipe-style microbatch schedule over a stage axis.

Not part of the default mesh (DESIGN.md §5: the assigned cells fit without
PP and a stage axis strictly increases the collective term for them), but
required posture for >HBM models at 1000+ nodes. Implementation is
TPU-native: ``shard_map`` over a ``stage`` mesh axis with
``jax.lax.ppermute`` moving activations stage->stage+1; the classic GPipe
schedule runs M microbatches over S stages in M+S-1 ticks (bubble fraction
(S-1)/(M+S-1)).

``pipeline_apply`` is checked against the sequential reference in
tests/test_pipeline.py (exact equality at f32).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Pytree = Any


def pipeline_apply(
    stage_fn: Callable[[Pytree, jax.Array], jax.Array],
    stage_params: Pytree,          # leaves stacked [S, ...]
    microbatches: jax.Array,       # [M, mb, ...] (same shape through stages)
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jax.Array:
    """Run ``x -> stage_fn(p_S-1, ... stage_fn(p_0, x))`` pipelined.

    Returns [M, mb, ...] outputs. ``stage_fn`` must preserve the activation
    shape (standard for transformer blocks).
    """
    n_stages = mesh.shape[stage_axis]
    m = microbatches.shape[0]
    ticks = m + n_stages - 1

    param_specs = jax.tree.map(
        lambda _: PartitionSpec(stage_axis), stage_params
    )
    in_specs = (param_specs, PartitionSpec())          # microbatches replicated
    out_specs = PartitionSpec()                        # outputs replicated

    def per_stage(params_local: Pytree, micro: jax.Array) -> jax.Array:
        # params_local leaves: [1, ...] (this stage's slice)
        params_here = jax.tree.map(lambda p: p[0], params_local)
        stage_id = jax.lax.axis_index(stage_axis)
        mb_shape = micro.shape[1:]

        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        # carry: (inflight activation for this stage, collected outputs)
        def body(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (clamped reads are masked by the
            # commit window on the last stage)
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(stage_id == 0, microbatches[mb_idx], inflight)
            y = stage_fn(params_here, x_in)
            # last stage commits its result for microbatch (t - S + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            commit = jnp.logical_and(
                stage_id == n_stages - 1, t >= n_stages - 1
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(commit, y, outputs[out_idx]),
                out_idx,
                axis=0,
            )
            # move activations to the next stage
            nxt = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return (nxt, outputs), None

        inflight0 = jnp.zeros(mb_shape, microbatches.dtype)
        outputs0 = jnp.zeros((m,) + mb_shape, microbatches.dtype)
        (_, outputs), _ = jax.lax.scan(
            body, (inflight0, outputs0), jnp.arange(ticks)
        )
        # only the last stage's `outputs` is real; broadcast via all_gather
        # so out_specs can be replicated.
        gathered = jax.lax.all_gather(outputs, stage_axis)   # [S, M, mb...]
        return gathered[n_stages - 1]

    fn = jax.shard_map(
        per_stage, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(stage_params, microbatches)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1) / (M + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
