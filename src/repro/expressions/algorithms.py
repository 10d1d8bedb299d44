"""Executable JAX implementations of chain algorithms.

Each :class:`~repro.expressions.chain.ChainAlgorithm` lowers to a sequence of
``jnp.dot`` calls executed in the algorithm's instruction order. The builder
returns a zero-argument callable that blocks on the result
(``block_until_ready``), suitable for :class:`repro.core.WallClockTimer`.

Note on instruction order under XLA: independent GEMMs inside one jitted
function may be reordered by the compiler, so two instruction orders of the
same parenthesization typically compile to identical HLO — i.e. they are
*equivalent algorithms*, which is exactly the situation the paper's
three-way comparison is designed to detect (they should land in one
performance class). The ``jit=False`` mode executes ops eagerly in the given
order for settings where order effects (cache warmth) are under study.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import named

from .chain import ChainAlgorithm, Step


def make_chain_inputs(
    dims: Sequence[int],
    dtype: jnp.dtype = jnp.float32,
    seed: int = 0,
) -> List[jax.Array]:
    """Concrete random matrices M0..M_{n-1} for a chain instance."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims) - 1)
    return [
        jax.random.normal(keys[i], (dims[i], dims[i + 1]), dtype=dtype)
        / np.sqrt(dims[i + 1])
        for i in range(len(dims) - 1)
    ]


def _execute_steps(
    steps: Sequence[Step], operands: Dict[str, jax.Array]
) -> jax.Array:
    env = dict(operands)
    last = None
    for dest, lhs, rhs in steps:
        env[dest] = jnp.dot(env[lhs], env[rhs])
        last = env[dest]
    assert last is not None
    return last


def algorithm_fn(alg: ChainAlgorithm) -> Callable[..., jax.Array]:
    """The algorithm as a pure function of ``M0..M_{n-1}`` — the program
    a jitted workload runs."""

    def fn(*mats: jax.Array) -> jax.Array:
        return _execute_steps(alg.steps, {f"M{i}": m for i, m in enumerate(mats)})

    return fn


def build_algorithm_fn(
    alg: ChainAlgorithm,
    matrices: Sequence[jax.Array],
    jit: bool = True,
) -> Callable[[], jax.Array]:
    """Zero-arg callable running one algorithm to completion."""
    operands = {f"M{i}": m for i, m in enumerate(matrices)}

    if jit:
        jitted = jax.jit(named(f"chain_{alg.name}", algorithm_fn(alg)))
        mats = tuple(matrices)

        def run() -> jax.Array:
            return jax.block_until_ready(jitted(*mats))

        return run

    def run_eager() -> jax.Array:
        return jax.block_until_ready(_execute_steps(alg.steps, operands))

    return run_eager


def build_workloads(
    algs: Sequence[ChainAlgorithm],
    matrices: Sequence[jax.Array],
    jit: bool = True,
    warmup: bool = True,
) -> Dict[str, Callable[[], jax.Array]]:
    """name -> callable table for :class:`repro.core.WallClockTimer`.

    With ``warmup=True`` each callable is executed once here so that jit
    compilation ("library overheads", paper Sec. I step 1) never lands inside
    a timed region.
    """
    table: Dict[str, Callable[[], jax.Array]] = {}
    for alg in algs:
        fn = build_algorithm_fn(alg, matrices, jit=jit)
        if warmup:
            fn()
        table[alg.name] = fn
    return table


def reference_product(matrices: Sequence[jax.Array]) -> jax.Array:
    """Left-to-right oracle product for correctness checks."""
    out = matrices[0]
    for m in matrices[1:]:
        out = jnp.dot(out, m)
    return out


def verify_algorithms(
    algs: Sequence[ChainAlgorithm],
    matrices: Sequence[jax.Array],
    rtol: float = 1e-4,
    atol: float = 1e-4,
) -> None:
    """Assert every algorithm computes the same product (mathematical
    equivalence — distinct parenthesizations differ only by fp rounding)."""
    ref = np.asarray(reference_product(matrices), dtype=np.float64)
    for alg in algs:
        out = np.asarray(build_algorithm_fn(alg, matrices, jit=False)())
        np.testing.assert_allclose(out, ref, rtol=rtol, atol=atol, err_msg=alg.name)
