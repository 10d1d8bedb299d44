"""Executable JAX implementations of chain algorithms.

Each :class:`~repro.expressions.chain.ChainAlgorithm` lowers to a sequence of
``jnp.dot`` calls executed in the algorithm's instruction order. The builder
returns a zero-argument callable that blocks on the result
(``block_until_ready``), suitable for :class:`repro.core.WallClockTimer`.

An algorithm's jitted program depends only on its name and its steps, never
on the dims or the data, so each is built once per process
(:func:`chain_program`) and every instance of the same chain length shares
it; ``jax.jit`` still compiles one executable per shape signature.

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

from repro.core.measure import warm
from repro.core.spans import ProgramCache, named

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
    steps = alg.steps

    def fn(*mats: jax.Array) -> jax.Array:
        return _execute_steps(steps, {f"M{i}": m for i, m in enumerate(mats)})

    return fn


#: Keyed by ``(alg.name, alg.steps)``: the 6 algorithms of a 4-matrix chain
#: are 6 keys, and the chains of 6-8 matrices a few hundred.
_PROGRAMS = ProgramCache(maxsize=256)


def chain_program(alg: ChainAlgorithm) -> Callable[..., jax.Array]:
    """``jax.jit`` of the algorithm as the program ``jit_chain_<name>``,
    built once per process for each ``(alg.name, alg.steps)`` (at most 256
    kept, the least recently used dropped first). The name is in the key
    because the device trace reads it."""
    return _PROGRAMS.get(
        (alg.name, alg.steps),
        lambda: jax.jit(named(f"chain_{alg.name}", algorithm_fn(alg))))


def build_algorithm_fn(
    alg: ChainAlgorithm,
    matrices: Sequence[jax.Array],
    jit: bool = True,
) -> Callable[[], jax.Array]:
    """Zero-arg callable running one algorithm to completion: with ``jit``
    the process's shared :func:`chain_program` on these matrices, else the
    steps eagerly in order."""
    operands = {f"M{i}": m for i, m in enumerate(matrices)}

    if jit:
        jitted = chain_program(alg)
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

    With ``warmup=True`` each callable is executed once here
    (:func:`repro.core.measure.warm`) so that jit compilation ("library
    overheads", paper Sec. I step 1) never lands inside a timed region.
    """
    table = {alg.name: build_algorithm_fn(alg, matrices, jit=jit) for alg in algs}
    if warmup:
        warm(table)
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
