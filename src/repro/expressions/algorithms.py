"""Executable JAX implementations of chain algorithms.

Each :class:`~repro.expressions.chain.ChainAlgorithm` lowers to a sequence of
``jnp.dot`` calls executed in the algorithm's instruction order.
:func:`chain_site` makes a chain's algorithms a
:class:`~repro.core.programs.VariantSite`, whose table of blocking, warmed
thunks :class:`repro.core.WallClockTimer` measures.

An algorithm's jitted program depends only on its name and its steps, never
on the dims or the data, so it is kept under both
(:func:`repro.core.programs.program`) and every instance of the same chain
length shares it; ``jax.jit`` still compiles one executable per shape
signature.

Note on instruction order under XLA: independent GEMMs inside one jitted
function may be reordered by the compiler, so two instruction orders of the
same parenthesization typically compile to identical HLO — i.e. they are
*equivalent algorithms*, which is exactly the situation the paper's
three-way comparison is designed to detect (they should land in one
performance class). :func:`verify_algorithms` runs the steps eagerly, in
order, as the reference of each algorithm's product.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.programs import Variant, VariantSite, program, runner

from .chain import ChainAlgorithm, Step, generate_chain_algorithms


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


def chain_site(dims: Sequence[int]) -> VariantSite:
    """The chain ``dims`` as a variant site: one variant per algorithm
    (:func:`~repro.expressions.chain.generate_chain_algorithms`), each the
    program ``jit_chain_<name>``; inputs ``make_chain_inputs(dims, seed)``."""
    dims = tuple(int(d) for d in dims)

    def build(alg: ChainAlgorithm) -> Callable[..., Callable[[], jax.Array]]:
        return lambda *mats: runner(
            program(f"chain_{alg.name}", algorithm_fn(alg), alg.steps), *mats)

    return VariantSite(
        name=f"chain{list(dims)}",
        variants=tuple(Variant(alg.name, float(alg.flops), build(alg))
                       for alg in generate_chain_algorithms(dims)),
        make_inputs=lambda seed: make_chain_inputs(dims, seed=seed),
    )


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
    operands = {f"M{i}": m for i, m in enumerate(matrices)}
    for alg in algs:
        out = np.asarray(_execute_steps(alg.steps, operands))
        np.testing.assert_allclose(out, ref, rtol=rtol, atol=atol, err_msg=alg.name)
