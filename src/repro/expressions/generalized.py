"""Beyond-chain linear-algebra expression families.

Linnea-class generators emit variants for general expressions, not just
chains. We implement a small set of families whose variant spaces exercise
different mathematical identities (the paper's Sec. II situates chains within
this broader LAMP space):

* ``GramFamily``     — ``X = A Aᵀ B``: associativity + symmetry (``(AAᵀ)B``
  vs ``A(AᵀB)``; syrk-style half-FLOPs accounting for the symmetric product).
* ``DistributiveFamily`` — ``X = (A + B) C`` vs ``AC + BC``: distributivity
  *changes* the FLOP count (one GEMM vs two) — a family where FLOPs should
  discriminate strongly.
* ``SolveFamily``    — ``x = A⁻¹ b``: explicit inverse + GEMV vs LU solve —
  the canonical "never invert" example; FLOPs 2n³(inv) + 2n² vs ~(2/3)n³.
* ``BilinearFamily`` — ``y = uᵀ M v``: ``(uᵀM)v`` vs ``uᵀ(Mv)`` — equal
  FLOPs for square M, different memory-access patterns (row vs column
  traversal): the equal-FLOPs regime again.

Each family yields named variants with analytic FLOP counts and JAX
callables, pluggable into the same ranking pipeline as the chains.

jax is imported lazily, at workload-build time: constructing a family and
reading its FLOP table is pure python/numpy, so analytic consumers (the
DiscriminantSweep cost-model backend, FLOP-count tests) never pay the jax
import.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

from repro.core.measure import warm
from repro.core.spans import ProgramCache, named


@dataclass(frozen=True)
class ExpressionVariant:
    name: str
    label: str
    flops: float
    build: Callable[..., Callable[[], Any]]  # (*arrays) -> thunk, unwarmed


@dataclass(frozen=True)
class ExpressionFamily:
    name: str
    variants: Tuple[ExpressionVariant, ...]
    make_inputs: Callable[[int, int], List[Any]]  # (size, seed)

    def flops_table(self) -> Dict[str, float]:
        return {v.name: v.flops for v in self.variants}

    def workloads(
        self, size: int, seed: int = 0, warmup: bool = True
    ) -> Dict[str, Callable[[], Any]]:
        """name -> thunk on the inputs of ``(size, seed)``; with ``warmup``
        each thunk runs once here (:func:`repro.core.measure.warm`), the only
        call before the timer's."""
        arrays = self.make_inputs(size, seed)
        table = {v.name: v.build(*arrays) for v in self.variants}
        if warmup:
            warm(table)
        return table


#: Keyed by the program's name, which names one body in this module.
_PROGRAMS = ProgramCache(maxsize=64)


def _jit_thunk(name: str, fn: Callable[..., Any], *arrays: Any) -> Callable[[], Any]:
    """The thunk that runs ``fn`` jitted as the program ``jit_<name>`` and
    waits for the device, returned unwarmed: it compiles on its first call,
    the family's warm run (:meth:`ExpressionFamily.workloads`). The jitted
    program is built once per process for each name: a later ``fn`` under
    the same name is not looked at."""
    import jax

    jitted = _PROGRAMS.get(name, lambda: jax.jit(named(name, fn)))

    def run() -> Any:
        return jax.block_until_ready(jitted(*arrays))

    return run


# ----------------------------------------------------------------- Gram ----

def gram_family(n: int, k: int) -> ExpressionFamily:
    """``X = A Aᵀ B`` with A: n×k, B: n×n."""

    def inputs(size: int, seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        kk = max(1, int(k * size / n))
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (size, kk), jnp.float32) / np.sqrt(kk)
        b = jax.random.normal(k2, (size, size), jnp.float32) / np.sqrt(size)
        return [a, b]

    def left_first(a: Any, b: Any) -> Callable[[], Any]:
        return _jit_thunk("gram_left", lambda a, b: (a @ a.T) @ b, a, b)

    def right_first(a: Any, b: Any) -> Callable[[], Any]:
        return _jit_thunk("gram_right", lambda a, b: a @ (a.T @ b), a, b)

    def left_syrk(a: Any, b: Any) -> Callable[[], Any]:
        # Symmetric rank-k update semantics: same math; in BLAS syrk halves
        # the FLOPs of AAᵀ. XLA has no syrk — the *analytic* count differs,
        # which is the interesting case for the discriminant test.
        return _jit_thunk("gram_left_syrk", lambda a, b: (a @ a.T) @ b, a, b)

    # FLOP accounting at the nominal size n (scaled at measurement time the
    # ratios are invariant, which is all RF needs).
    f_gemm_aat = 2 * n * n * k
    f_gemm_ab = 2 * n * n * n
    f_atb = 2 * k * n * n
    f_a_atb = 2 * n * k * n
    variants = (
        ExpressionVariant("gram_left", "(AAt)B", f_gemm_aat + f_gemm_ab, left_first),
        ExpressionVariant("gram_right", "A(AtB)", f_atb + f_a_atb, right_first),
        ExpressionVariant(
            "gram_left_syrk", "syrk(A)B", f_gemm_aat / 2 + f_gemm_ab, left_syrk
        ),
    )
    return ExpressionFamily("gram", variants, inputs)


# -------------------------------------------------------- Distributive ----

def distributive_family(n: int) -> ExpressionFamily:
    """``X = (A + B) C`` vs ``AC + BC`` (A, B, C: n×n)."""

    def inputs(size: int, seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        return [
            jax.random.normal(kk, (size, size), jnp.float32) / np.sqrt(size)
            for kk in keys
        ]

    def factored(a, b, c):
        return _jit_thunk("dist_factored", lambda a, b, c: (a + b) @ c, a, b, c)

    def expanded(a, b, c):
        return _jit_thunk("dist_expanded", lambda a, b, c: a @ c + b @ c, a, b, c)

    variants = (
        ExpressionVariant("dist_factored", "(A+B)C", n * n + 2 * n**3, factored),
        ExpressionVariant("dist_expanded", "AC+BC", 4 * n**3 + n * n, expanded),
    )
    return ExpressionFamily("distributive", variants, inputs)


# ---------------------------------------------------------------- Solve ----

def solve_family(n: int) -> ExpressionFamily:
    """``x = A⁻¹ b``: explicit inverse vs LU solve (A: n×n SPD-ish)."""

    def inputs(size: int, seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        a = jax.random.normal(k1, (size, size), jnp.float32) / np.sqrt(size)
        a = a @ a.T + size * jnp.eye(size, dtype=jnp.float32)  # well-conditioned
        b = jax.random.normal(k2, (size,), jnp.float32)
        return [a, b]

    def via_inverse(a, b):
        import jax.numpy as jnp

        return _jit_thunk("solve_inverse", lambda a, b: jnp.linalg.inv(a) @ b, a, b)

    def via_solve(a, b):
        import jax.numpy as jnp

        return _jit_thunk("solve_lu", lambda a, b: jnp.linalg.solve(a, b), a, b)

    def via_cholesky(a, b):
        import jax.scipy
        import jax.numpy as jnp

        def f(a, b):
            l = jnp.linalg.cholesky(a)
            y = jax.scipy.linalg.solve_triangular(l, b, lower=True)
            return jax.scipy.linalg.solve_triangular(l.T, y, lower=False)

        return _jit_thunk("solve_chol", f, a, b)

    variants = (
        ExpressionVariant("solve_inverse", "inv(A)b", 2.0 * n**3 + 2.0 * n * n, via_inverse),
        ExpressionVariant("solve_lu", "solve(A,b)", (2.0 / 3.0) * n**3 + 2.0 * n * n, via_solve),
        ExpressionVariant("solve_chol", "chol-solve", (1.0 / 3.0) * n**3 + 2.0 * n * n, via_cholesky),
    )
    return ExpressionFamily("solve", variants, inputs)


# ------------------------------------------------------------- Bilinear ----

def bilinear_family(n: int) -> ExpressionFamily:
    """``y = uᵀ M v``: row-major vs column-major traversal, equal FLOPs."""

    def inputs(size: int, seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        u = jax.random.normal(keys[0], (size,), jnp.float32)
        m = jax.random.normal(keys[1], (size, size), jnp.float32) / np.sqrt(size)
        v = jax.random.normal(keys[2], (size,), jnp.float32)
        return [u, m, v]

    def left(u, m, v):
        return _jit_thunk("bilinear_left", lambda u, m, v: (u @ m) @ v, u, m, v)

    def right(u, m, v):
        return _jit_thunk("bilinear_right", lambda u, m, v: u @ (m @ v), u, m, v)

    f = 2.0 * n * n + 2.0 * n
    variants = (
        ExpressionVariant("bilinear_left", "(utM)v", f, left),
        ExpressionVariant("bilinear_right", "ut(Mv)", f, right),
    )
    return ExpressionFamily("bilinear", variants, inputs)


FAMILIES: Dict[str, Callable[..., ExpressionFamily]] = {
    "gram": lambda n=512: gram_family(n, max(1, n // 4)),
    "distributive": lambda n=512: distributive_family(n),
    "solve": lambda n=512: solve_family(n),
    "bilinear": lambda n=1024: bilinear_family(n),
}
