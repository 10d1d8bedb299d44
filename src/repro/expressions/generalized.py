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

Each family is a :class:`~repro.core.programs.VariantSite` at one size,
its inputs made at that size. jax is imported lazily, at workload-build
time: constructing a family and reading its FLOP table is pure
python/numpy, so analytic consumers (the DiscriminantSweep cost-model
backend, FLOP-count tests) never pay the jax import.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from repro.core.programs import Variant, VariantSite, program, runner


def _build(name: str, fn: Callable[..., Any]) -> Callable[..., Callable[[], Any]]:
    """The builder of variant ``name``: an unwarmed runner of ``fn`` as the
    program ``jit_<name>``, kept under its name alone (the name names one
    body in this module)."""
    return lambda *arrays: runner(program(name, fn), *arrays)


# ----------------------------------------------------------------- Gram ----

def gram_family(n: int, k: int) -> VariantSite:
    """``X = A Aᵀ B`` with A: n×k, B: n×n."""

    def inputs(seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        a = jax.random.normal(k1, (n, k), jnp.float32) / np.sqrt(k)
        b = jax.random.normal(k2, (n, n), jnp.float32) / np.sqrt(n)
        return [a, b]

    f_gemm_aat = 2 * n * n * k
    f_gemm_ab = 2 * n * n * n
    f_atb = 2 * k * n * n
    f_a_atb = 2 * n * k * n
    variants = (
        Variant("gram_left", f_gemm_aat + f_gemm_ab,
                _build("gram_left", lambda a, b: (a @ a.T) @ b)),
        Variant("gram_right", f_atb + f_a_atb,
                _build("gram_right", lambda a, b: a @ (a.T @ b))),
        # Symmetric rank-k update semantics: same math; in BLAS syrk halves
        # the FLOPs of AAᵀ. XLA has no syrk — the *analytic* count differs,
        # which is the interesting case for the discriminant test.
        Variant("gram_left_syrk", f_gemm_aat / 2 + f_gemm_ab,
                _build("gram_left_syrk", lambda a, b: (a @ a.T) @ b)),
    )
    return VariantSite("gram", variants, inputs)


# -------------------------------------------------------- Distributive ----

def distributive_family(n: int) -> VariantSite:
    """``X = (A + B) C`` vs ``AC + BC`` (A, B, C: n×n)."""

    def inputs(seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        return [jax.random.normal(kk, (n, n), jnp.float32) / np.sqrt(n) for kk in keys]

    variants = (
        Variant("dist_factored", n * n + 2 * n**3,
                _build("dist_factored", lambda a, b, c: (a + b) @ c)),
        Variant("dist_expanded", 4 * n**3 + n * n,
                _build("dist_expanded", lambda a, b, c: a @ c + b @ c)),
    )
    return VariantSite("distributive", variants, inputs)


# ---------------------------------------------------------------- Solve ----

def _solve_inverse(a: Any, b: Any) -> Any:
    import jax.numpy as jnp

    return jnp.linalg.inv(a) @ b


def _solve_lu(a: Any, b: Any) -> Any:
    import jax.numpy as jnp

    return jnp.linalg.solve(a, b)


def _solve_chol(a: Any, b: Any) -> Any:
    import jax.numpy as jnp
    import jax.scipy

    l = jnp.linalg.cholesky(a)
    y = jax.scipy.linalg.solve_triangular(l, b, lower=True)
    return jax.scipy.linalg.solve_triangular(l.T, y, lower=False)


def solve_family(n: int) -> VariantSite:
    """``x = A⁻¹ b``: explicit inverse vs LU solve (A: n×n SPD-ish)."""

    def inputs(seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        a = jax.random.normal(k1, (n, n), jnp.float32) / np.sqrt(n)
        a = a @ a.T + n * jnp.eye(n, dtype=jnp.float32)  # well-conditioned
        b = jax.random.normal(k2, (n,), jnp.float32)
        return [a, b]

    variants = (
        Variant("solve_inverse", 2.0 * n**3 + 2.0 * n * n,
                _build("solve_inverse", _solve_inverse)),
        Variant("solve_lu", (2.0 / 3.0) * n**3 + 2.0 * n * n, _build("solve_lu", _solve_lu)),
        Variant("solve_chol", (1.0 / 3.0) * n**3 + 2.0 * n * n,
                _build("solve_chol", _solve_chol)),
    )
    return VariantSite("solve", variants, inputs)


# ------------------------------------------------------------- Bilinear ----

def bilinear_family(n: int) -> VariantSite:
    """``y = uᵀ M v``: row-major vs column-major traversal, equal FLOPs."""

    def inputs(seed: int) -> List[Any]:
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        u = jax.random.normal(keys[0], (n,), jnp.float32)
        m = jax.random.normal(keys[1], (n, n), jnp.float32) / np.sqrt(n)
        v = jax.random.normal(keys[2], (n,), jnp.float32)
        return [u, m, v]

    f = 2.0 * n * n + 2.0 * n
    variants = (
        Variant("bilinear_left", f, _build("bilinear_left", lambda u, m, v: (u @ m) @ v)),
        Variant("bilinear_right", f, _build("bilinear_right", lambda u, m, v: u @ (m @ v))),
    )
    return VariantSite("bilinear", variants, inputs)


FAMILIES: Dict[str, Callable[..., VariantSite]] = {
    "gram": lambda n=512: gram_family(n, max(1, n // 4)),
    "distributive": lambda n=512: distributive_family(n),
    "solve": lambda n=512: solve_family(n),
    "bilinear": lambda n=1024: bilinear_family(n),
}
