"""Family adapters, one module per program family, found by the family
name a configuration gives. Each adapter defines, without importing the
program: ``FAMILY`` (the program's family name), ``KERNELS`` (trace event
patterns by kernel), ``grid``, ``rows``, ``algorithms``, ``gemms``,
``inputs``, ``reference`` and ``control``.

The reference evaluates each algorithm as the configuration states its
precision: every GEMM operand rounded once to ``operands`` (``bfloat16`` is a
TPU's default for float32, one MXU pass; ``float32`` leaves it as it is, as a
CPU computes), on the device at ``HIGHEST`` precision: the products of
bfloat16 operands are exact there and the sums float32, within about 1e-7 of
the same evaluation in float64 (``bench/tests/test_bench_families.py``)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import numpy as np

#: bytes of one float32 element, the dtype every configuration stores
F32_BYTES = 4


@dataclass(frozen=True)
class Gemm:
    """One float32 GEMM, [m, k] @ [k, n]."""

    m: int
    k: int
    n: int

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n

    @property
    def bytes(self) -> float:
        """Each operand read once and the result written once."""
        return float(F32_BYTES * (self.m * self.k + self.k * self.n + self.m * self.n))

    def least_seconds(self, peak_flops: float, hbm_bw: float) -> Tuple[float, str]:
        """The chip's floor for this GEMM, and which term sets it."""
        compute, memory = self.flops / peak_flops, self.bytes / hbm_bw
        return (compute, "flops") if compute >= memory else (memory, "bytes")


def matmul(a: Any, b: Any, operands: str) -> Any:
    """``a @ b`` on the device with both operands rounded once to
    ``operands`` (to nearest even), at ``HIGHEST`` precision."""
    import jax
    import jax.numpy as jnp

    if operands not in ("float32", "bfloat16"):
        raise ValueError(f"no operand precision {operands!r}")
    if operands == "bfloat16":
        a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def derive_seed(seed: int, *keys: int) -> int:
    """A 31-bit instance seed from the run's seed and the row's keys: the
    same seed gives the same rows, any whole number is taken."""
    entropy = [int(k) % 2**64 for k in (seed, *keys)]
    return int(np.random.SeedSequence(entropy).generate_state(1)[0] & 0x7FFFFFFF)
