"""The program's kernel-variant family at its ``matmul`` site: square f32
GEMM by the repo's Pallas kernel at several tile shapes and by XLA's dot.

Rows, FLOP and byte counts, the plain reference and its lower-precision
control are written here from the site's documented shapes and input recipe;
nothing is imported from the program. The variants are FLOP-identical, so
every rank split is an anomaly.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Mapping, Tuple

import numpy as np

from bench.families import Gemm, derive_seed, matmul

#: the program's registered family name
FAMILY = "kernel_variants"

#: device trace events by kernel: the Pallas GEMM is the only
#: ``tpu_custom_call`` of the site; XLA's dot lowers to a ``convolution``,
#: alone or in an output fusion
KERNELS = {
    "pallas_matmul": re.compile(r'custom_call_target="tpu_custom_call"'),
    "xla_dot": re.compile(r"kind=kOutput|= \S+ convolution\("),
}


def grid(config: Mapping[str, Any], traffic: Mapping[str, Any]) -> Dict[str, Any]:
    """The ``SweepSpec`` grid the cell's census stands for."""
    return {"sites": [config["site"]], "sizes": [int(traffic["size"])],
            "per_size": int(traffic["pool"])}


def rows(config: Mapping[str, Any], traffic: Mapping[str, Any], seed: int,
         round_no: int) -> List[Tuple[str, Dict[str, Any]]]:
    """(uid, params) of one round's pool; ``round_no`` -1 is set-up's."""
    site, size = str(config["site"]), int(traffic["size"])
    return [
        (f"kernel_variants-{site}-n{size}-r{round_no}-i{i:03d}",
         {"site": site, "size": size, "seed": derive_seed(seed, round_no, i)})
        for i in range(int(traffic["pool"]) if round_no >= 0 else 1)
    ]


def algorithms(params: Mapping[str, Any]) -> List[str]:
    """Tiles 128/256/512 that fit the size, one whole-array tile where none
    does, and XLA's dot."""
    size = int(params["size"])
    blocks = [b for b in (128, 256, 512) if b <= size] or [size]
    return [f"blocks_{b}x{b}x{b}" for b in blocks] + ["xla_dot"]


def gemms(params: Mapping[str, Any]) -> Dict[str, List[Gemm]]:
    """The GEMM each variant runs: the same one."""
    n = int(params["size"])
    return {name: [Gemm(n, n, n)] for name in algorithms(params)}


def inputs(params: Mapping[str, Any]) -> List[Any]:
    """A and B on the device, by the site's documented recipe: two PRNG keys
    split from ``seed``, standard normal float32 entries."""
    import jax

    n = int(params["size"])
    keys = jax.random.split(jax.random.PRNGKey(int(params["seed"])), 2)
    return [jax.random.normal(keys[0], (n, n), np.float32),
            jax.random.normal(keys[1], (n, n), np.float32)]


def reference(params: Mapping[str, Any], operands: str) -> Dict[str, np.ndarray]:
    """Every variant's answer: A @ B from operands rounded to ``operands``."""
    product = np.asarray(matmul(*inputs(params), operands), np.float64)
    return {name: product for name in algorithms(params)}


def control(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """The reference in bfloat16, in the program's place: operands and
    product in bfloat16 for every variant."""
    import jax.numpy as jnp

    a, b = (x.astype(jnp.bfloat16) for x in inputs(params))
    product = np.asarray(jnp.dot(a, b).astype(jnp.float32))
    return {name: product for name in algorithms(params)}
