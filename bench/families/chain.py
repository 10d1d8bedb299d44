"""The paper's Expression 1, X = M0 M1 ... M(n-1), as the benchmark sees it.

Rows, FLOP and byte counts, the plain reference and its lower-precision
control are written here from the paper and the family's documented input
recipe; nothing is imported from the program. Instance ``seed`` params are
drawn from the run's ``--seed``; the dims are fixed by the traffic mix
(``lo == hi``), so the seed changes the data and never the shapes.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Dict, List, Mapping, Sequence, Tuple, Union

import numpy as np

from bench.families import Gemm, derive_seed, matmul

#: the program's registered family name
FAMILY = "chain"

#: device trace events of XLA's GEMMs: on a TPU ``jnp.dot`` lowers to a
#: ``convolution``, alone or in an output fusion
KERNELS = {"xla_dot": re.compile(r"kind=kOutput|= \S+ convolution\(")}

Tree = Union[int, Tuple["Tree", "Tree"]]


def grid(config: Mapping[str, Any], traffic: Mapping[str, Any]) -> Dict[str, Any]:
    """The ``SweepSpec`` grid the cell's census stands for."""
    size = int(traffic["size"])
    return {"count": int(traffic["pool"]), "n_matrices": [int(config["n_matrices"])],
            "lo": size, "hi": size}


def rows(config: Mapping[str, Any], traffic: Mapping[str, Any], seed: int,
         round_no: int) -> List[Tuple[str, Dict[str, Any]]]:
    """(uid, params) of one round's pool; ``round_no`` -1 is set-up's."""
    size, n = int(traffic["size"]), int(config["n_matrices"])
    return [
        (f"chain-n{n}-r{round_no}-i{i:03d}",
         {"n_matrices": n, "lo": size, "hi": size,
          "seed": derive_seed(seed, round_no, i)})
        for i in range(int(traffic["pool"]) if round_no >= 0 else 1)
    ]


# ------------------------------------------------------------ algorithms ---


@functools.lru_cache(maxsize=None)
def _trees(i: int, j: int) -> Tuple[Tree, ...]:
    if i == j:
        return (i,)
    return tuple((left, right) for k in range(i, j)
                 for left in _trees(i, k) for right in _trees(k + 1, j))


def _shape(tree: Tree, dims: Sequence[int]) -> Tuple[int, int]:
    if isinstance(tree, int):
        return dims[tree], dims[tree + 1]
    return _shape(tree[0], dims)[0], _shape(tree[1], dims)[1]


def _gemms(tree: Tree, dims: Sequence[int]) -> List[Gemm]:
    if isinstance(tree, int):
        return []
    (m, k), (_, n) = _shape(tree[0], dims), _shape(tree[1], dims)
    return _gemms(tree[0], dims) + _gemms(tree[1], dims) + [Gemm(m, k, n)]


def _products(tree: Tree) -> int:
    return 0 if isinstance(tree, int) else 1 + _products(tree[0]) + _products(tree[1])


def _orders(tree: Tree) -> int:
    """Instruction orders of a tree: the linear extensions of its products,
    which interleave the two subtrees' orders before the root's product."""
    if isinstance(tree, int):
        return 1
    left, right = _products(tree[0]), _products(tree[1])
    return (_orders(tree[0]) * _orders(tree[1])
            * math.comb(left + right, left))


def algorithms(params: Mapping[str, Any]) -> Dict[str, Tree]:
    """Algorithm name -> parenthesization, named as the paper numbers them:
    trees in enumeration order, stably sorted by FLOPs, one name per
    instruction order, so ``algorithm0`` computes the least FLOPs."""
    dims = [int(params["lo"])] * (int(params["n_matrices"]) + 1)
    trees = sorted(_trees(0, len(dims) - 2),
                   key=lambda t: sum(g.flops for g in _gemms(t, dims)))
    out: Dict[str, Tree] = {}
    for tree in trees:
        for _ in range(_orders(tree)):
            out[f"algorithm{len(out)}"] = tree
    return out


def gemms(params: Mapping[str, Any]) -> Dict[str, List[Gemm]]:
    """The GEMMs each algorithm runs."""
    dims = [int(params["lo"])] * (int(params["n_matrices"]) + 1)
    return {name: _gemms(tree, dims) for name, tree in algorithms(params).items()}


# ------------------------------------------------------------- reference ---


def inputs(params: Mapping[str, Any]) -> List[Any]:
    """M0..M(n-1) on the device, by the family's documented recipe: one
    PRNG key per matrix split from ``seed``, standard normal float32 entries
    scaled by 1/sqrt(columns)."""
    import jax

    dims = [int(params["lo"])] * (int(params["n_matrices"]) + 1)
    keys = jax.random.split(jax.random.PRNGKey(int(params["seed"])), len(dims) - 1)
    return [jax.random.normal(keys[i], (dims[i], dims[i + 1]), np.float32)
            / np.sqrt(dims[i + 1]) for i in range(len(dims) - 1)]


def reference(params: Mapping[str, Any], operands: str) -> Dict[str, np.ndarray]:
    """Every algorithm's answer: each product of its parenthesization from
    operands rounded to ``operands``; products that several algorithms
    share are computed once."""
    mats = inputs(params)
    done: Dict[Tree, Any] = {}

    def run(tree: Tree):
        if isinstance(tree, int):
            return mats[tree]
        if tree not in done:
            done[tree] = matmul(run(tree[0]), run(tree[1]), operands)
        return done[tree]

    return {name: np.asarray(run(tree), np.float64)
            for name, tree in algorithms(params).items()}


def control(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """The reference in bfloat16, in the program's place: each algorithm's
    parenthesization with every operand and every product in bfloat16."""
    import jax.numpy as jnp

    mats = [m.astype(jnp.bfloat16) for m in inputs(params)]

    def run(tree: Tree):
        if isinstance(tree, int):
            return mats[tree]
        return jnp.dot(run(tree[0]), run(tree[1]))

    return {name: np.asarray(run(tree).astype(jnp.float32))
            for name, tree in algorithms(params).items()}
