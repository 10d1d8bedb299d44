"""The program's kernel-variant family at its ``moe`` site, run at a model's
published widths: one MoE sublayer (sigmoid top-k routing, routed experts
and one ungated shared expert) of the configuration, ranked over a dense
run of every expert, a capacity gather sized so that nothing drops, XLA's
``ragged_dot`` over the assignments sorted by expert, and the Pallas grouped
matmul (megablox ``gmm``) at three m tiles.

Rows, the routing rule, FLOP and byte counts, the plain reference and its
lower-precision control are written here from the configuration's published
widths and the site's documented recipe; nothing is imported from the
program. Each variant carries the FLOPs it executes, so the min-FLOPs set is
``ragged_dot`` alone (the assignments' own rows), and an instance where
another variant ranks better is an anomaly.

The routing (:func:`routing`), on the host in float64: logits standard
normal [T, E] from the instance seed, in place of x W_r (the router GEMM is
not timed); the layer's expert bias ``bias_scale`` z, z standard normal [E]
from (``WEIGHT_SEED``, layer index); the top k of sigmoid(logits) + bias,
ties to the lower index; the weights the chosen scores over their sum +
1e-20, times ``route_scale``.

The work of one call (``gemms``):

- FLOPs: 6 d f per row through the routed experts (three GEMMs of 2 d f) and
  6 d f_s per token for the shared expert. The rows: T E (``dense``), E C
  with C the smallest power of two at or above the hottest expert's load
  (``gather_capacity``), T k (``ragged_dot``), and the m tiles the kernel
  visits times its m tile (``gmm_<tm>``: each non-empty expert's rows
  rounded out to whole tiles, as megablox's ``make_group_metadata`` counts);
- bytes: x read once in bfloat16, the weights of the experts that have rows
  and of the shared expert read once in bfloat16, and the float32 result
  written once (the least any variant can move).
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict, List, Mapping, Tuple

import numpy as np

from bench.families import derive_seed
from bench.families.attention_variants import Work

#: the program's registered family name
FAMILY = "kernel_variants"

#: device trace events by kernel: the grouped matmul's Pallas call is named
#: ``gmm_<tm>x<tk>x<tn>`` (tiles as run), its float32 result [m, n] after it
KERNELS = {
    "gmm": re.compile(r"^%?gmm_(\d+)x(\d+)x(\d+)(?:\.\d+)? = f32\[(\d+),(\d+)\]"),
}

BF16_BYTES, F32_BYTES = 2, 4
#: the site's algorithms as its documentation states them
GMM_TM = (128, 256, 512)
#: the seed of the model's MoE weights and expert biases, with the layer index
WEIGHT_SEED = 0
#: the instance params that restate the configuration's MoE widths
WIDTHS = {"d_model": "hidden_size", "experts": "num_experts", "top_k": "num_experts_per_tok",
          "expert_d_ff": "moe_intermediate_size", "route_scale": "route_scale"}


# ----------------------------------------------------------------- rows ---


def grid(config: Mapping[str, Any], traffic: Mapping[str, Any]) -> Dict[str, Any]:
    """The ``SweepSpec`` grid the cell's census stands for."""
    return {"sites": [config["site"]], "sizes": [int(traffic["size"])], "per_size": 1,
            "config": config["model"]}


def rows(config: Mapping[str, Any], traffic: Mapping[str, Any], seed: int,
         round_no: int) -> List[Tuple[str, Dict[str, Any]]]:
    """(uid, params) of one round's pool: the ``pool`` MoE layers that follow
    the leading dense ones, one instance each; set-up's round (-1) is the
    same pool, so that every layer's weights are made and every capacity
    program compiles before the window."""
    size, first = int(traffic["size"]), int(config["num_dense_layers"])
    shared_f = int(config["moe_intermediate_size"]) * int(config["num_shared_experts"])
    out = []
    for i in range(int(traffic["pool"])):
        params = {
            "site": config["site"], "config": config["model"], "layer": "moe",
            "layer_index": first + i, "size": size, "seed": derive_seed(seed, round_no, i),
            "bias_scale": float(traffic["bias_scale"]),
            **{k: config[v] for k, v in WIDTHS.items()}, "shared_d_ff": shared_f,
        }
        out.append((f"kernel_variants-moe-{config['model']}-l{first + i:02d}-n{size}"
                    f"-r{round_no}-i{i:03d}", params))
    return out


# ------------------------------------------------------------ the routing ---


@functools.lru_cache(maxsize=8)
def _routing(t: int, e: int, k: int, seed: int, layer: int, bias_scale: float,
             route_scale: float) -> Tuple[np.ndarray, np.ndarray]:
    logits = np.random.default_rng(seed).standard_normal((t, e))
    bias = bias_scale * np.random.default_rng([WEIGHT_SEED, layer]).standard_normal(e)
    scores = 1.0 / (1.0 + np.exp(-logits))
    ids = np.argsort(-(scores + bias), axis=1, kind="stable")[:, :k]
    chosen = np.take_along_axis(scores, ids, axis=1)
    chosen = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20)
    return ids.astype(np.int32), (chosen * route_scale).astype(np.float32)


def routing(params: Mapping[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """(expert ids [T, k] int32, weights [T, k] float32) of the instance."""
    return _routing(int(params["size"]), int(params["experts"]), int(params["top_k"]),
                    int(params["seed"]), int(params["layer_index"]),
                    float(params["bias_scale"]), float(params["route_scale"]))


def loads(params: Mapping[str, Any]) -> np.ndarray:
    """Rows routed to each expert [E]."""
    return np.bincount(routing(params)[0].ravel(), minlength=int(params["experts"]))


# ------------------------------------------------------------ the work ---


def algorithms(params: Mapping[str, Any]) -> List[str]:
    return ["dense", "gather_capacity", "ragged_dot"] + [f"gmm_{tm}" for tm in GMM_TM]


def tiles_visited(rows_per_expert: np.ndarray, tm: int) -> int:
    """m tiles of ``tm`` rows the grouped matmul computes: each non-empty
    expert's run of the sorted rows, rounded out to whole tiles."""
    sizes = np.asarray(rows_per_expert, np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return int(np.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 0).sum())


def executed_rows(name: str, params: Mapping[str, Any]) -> int:
    t, e, k = (int(params[n]) for n in ("size", "experts", "top_k"))
    load = loads(params)
    if name == "dense":
        return e * t
    if name == "gather_capacity":
        return e * (1 << (int(load.max()) - 1).bit_length())
    if name == "ragged_dot":
        return t * k
    tm = int(name[len("gmm_"):])
    return tiles_visited(load, tm) * tm


def least_bytes(params: Mapping[str, Any]) -> float:
    t, d, f, sf = (int(params[n]) for n in ("size", "d_model", "expert_d_ff", "shared_d_ff"))
    live = int(np.count_nonzero(loads(params)))
    return float(BF16_BYTES * (t * d + 3 * d * (live * f + sf)) + F32_BYTES * t * d)


def gemms(params: Mapping[str, Any]) -> Dict[str, List[Work]]:
    """Each variant's work in one call."""
    t, d, f, sf = (int(params[n]) for n in ("size", "d_model", "expert_d_ff", "shared_d_ff"))
    shared = 6.0 * d * sf * t
    return {name: [Work(6.0 * d * f * executed_rows(name, params) + shared, least_bytes(params))]
            for name in algorithms(params)}


def gmm_least_seconds(params: Mapping[str, Any], tm: int, n_out: int, peak_flops: float,
                      hbm_bw: float) -> float:
    """The chip's floor for one grouped-matmul call of the instance at m
    tile ``tm`` whose result is ``n_out`` wide: the gate or up GEMM
    (n_out = f, k = d) or the down GEMM (n_out = d, k = f). FLOPs 2 k n per
    executed row; bytes the sorted rows read (bfloat16), the weights of the
    experts that have rows (bfloat16) and the float32 result."""
    t, d, f, top_k = (int(params[n]) for n in ("size", "d_model", "expert_d_ff", "top_k"))
    k_in = d if n_out == f else f
    m = t * top_k
    load = loads(params)
    flops = 2.0 * k_in * n_out * tiles_visited(load, tm) * tm
    moved = (BF16_BYTES * (m * k_in + int(np.count_nonzero(load)) * k_in * n_out)
             + F32_BYTES * m * n_out)
    return Work(flops, float(moved)).least_seconds(peak_flops, hbm_bw)[0]


# ------------------------------------------------------------- answers ---


def _expert_weights(key, d: int, f: int):
    """One expert's (wg, wi [d, f], wo [f, d]) by the site's recipe: three
    keys split from ``key``, normal float32 over sqrt(fan-in), rounded to
    bfloat16."""
    import jax
    import jax.numpy as jnp

    kg, ki, ko = jax.random.split(key, 3)
    shapes = ((d, f, d), (d, f, d), (f, d, f))
    return tuple((jax.random.normal(kk, (a, b), jnp.float32) / np.sqrt(fan)).astype(jnp.bfloat16)
                 for kk, (a, b, fan) in zip((kg, ki, ko), shapes))


def expert_weights(params: Mapping[str, Any], expert: int):
    """Expert ``expert`` of the instance's layer (``experts``: the shared
    expert): key fold_in(fold_in(PRNGKey(WEIGHT_SEED), layer index), expert)."""
    import jax

    d = int(params["d_model"])
    f = int(params["shared_d_ff" if expert == int(params["experts"]) else "expert_d_ff"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(WEIGHT_SEED),
                                                int(params["layer_index"])), expert)
    return _expert_weights(key, d, f)


def inputs(params: Mapping[str, Any]) -> List[Any]:
    """x [T, d]: normal float32 from PRNGKey(seed), rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    t, d = int(params["size"]), int(params["d_model"])
    x = jax.random.normal(jax.random.PRNGKey(int(params["seed"])), (t, d), jnp.float32)
    return [x.astype(jnp.bfloat16)]


@functools.lru_cache(maxsize=None)
def _expert_program(low: bool):
    """acc + w[:, None] * expert(x) for one expert made from its key: in
    float32 at ``HIGHEST`` with every GEMM operand in bfloat16 as the
    configuration states it (x, the weights, and silu(g) * h, rounded once
    for the down GEMM), the reference; or with the GEMMs' results,
    silu(g) * h and the combine left in bfloat16 (``low``, the control)."""
    import jax
    import jax.numpy as jnp

    def expert(key, x, w, acc, f):
        wg, wi, wo = _expert_weights(key, x.shape[1], f)
        if low:
            a = jax.nn.silu(jnp.dot(x, wg)) * jnp.dot(x, wi)
            return acc + w.astype(jnp.bfloat16)[:, None] * jnp.dot(a, wo)
        hi = jax.lax.Precision.HIGHEST
        x, wg, wi, wo = (v.astype(jnp.float32) for v in (x, wg, wi, wo))
        a = jax.nn.silu(jnp.dot(x, wg, precision=hi)) * jnp.dot(x, wi, precision=hi)
        a = a.astype(jnp.bfloat16).astype(jnp.float32)      # the down GEMM's operand
        return acc + w[:, None] * jnp.dot(a, wo, precision=hi)

    return jax.jit(expert, static_argnums=4)


def _layer(params: Mapping[str, Any], low: bool) -> np.ndarray:
    """[T, d]: expert by expert, every token through each, weighted by its
    routing weight (zero off its top k), then the shared expert with
    weight 1."""
    import jax
    import jax.numpy as jnp

    (x,) = inputs(params)
    ids, weights = routing(params)
    t, e = int(params["size"]), int(params["experts"])
    combine = np.zeros((t, e + 1), np.float32)
    np.put_along_axis(combine, ids, weights, axis=1)
    combine[:, e] = 1.0
    layer_key = jax.random.fold_in(jax.random.PRNGKey(WEIGHT_SEED), int(params["layer_index"]))
    acc = jnp.zeros(x.shape, jnp.bfloat16 if low else jnp.float32)
    program = _expert_program(low)
    for i in range(e + 1):
        f = int(params["shared_d_ff" if i == e else "expert_d_ff"])
        acc = program(jax.random.fold_in(layer_key, i), x, jnp.asarray(combine[:, i]), acc, f)
    return np.asarray(acc, np.float64)


def reference(params: Mapping[str, Any], operands: str) -> Dict[str, np.ndarray]:
    """Every variant's answer: the layer in float32 at ``HIGHEST`` from the
    bfloat16 x and weights, silu(g) * h rounded to bfloat16 for the down
    GEMM (``operands``: the stored precision of every GEMM operand)."""
    if operands != "bfloat16":
        raise ValueError(f"MoE operands are stored in bfloat16, not {operands!r}")
    out = _layer(params, low=False)
    return {name: out for name in algorithms(params)}


def control(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """The reference in bfloat16, in the program's place: the GEMMs'
    results, silu(g) * h and the weighted combine left in bfloat16."""
    out = _layer(params, low=True)
    return {name: out for name in algorithms(params)}
