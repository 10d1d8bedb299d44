"""The program's kernel-variant family at its ``attention`` site, run at a
model's published widths: one causal attention layer (sliding-window or
full) of the configuration, ranked over the repo's Pallas flash kernel at
three tilings and the jnp scans.

Rows, FLOP and byte counts, the plain reference and its lower-precision
control are written here from the configuration's published widths and the
site's documented recipe; nothing is imported from the program. Each
variant carries the FLOPs it executes, so the min-FLOPs set is the variants
that skip the most score blocks, and a rank split among FLOP-equal variants
or a loss of the min-FLOPs set to a costlier one is an anomaly.

The work of one call (``gemms``):

- FLOPs: 4 d per score entry computed (2 d in q @ k^T, 2 d in p @ v); the
  flash kernel computes each live block whole (a block is live where any of
  its query, key pairs is visible, counted here pair by pair), the
  ``local_chunked`` scan each q block's static key span (window + q block),
  and the rest the whole s x s rectangle;
- bytes: q, k, v read once and o written once, in bfloat16 (the least any
  variant can move).
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from bench.families import derive_seed

#: the program's registered family name
FAMILY = "kernel_variants"

#: device trace events by kernel: the flash kernel's Pallas call is named
#: ``flash_<causal|swa<window>>_<block_q>x<block_k>`` (blocks as run)
KERNELS = {
    "flash": re.compile(r"^%?flash_(?:causal|swa(\d+))_(\d+)x(\d+)(?:\.\d+)? = "),
}

#: bytes of one bfloat16 element, the dtype q, k, v and o are stored in
BF16_BYTES = 2
#: the site's algorithms as its documentation states them
FLASH_TILES = ((128, 512), (256, 512), (512, 1024))
LOCAL_Q_BLOCK = 256
#: a ``reference_*`` variant runs where one all-heads f32 score buffer fits this
SCORE_BUFFER_BYTES = 2**30
#: standard deviation of the q and k entries (v's is 1): scores of standard
#: deviation 4, a peaked softmax, where scores rounded to bfloat16 move the
#: answer well past what the stated precision does
QK_STD = 2.0
#: the configuration's ``layer_types`` by the program's layer kind names
LAYER_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


@dataclass(frozen=True)
class Work:
    """One call of one variant: the FLOPs it executes, the bytes it must
    move at least."""

    flops: float
    bytes: float

    def least_seconds(self, peak_flops: float, hbm_bw: float) -> Tuple[float, str]:
        """The chip's floor for this call, and which term sets it."""
        compute, memory = self.flops / peak_flops, self.bytes / hbm_bw
        return (compute, "flops") if compute >= memory else (memory, "bytes")


# ----------------------------------------------------------------- rows ---


def period(config: Mapping[str, Any]) -> List[str]:
    """The layer kinds of one period of the model's layer pattern."""
    n = int(config["global_attn_every_n_layers"])
    return [LAYER_KINDS[t] for t in config["layer_types"][:n]]


def grid(config: Mapping[str, Any], traffic: Mapping[str, Any]) -> Dict[str, Any]:
    """The ``SweepSpec`` grid the cell's census stands for."""
    return {"sites": [config["site"]], "sizes": [int(traffic["size"])], "per_size": 1,
            "config": config["model"]}


def rows(config: Mapping[str, Any], traffic: Mapping[str, Any], seed: int,
         round_no: int) -> List[Tuple[str, Dict[str, Any]]]:
    """(uid, params) of one round's pool: ``pool`` layers in the order of the
    model's layer pattern; ``round_no`` -1 is set-up's, one layer of each
    kind, so that every program compiles before the window."""
    size, kinds = int(traffic["size"]), period(config)
    if round_no >= 0:
        layers = [kinds[i % len(kinds)] for i in range(int(traffic["pool"]))]
    else:
        layers = sorted(set(kinds), key=kinds.index)
    out = []
    for i, layer in enumerate(layers):
        params = {
            "site": config["site"], "config": config["model"], "layer": layer, "size": size,
            "seed": derive_seed(seed, round_no, i),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "window": int(config["sliding_window"]) if layer == "sliding" else None,
        }
        out.append((f"kernel_variants-attention-{config['model']}-{layer}-n{size}"
                    f"-r{round_no}-i{i:03d}", params))
    return out


# ------------------------------------------------------------ the work ---


def algorithms(params: Mapping[str, Any]) -> List[str]:
    """The flash kernel at three tilings, ``local_chunked`` on a windowed
    layer, the ``chunked`` scan, and the ``reference_*`` pair where its
    score buffer fits."""
    s, h = int(params["size"]), int(params["heads"])
    names = [f"flash_{bq}x{bk}" for bq, bk in FLASH_TILES]
    if params["window"] is not None:
        names.append("local_chunked")
    names.append("chunked")
    if 4 * h * s * s <= SCORE_BUFFER_BYTES:
        names += ["reference_grouped", "reference_broadcast"]
    return names


def visible(s: int, window: Optional[int]) -> np.ndarray:
    """[s, s] bool: key ``j`` is visible to query ``i`` in a causal layer,
    ``window`` keys wide or full."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return (j <= i) & (j > i - window) if window is not None else j <= i


@functools.lru_cache(maxsize=16)
def live_blocks(s: int, block_q: int, block_k: int, window: Optional[int]) -> int:
    """Blocks of a [block_q, block_k] tiling (capped at ``s``) that hold a
    visible query, key pair: those the flash kernel computes."""
    bq, bk = min(block_q, s), min(block_k, s)
    blocks = visible(s, window).reshape(s // bq, bq, s // bk, bk)
    return int(blocks.any(axis=(1, 3)).sum())


def score_entries(name: str, s: int, window: Optional[int]) -> int:
    """Score entries one (batch, head) row of a variant computes."""
    if name.startswith("flash_"):
        bq, bk = (int(x) for x in name[len("flash_"):].split("x"))
        return live_blocks(s, bq, bk, window) * min(bq, s) * min(bk, s)
    if name == "local_chunked":
        span = window + min(LOCAL_Q_BLOCK, s)
        return s * span if span < s else s * s
    return s * s


def least_bytes(params: Mapping[str, Any]) -> float:
    s, h, kv, d = (int(params[k]) for k in ("size", "heads", "kv_heads", "head_dim"))
    return float(BF16_BYTES * s * d * (2 * h + 2 * kv))


def gemms(params: Mapping[str, Any]) -> Dict[str, List[Work]]:
    """Each variant's work in one call (one batch row)."""
    s, h, d = int(params["size"]), int(params["heads"]), int(params["head_dim"])
    return {name: [Work(4.0 * h * d * score_entries(name, s, params["window"]),
                        least_bytes(params))]
            for name in algorithms(params)}


def flash_least_seconds(params: Mapping[str, Any], window: Optional[int], block_q: int,
                        block_k: int, peak_flops: float, hbm_bw: float) -> float:
    """The chip's floor for one flash call of the row's shape, ``window``
    keys wide (None: full) at the tiling the kernel ran."""
    s, h, d = int(params["size"]), int(params["heads"]), int(params["head_dim"])
    flops = 4.0 * h * d * live_blocks(s, block_q, block_k, window) * block_q * block_k
    return Work(flops, least_bytes(params)).least_seconds(peak_flops, hbm_bw)[0]


# ------------------------------------------------------------- answers ---


def inputs(params: Mapping[str, Any]) -> List[Any]:
    """q [1, s, h, d] and k, v [1, s, kv, d] on the device, by the site's
    recipe: three PRNG keys split from ``seed``, normal float32 entries (q
    and k at ``QK_STD``, v standard) rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    s, h, kv, d = (int(params[k]) for k in ("size", "heads", "kv_heads", "head_dim"))
    keys = jax.random.split(jax.random.PRNGKey(int(params["seed"])), 3)
    shapes = ((1, s, h, d), (1, s, kv, d), (1, s, kv, d))
    return [(jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)
            for key, shape, std in zip(keys, shapes, (QK_STD, QK_STD, 1.0))]


@functools.lru_cache(maxsize=None)
def _head_program(window: Optional[int], low: bool):
    """softmax(q k^T / sqrt(d), causal + window mask) v for one head, [s, d]
    each: in float32 at ``HIGHEST`` (the reference), or with the scores,
    the softmax and p @ v left in bfloat16 (``low``, the control)."""
    import jax
    import jax.numpy as jnp

    def head(q, k, v):
        s, d = q.shape
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        allowed = (j <= i) & (j > i - window) if window is not None else j <= i
        if low:
            scores = jnp.dot(q, k.T) * jnp.bfloat16(1.0 / np.sqrt(d))
            p = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
            return jnp.dot(p, v)
        hi = jax.lax.Precision.HIGHEST
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        scores = jnp.dot(q, k.T, precision=hi) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.dot(p, v, precision=hi)

    return jax.jit(head)


def _attend(params: Mapping[str, Any], low: bool) -> np.ndarray:
    """[1, s, h, d]: every head on its own, q head ``i`` with kv head
    ``i // (h / kv)``."""
    q, k, v = inputs(params)
    h, kv = int(params["heads"]), int(params["kv_heads"])
    program = _head_program(params["window"], low)
    out = [np.asarray(program(q[0, :, i], k[0, :, i // (h // kv)], v[0, :, i // (h // kv)]),
                      np.float64) for i in range(h)]
    return np.stack(out, axis=1)[None]


def reference(params: Mapping[str, Any], operands: str) -> Dict[str, np.ndarray]:
    """Every variant's answer: per-head attention in float32 at ``HIGHEST``
    from the bfloat16 q, k, v (``operands``: the stored precision, which
    the inputs already have)."""
    if operands != "bfloat16":
        raise ValueError(f"attention operands are stored in bfloat16, not {operands!r}")
    out = _attend(params, low=False)
    return {name: out for name in algorithms(params)}


def control(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """The reference in bfloat16, in the program's place: scores, softmax
    and p @ v left in bfloat16 for every variant."""
    out = _attend(params, low=True)
    return {name: out for name in algorithms(params)}
