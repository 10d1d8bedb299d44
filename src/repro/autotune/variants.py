"""Variant sites of the repo's kernels and model layers: sets of
mathematically equivalent implementations, each a
:class:`~repro.core.programs.VariantSite` whose variants carry an analytic
FLOP count, so the FLOPs-discriminant test applies directly:

* ``attention_impl``     — reference / chunked:
  equal math; chunked wastes masked-block FLOPs, reference materialises the
  score matrix (memory). Neither FLOPs nor bytes alone predicts the winner
  across shapes — the paper's anomaly regime.
* ``attention_layer``    — one causal attention layer of a model at its
  widths (sliding-window or full): the Pallas flash kernel at three tilings,
  the jnp scans, each with its own executed FLOPs (live blocks only for the
  kernel, the window's span for ``local_chunked``, the rectangle for the
  rest).
* ``gqa_mode``           — grouped vs broadcast: EQUAL FLOPs, different
  memory traffic (K/V repeated g times). Pure equal-FLOPs regime
  (paper Instance B analogue).
* ``moe_dispatch``       — gather vs dense: identical outputs, dense costs
  ~E/top_k x the FLOPs but has no scatter/gather — FLOPs *should*
  discriminate; when it doesn't, that's a textbook anomaly.
* ``moe_layer``          — one MoE sublayer of a model at its widths, on a
  host-routed skewed routing: dense, a capacity gather sized so that
  nothing drops, ``ragged_dot`` over the sorted assignments, and the Pallas
  grouped matmul at three m tiles, each with its own executed FLOPs (every
  token x expert, the padded slots, the assignments, the visited tiles).
* ``ssd_chunk``          — Mamba-2 chunk length: equal leading-order FLOPs.
* ``matmul_blocks``      — Pallas GEMM tile shapes: equal FLOPs exactly;
  native on a TPU, interpreted elsewhere (:func:`pallas_interpret`).

Every variant's program comes from :func:`repro.core.programs.program`,
keyed by its static values, so a second instance of a site builds nothing.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.programs import Variant, VariantSite, program, runner
from repro.core.spans import count, span


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas workload built now runs in the interpreter: never
    on a TPU, always elsewhere. Timing the interpreter on a TPU, or asking
    for a native kernel on another backend, measures the wrong program, so
    an explicit ``interpret`` that disagrees with the backend raises."""
    backend = jax.default_backend()
    expected = backend != "tpu"
    if interpret is not None and bool(interpret) != expected:
        mode = "interpret" if interpret else "native"
        raise ValueError(
            f"Pallas {mode} mode on the {backend!r} backend: kernels run "
            "natively on a TPU and in the interpreter everywhere else"
        )
    return expected


def _attention(name: str, fn, **static):
    """The builder of attention variant ``name``: an unwarmed runner of
    ``fn(q, k, v, **static)`` as the program ``jit_attention_<name>``, kept
    under its static values."""
    return lambda q, k, v: runner(
        program(f"attention_{name}", functools.partial(fn, **static), *sorted(static.items())),
        q, k, v)


def attention_site(
    b: int = 2, s: int = 1024, h: int = 8, kv: int = 2, d: int = 64,
    dtype=jnp.float32,
) -> VariantSite:
    """The toy attention site: the jnp variants at one shared-math FLOP
    count (the score rectangle), whatever each computes."""
    from repro.models.attention import attention_chunked, attention_reference

    def inputs(seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
        v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
        return [q, k, v]

    f_scores = 2.0 * b * h * s * s * d * 2

    return VariantSite(
        name=f"attention[b{b} s{s} h{h}kv{kv} d{d}]",
        variants=(
            Variant("reference_grouped", f_scores,
                    _attention("reference_grouped", attention_reference, gqa="grouped")),
            # K/V repeated to H heads: more traffic, the same FLOPs
            Variant("reference_broadcast", f_scores,
                    _attention("reference_broadcast", attention_reference, gqa="broadcast")),
            # O(s * block) memory, not O(s^2)
            Variant("chunked_flash", f_scores,
                    _attention("chunked_flash", attention_chunked,
                               q_block=min(256, s), kv_block=min(512, s))),
        ),
        make_inputs=inputs,
    )


#: the flash kernel's tilings (block_q, block_k) ranked at a layer's widths
FLASH_TILES = ((128, 512), (256, 512), (512, 1024))
#: q block of ``local_chunked``: each block attends to window + q block keys
LOCAL_Q_BLOCK = 256
#: (q block, kv block) of ``chunked``, the jnp online-softmax scan
CHUNK_BLOCKS = (256, 512)
#: standard deviation of the attention layer's q and k entries (v's is 1):
#: the scores q k^T / sqrt(d) then spread with standard deviation 4, so a
#: row's softmax is peaked as a trained model's is; at 1 it is nearly flat,
#: and scores rounded to bfloat16 barely move the answer
QK_STD = 2.0
#: the largest all-heads float32 score buffer a ``reference_*`` variant may
#: materialise; past it the variant is left out (at s=8192 and 32 heads one
#: buffer is 8.6 GB of a v5e's 16)
SCORE_BUFFER_BYTES = 2**30


def attention_algorithms(b: int, s: int, h: int, window: Optional[int]) -> List[str]:
    """The variants of one attention layer: the flash kernel at each tiling,
    ``local_chunked`` for a windowed layer, the ``chunked`` scan, and the
    ``reference_*`` pair where its score buffer fits."""
    names = [f"flash_{bq}x{bk}" for bq, bk in FLASH_TILES]
    if window is not None:
        names.append("local_chunked")
    names.append("chunked")
    if 4 * b * h * s * s <= SCORE_BUFFER_BYTES:
        names += ["reference_grouped", "reference_broadcast"]
    return names


def _blocks(name: str, s: int, window: Optional[int]) -> Tuple[int, int]:
    """(q block, kv block) of a variant, capped at the sequence as the
    variant caps them; the kv block of ``local_chunked`` is its key span."""
    if name.startswith("flash_"):
        bq, bk = (int(x) for x in name[len("flash_"):].split("x"))
        return min(bq, s), min(bk, s)
    if name == "local_chunked":
        qb = min(LOCAL_Q_BLOCK, s)
        return qb, min(window + qb, s)
    if name == "chunked":
        return min(CHUNK_BLOCKS[0], s), min(CHUNK_BLOCKS[1], s)
    if name.startswith("reference_"):
        return s, s
    raise ValueError(f"unknown attention variant {name!r}")


def attention_score_tiles(name: str, s: int, window: Optional[int]) -> Tuple[int, int]:
    """(rows, cols): variant ``name`` computes rows x cols score entries
    (query, key pairs) in each (batch, head) row of a causal layer of ``s``
    tokens, ``window`` keys wide or full. The counting rule of the site's
    FLOP table and of its kernel decomposition:

    - ``flash_<bq>x<bk>``: (live grid steps x bq, bk), the live steps counted
      with the kernel's own predicate (:func:`grid_steps`); a live block is
      computed whole, its masked entries too, and a dead one not at all;
    - ``local_chunked``: (s, window + q_block), the static key span each q
      block slices (s x s where that span reaches s, as the variant then runs
      the full scan);
    - ``chunked`` and ``reference_*``: the s x s rectangle, masked entries
      computed and discarded.
    """
    # from the defining module: the package-level name can be shadowed by
    # the like-named subpackage after a dotted import (see repro.kernels)
    from repro.kernels.flash_attention.flash_attention import grid_steps

    bq, bk = _blocks(name, s, window)
    if name.startswith("flash_"):
        live, _ = grid_steps(s, s, block_q=bq, block_k=bk, causal=True, window=window)
        return live * bq, bk
    if name == "local_chunked" and bk < s:
        return s, bk
    return s, s


def attention_flops(name: str, *, b: int, s: int, h: int, d: int,
                    window: Optional[int]) -> float:
    """Executed FLOPs of one call: each score entry costs 2d in q @ k^T and
    2d in p @ v (the paper counts multiply-adds of the GEMMs; the softmax's
    exponentials, maxima and sums are not counted)."""
    rows, cols = attention_score_tiles(name, s, window)
    return 4.0 * b * h * d * rows * cols


def check_attention_size(s: int, window: Optional[int]) -> None:
    """Raise unless every variant's blocks divide ``s``."""
    for name in attention_algorithms(1, s, 1, window):
        bq, bk = _blocks(name, s, window)
        if s % bq or (name != "local_chunked" and s % bk):
            raise ValueError(f"attention size {s} is not a multiple of {name}'s "
                             f"blocks ({bq}, {bk})")


def attention_layer_site(
    s: int, h: int, kv: int, d: int, window: Optional[int], b: int = 1,
    interpret: Optional[bool] = None,
) -> VariantSite:
    """One causal attention layer at a model's widths, ``window`` keys wide
    (sliding) or full: q [b, s, h, d] and k, v [b, s, kv, d] in bfloat16
    (normal, q and k at :data:`QK_STD`), GQA by index. Every variant keeps
    the precision contract of :mod:`repro.models.attention` and carries its
    own executed FLOPs
    (:func:`attention_flops`). Building a flash variant counts its grid
    steps into the active span sink (``flash_grid_steps``,
    ``flash_live_steps``, all heads)."""
    from repro.kernels.flash_attention.flash_attention import grid_steps
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.attention import (attention_chunked, attention_local_chunked,
                                        attention_reference)

    check_attention_size(s, window)
    interpret = pallas_interpret(interpret)

    def inputs(seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        shapes = ((b, s, h, d), (b, s, kv, d), (b, s, kv, d))
        return [(jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)
                for key, shape, std in zip(ks, shapes, (QK_STD, QK_STD, 1.0))]

    def build(name):
        bq, bk = _blocks(name, s, window)
        if name.startswith("flash_"):
            make = _attention(name, flash_attention, causal=True, window=window,
                              block_q=bq, block_k=bk, interpret=interpret)
            live, total = grid_steps(s, s, block_q=bq, block_k=bk, causal=True, window=window)

            def counted(q, k, v):
                count("flash_grid_steps", b * h * total)
                count("flash_live_steps", b * h * live)
                return make(q, k, v)

            return counted
        if name == "local_chunked":
            return _attention(name, attention_local_chunked, window=window, q_block=bq)
        if name == "chunked":
            return _attention(name, attention_chunked, causal=True, window=window,
                              q_block=bq, kv_block=bk)
        return _attention(name, attention_reference, causal=True, window=window,
                          gqa=name[len("reference_"):])

    kind = "full" if window is None else f"swa{window}"
    return VariantSite(
        name=f"attention[{kind} b{b} s{s} h{h}kv{kv} d{d}]",
        variants=tuple(
            Variant(name, attention_flops(name, b=b, s=s, h=h, d=d, window=window),
                    build(name))
            for name in attention_algorithms(b, s, h, window)
        ),
        make_inputs=inputs,
    )


# ------------------------------------------------------------- MoE site ----

def moe_dispatch_site(
    tokens: int = 2048, d: int = 256, e: int = 8, top_k: int = 2, d_ff: int = 128,
    dtype=jnp.float32,
) -> VariantSite:
    from repro.models import ModelConfig
    from repro.models.moe import init_moe, moe_dense, moe_gather
    from repro.models.layers import split_params

    cfg = ModelConfig(
        name="site-moe", n_layers=2, d_model=d, n_heads=4, n_kv_heads=4,
        d_ff=d_ff, vocab_size=128, n_experts=e, top_k=top_k, moe_d_ff=d_ff,
        dtype="float32", param_dtype="float32",
    )
    params, _ = split_params(init_moe(cfg, jax.random.PRNGKey(7)))

    def inputs(seed: int):
        x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, d), dtype)
        return [x]

    f_expert = 6.0 * tokens * d * d_ff  # 3 gemms x 2
    f_gather = f_expert * top_k * cfg.moe_capacity_factor + 2.0 * tokens * d * e
    f_dense = f_expert * e + 2.0 * tokens * d * e

    def build(name, dispatch):
        # the expert weights are arguments, so one program serves every
        # instance of the same config
        return lambda x: runner(
            program(name, lambda params, x: dispatch(cfg, params, x)[0], cfg), params, x)

    return VariantSite(
        name=f"moe_dispatch[T{tokens} E{e} k{top_k}]",
        variants=(
            Variant("gather", f_gather, build("gather", moe_gather)),
            Variant("dense", f_dense, build("dense", moe_dense)),
        ),
        make_inputs=inputs,
    )


# ------------------------------------------------------- MoE layer site ----

#: m tiles of the ``gmm_<tm>`` variants (the megablox grouped matmul)
GMM_TM = (128, 256, 512)
#: (k tile, n tile) of every ``gmm_<tm>`` variant, capped at the GEMM's dims
GMM_TK_TN = (512, 512)
#: tokens per step of the ``dense`` variant: at 128 experts of width 1024
#: over d 2048, ~1 GB of float32 intermediates per step
DENSE_BLOCK = 512
#: the per-layer expert bias b = MOE_BIAS_SCALE * z (z standard normal),
#: added to standard-normal router logits' sigmoid scores for selection:
#: the routing skew of a sequence whose topic loads a few experts
MOE_BIAS_SCALE = 0.05
#: the seed of a model's MoE weights and expert biases, with the layer index
MOE_WEIGHT_SEED = 0


def moe_algorithms() -> List[str]:
    """The MoE layer's algorithms, every one exact (no token dropped)."""
    return ["dense", "gather_capacity", "ragged_dot"] + [f"gmm_{tm}" for tm in GMM_TM]


@dataclass(frozen=True)
class MoeRouting:
    """One instance's routing: expert ids [T, k] int32 and weights [T, k]
    float32 per token, and the rows per expert [E]."""

    ids: np.ndarray
    weights: np.ndarray
    loads: np.ndarray

    @property
    def capacity(self) -> int:
        """The ``gather_capacity`` slots per expert: the smallest power of
        two at or above the hottest expert's load, so nothing drops."""
        return 1 << max(int(self.loads.max()) - 1, 0).bit_length()


def sigmoid_topk(logits: np.ndarray, bias: np.ndarray, k: int, route_scale: float,
                 norm_topk: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """The published router (DeepSeek-V3's, AFMoE's) on the host, in
    float64: scores sigmoid(logits) [T, E]; the top ``k`` of scores + bias,
    ties to the lower index; the weights the chosen scores, over their sum
    + 1e-20 where ``norm_topk``, times ``route_scale`` (the bias never enters
    them). Returns (ids [T, k] int32, weights [T, k] float32)."""
    scores = 1.0 / (1.0 + np.exp(-logits))
    ids = np.argsort(-(scores + bias), axis=1, kind="stable")[:, :k]
    chosen = np.take_along_axis(scores, ids, axis=1)
    if norm_topk:
        chosen = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20)
    return ids.astype(np.int32), (chosen * route_scale).astype(np.float32)


@functools.lru_cache(maxsize=8)
def moe_routing(t: int, e: int, k: int, seed: int, layer: int, bias_scale: float,
                route_scale: float, norm_topk: bool) -> MoeRouting:
    """One instance's routing by :func:`sigmoid_topk`, on logits drawn in
    place of x W_r: standard normal [T, E] from ``seed``; the layer's expert
    bias ``bias_scale * z``, z standard normal [E] from
    (:data:`MOE_WEIGHT_SEED`, ``layer``). Once per instance (kept), in the
    span ``moe.route``; counts ``moe_assignments`` (T k) and
    ``moe_peak_load`` (the hottest expert's rows)."""
    with span("moe.route", "route_s"):
        logits = np.random.default_rng(seed).standard_normal((t, e))
        bias = bias_scale * np.random.default_rng([MOE_WEIGHT_SEED, layer]).standard_normal(e)
        ids, weights = sigmoid_topk(logits, bias, k, route_scale, norm_topk)
        routing = MoeRouting(ids, weights, np.bincount(ids.ravel(), minlength=e))
    count("moe_assignments", t * k)
    count("moe_peak_load", int(routing.loads.max()))
    return routing


def moe_executed_rows(name: str, t: int, e: int, k: int, routing: MoeRouting) -> int:
    """Rows one variant runs through each routed expert GEMM: every token
    through every expert (``dense``), every expert's padded slots
    (``gather_capacity``), the assignments (``ragged_dot``: the math
    count, whatever XLA executes), and the m tiles the kernel visits times
    its m tile (``gmm_<tm>``, counted as ``make_group_metadata`` counts)."""
    from repro.kernels.gmm.gmm import tiles_visited

    if name == "dense":
        return e * t
    if name == "gather_capacity":
        return e * routing.capacity
    if name == "ragged_dot":
        return t * k
    if name.startswith("gmm_"):
        tm = int(name[len("gmm_"):])
        return tiles_visited(routing.loads, tm) * tm
    raise ValueError(f"unknown MoE variant {name!r}")


def moe_rows_moved(name: str, t: int, e: int, k: int, routing: MoeRouting) -> int:
    """Rows a variant gathers into expert order, and again back to token
    order in the combine: none for ``dense``."""
    if name == "dense":
        return 0
    return e * routing.capacity if name == "gather_capacity" else t * k


def check_moe_size(t: int, k: int) -> None:
    """Raise unless the assignments fill whole m tiles of every gmm variant
    and the dense variant's blocks."""
    if (t * k) % max(GMM_TM) or t % min(DENSE_BLOCK, t):
        raise ValueError(f"MoE size {t} x top-{k} is not a multiple of the gmm m tile "
                         f"{max(GMM_TM)} and the dense block {DENSE_BLOCK}")


def _expert_weights(key, d: int, f: int):
    """One expert's (wg, wi [d, f], wo [f, d]) in bfloat16: normal float32
    scaled by 1/sqrt(fan-in), from three keys split from ``key``."""
    kg, ki, ko = jax.random.split(key, 3)
    scale = ((d, f, d), (d, f, d), (f, d, f))
    return tuple((jax.random.normal(kk, (a, b), jnp.float32) / np.sqrt(fan)).astype(jnp.bfloat16)
                 for kk, (a, b, fan) in zip((kg, ki, ko), scale))


@functools.lru_cache(maxsize=4)
def moe_layer_weights(layer: int, d: int, e: int, f: int, shared_f: int):
    """One MoE layer's weights on the device, made once per process and held
    (four layers, as a pipeline stage holds its layers): routed experts
    ``wg``/``wi`` [E, d, f], ``wo`` [E, f, d] and the shared expert [d, f_s]
    / [f_s, d] in bfloat16. Expert ``i`` draws from
    fold_in(fold_in(PRNGKey(MOE_WEIGHT_SEED), layer), i), the shared expert
    as expert ``E``."""

    def make(layer):
        key = jax.random.fold_in(jax.random.PRNGKey(MOE_WEIGHT_SEED), layer)
        wg, wi, wo = jax.lax.map(
            lambda i: _expert_weights(jax.random.fold_in(key, i), d, f), jnp.arange(e))
        sg, si, so = _expert_weights(jax.random.fold_in(key, e), d, shared_f)
        return {"wg": wg, "wi": wi, "wo": wo}, {"wg": sg, "wi": si, "wo": so}

    return program("moe_layer_weights", make, d, e, f, shared_f)(layer)


def moe_flops(name: str, *, t: int, d: int, e: int, k: int, f: int, shared_f: int,
              routing: MoeRouting) -> float:
    """Executed FLOPs of one call: 6 d f per executed row of the routed
    experts (three GEMMs of 2 d f), and 6 d f_s per token of the shared
    expert (the paper counts the GEMMs' multiply-adds; silu, the products
    and the combine's adds are not counted)."""
    return 6.0 * d * f * moe_executed_rows(name, t, e, k, routing) + 6.0 * d * shared_f * t


def moe_layer_site(
    t: int, d: int, e: int, k: int, f: int, shared_f: int, layer: int, seed: int,
    route_scale: float, norm_topk: bool = True, bias_scale: float = MOE_BIAS_SCALE,
    interpret: Optional[bool] = None,
) -> VariantSite:
    """One MoE sublayer at a model's widths, for the instance ``seed`` (the
    routing, and so the FLOP table, is the seed's): T tokens x [T, d] in
    bfloat16 (standard normal, from the seed), routed to ``k`` of ``e`` experts of
    width ``f`` by the host router (:func:`moe_routing`) plus one ungated
    shared expert of width ``shared_f``; the layer's weights from
    :func:`moe_layer_weights`. Each variant takes (weights, x, ids, routing
    weights), returns [T, d] float32 under the precision contract of
    :mod:`repro.models.moe`, drops no token, and carries its executed FLOPs
    (6 d f per executed row, :func:`moe_executed_rows`, plus the shared
    expert's 6 d f_s per token). The router GEMM x W_r is not timed. A gmm
    variant's build counts ``gmm_assigned_rows`` (T k) and
    ``gmm_executed_rows`` into the active span sink."""
    from repro.kernels.gmm.gmm import gmm
    from repro.models.moe import (experts_capacity, experts_dense, experts_grouped,
                                  ragged_matmul, shared_expert)

    check_moe_size(t, k)
    interpret = pallas_interpret(interpret)
    route = functools.partial(moe_routing, t, e, k, layer=layer, bias_scale=bias_scale,
                              route_scale=route_scale, norm_topk=norm_topk)

    def inputs(seed: int):
        routing = route(seed)
        x = jax.random.normal(jax.random.PRNGKey(seed), (t, d), jnp.float32).astype(jnp.bfloat16)
        return [x, jnp.asarray(routing.ids), jnp.asarray(routing.weights), routing]

    def gmm_matmul(tm):
        def matmul(lhs, rhs, sizes):
            kk, n = rhs.shape[1:]
            tiling = (tm, min(GMM_TK_TN[0], kk), min(GMM_TK_TN[1], n))
            return gmm(lhs, rhs, sizes, tiling=tiling, interpret=interpret)

        return matmul

    def layer_fn(experts):
        def fn(w, x, ids, wts):
            routed, shared = w
            return experts(x, ids, wts, routed) + shared_expert(x, shared)

        return fn

    def build(name):
        def make(x, ids, wts, routing):
            if name == "dense":
                fn, key = layer_fn(functools.partial(experts_dense, block=DENSE_BLOCK)), ()
            elif name == "gather_capacity":
                c = routing.capacity
                fn, key = layer_fn(functools.partial(experts_capacity, capacity=c)), (c,)
            elif name == "ragged_dot":
                fn, key = layer_fn(functools.partial(experts_grouped, matmul=ragged_matmul)), ()
            else:
                tm = int(name[len("gmm_"):])
                fn = layer_fn(functools.partial(experts_grouped, matmul=gmm_matmul(tm)))
                key = (interpret,)
                count("gmm_assigned_rows", t * k)
                count("gmm_executed_rows", moe_executed_rows(name, t, e, k, routing))
            weights = moe_layer_weights(layer, d, e, f, shared_f)
            return runner(program(f"moe_{name}", fn, *key), weights, x, ids, wts)

        return make

    routing = route(seed)
    return VariantSite(
        name=f"moe[T{t} d{d} E{e} k{k} f{f} shared{shared_f} layer{layer} seed{seed}]",
        variants=tuple(
            Variant(name, moe_flops(name, t=t, d=d, e=e, k=k, f=f, shared_f=shared_f,
                                    routing=routing), build(name))
            for name in moe_algorithms()),
        make_inputs=inputs,
    )


# ------------------------------------------------------------- SSD site ----

def ssd_chunk_site(
    b: int = 2, s: int = 2048, h: int = 8, p: int = 32, n: int = 32,
    chunks: Sequence[int] = (64, 128, 256, 512),
    dtype=jnp.float32,
) -> VariantSite:
    from repro.models.mamba2 import ssd_chunked

    def inputs(seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        x = jax.random.normal(ks[0], (b, s, h, p), dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a_log = jax.random.normal(ks[2], (h,)) * 0.5
        bm = jax.random.normal(ks[3], (b, s, 1, n))
        cm = jax.random.normal(ks[4], (b, s, 1, n))
        return [x, dt, a_log, bm, cm]

    def make(chunk):
        def fn(x, dt, a_log, bm, cm):
            return ssd_chunked(x, dt, a_log, bm, cm, chunk)[0]

        return lambda *arrays: runner(program(f"chunk_{chunk}", fn, chunk), *arrays)

    def flops(q):
        return b * s * h * (2.0 * q * n + 2.0 * q * p + 4.0 * p * n)

    return VariantSite(
        name=f"ssd_chunk[s{s} h{h} p{p} n{n}]",
        variants=tuple(
            Variant(f"chunk_{q}", flops(q), make(q)) for q in chunks
        ),
        make_inputs=inputs,
    )


# ---------------------------------------------------------- matmul site ----

def matmul_blocks_site(
    m: int = 1024, k: int = 1024, n: int = 1024,
    blocks: Sequence[tuple] = ((128, 128, 128), (256, 256, 256), (512, 512, 256)),
    dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> VariantSite:
    from repro.kernels.matmul.matmul import matmul_kernel

    interpret = pallas_interpret(interpret)

    def inputs(seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        a = jax.random.normal(ks[0], (m, k), dtype)
        b_ = jax.random.normal(ks[1], (k, n), dtype)
        return [a, b_]

    f = 2.0 * m * k * n

    def make(bm, bn, bk):
        # every tiling is the program ``jit_matmul``, kept under its tiles
        kernel = functools.partial(matmul_kernel, block_m=bm, block_n=bn, block_k=bk,
                                   interpret=interpret)
        return lambda a, b_: runner(program("matmul", kernel, bm, bn, bk, interpret), a, b_)

    variants = tuple(
        Variant(f"blocks_{bm}x{bn}x{bk}", f, make(bm, bn, bk)) for bm, bn, bk in blocks
    ) + (
        Variant("xla_dot", f, lambda a, b_: runner(program("xla_dot", jnp.dot), a, b_)),
    )
    return VariantSite(
        name=f"matmul[{m}x{k}x{n}]", variants=variants, make_inputs=inputs
    )
