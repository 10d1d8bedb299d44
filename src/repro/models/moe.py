"""Mixture-of-Experts FFN with mathematically equivalent dispatches.

* ``gather``  — the sparse dispatch. Where the config drops tokens
  (``moe_drop_tokens``, GShard semantics): top-k routing, position within
  expert via cumsum, gather [E, C, d] -> expert GEMMs -> weighted
  scatter-add. FLOPs proportional to *active* parameters (the production
  path). Tokens overflowing an expert's capacity are dropped;
  capacity_factor trades drop rate for padding waste. Where it drops none
  (Trinity-Mini / AFMoE): the assignments sorted by expert and the expert
  GEMMs run over the sorted rows (``jax.lax.ragged_dot``), exactly the
  active rows.
* ``dense``   — every token runs every expert; routing weights (zero for
  unselected experts) combine the results. No gather/scatter memory ops but
  ~E/top_k x more FLOPs. With no capacity drops the dispatches are
  bit-identical in exact arithmetic — the equal-*result*, different-FLOPs
  regime of the paper's discriminant test (see repro.autotune).

The router is a softmax over the experts, or (``moe_score_func="sigmoid"``,
DeepSeek-V3 / AFMoE) a sigmoid per expert whose top-k is chosen on the
scores plus a per-expert bias that never enters the weights; the weights
are renormalised (``moe_norm_topk``) and scaled by ``moe_route_scale``.

The expert paths (``experts_dense``, ``experts_capacity``,
``experts_grouped``, ``shared_expert``) take the routing as arguments, so
the model's dispatches and a census site that times the expert work alone
on one routing run the same code. GEMMs multiply the operands' dtype and
accumulate to ``acc``; act(g) * h is computed in ``acc`` and rounded to the
operands' dtype for the down projection. The census site takes ``acc``
float32; the model's dispatches take the activations' dtype (the grouped
GEMMs accumulate to float32 in both).

TPU adaptation: everything is static-shape einsum + cumsum + scatter — no
dynamic shapes, MXU-friendly; expert dim shards over "model" (EP) when
divisible, else per-expert d_ff shards over "model" (TP-in-expert).
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import Params, normal_init, param_dtype, zeros_init


def init_moe(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = param_dtype(cfg)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.resolved_moe_d_ff
    keys = jax.random.split(key, 8)
    out_std = 0.02 / np.sqrt(2 * cfg.n_layers)
    params: Params = {
        "router": normal_init(keys[0], (d, e), ("embed", None), dt),
        "wi": normal_init(keys[1], (e, d, f), ("experts", "embed", "moe_ffn"), dt),
        "wg": normal_init(keys[2], (e, d, f), ("experts", "embed", "moe_ffn"), dt),
        "wo": normal_init(keys[3], (e, f, d), ("experts", "moe_ffn", "embed"), dt, out_std),
    }
    if cfg.moe_score_func == "sigmoid":
        # selection-only bias of aux-loss-free balancing (zero at init)
        params["expert_bias"] = zeros_init((e,), (None,), jnp.float32)
    if cfg.n_shared_experts > 0:
        sf = cfg.resolved_shared_d_ff
        params["shared"] = {
            "wi": normal_init(keys[4], (d, sf), ("embed", "ffn"), dt),
            "wg": normal_init(keys[5], (d, sf), ("embed", "ffn"), dt),
            "wo": normal_init(keys[6], (sf, d), ("ffn", "embed"), dt, out_std),
        }
        if cfg.moe_shared_gate:
            params["shared"]["gate"] = normal_init(keys[7], (d, 1), ("embed", None), dt)
    return params


def _routing(
    cfg: ModelConfig, params: Params, x2d: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Router: probs [T, E], top-k weights [T, k], indices [T, k], aux loss."""
    logits = jnp.einsum(
        "td,de->te", x2d.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    if cfg.moe_score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(scores + params["expert_bias"].astype(jnp.float32), cfg.top_k)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
        if cfg.moe_norm_topk:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)   # for the aux loss
    elif cfg.moe_score_func == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
        if cfg.moe_norm_topk:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    else:
        raise ValueError(f"unknown MoE score function {cfg.moe_score_func!r}")
    if cfg.moe_route_scale != 1.0:
        top_w = top_w * cfg.moe_route_scale
    # Switch-style load-balance aux loss.
    t = x2d.shape[0]
    e = cfg.n_experts
    dispatch = jax.nn.one_hot(top_i, e, dtype=jnp.float32)        # [T, k, E]
    frac_tokens = jnp.mean(jnp.sum(dispatch, axis=1), axis=0)      # [E]
    frac_probs = jnp.mean(probs, axis=0)                           # [E]
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return probs, top_w, top_i, aux


def _dispatch_table(
    top_i: jax.Array, t: int, e: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(expert of each assignment [T*k], its position within the expert
    [T*k], token ids by (expert, slot) [E, C], T where the slot is empty).
    Assignments at a position >= ``capacity`` are dropped from the table."""
    k = top_i.shape[1]
    # Position of each assignment within its expert, sort-based: argsort
    # groups assignments by expert; the position is the rank within the
    # expert's run. Integer-only (no [T*k, E] one-hot/cumsum tensors in the
    # fwd or bwd graph — §Perf iteration on granite-moe).
    flat_e = top_i.reshape(-1)                            # [T*k]
    order = jnp.argsort(flat_e, stable=True)              # [T*k]
    counts = jnp.bincount(flat_e, length=e)               # [E]
    starts = jnp.cumsum(counts) - counts                  # exclusive [E]
    sorted_e = flat_e[order]
    pos_sorted = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros_like(flat_e).at[order].set(pos_sorted)

    token_of = jnp.tile(jnp.arange(t)[:, None], (1, k)).reshape(-1)
    # Scatter token ids into the dispatch table. Overflowing assignments have
    # pos >= capacity, i.e. out-of-bounds — mode="drop" discards them without
    # clobbering legitimate slots. Unfilled slots keep the sentinel T.
    disp = jnp.full((e, capacity), t, dtype=jnp.int32)
    disp = disp.at[flat_e, pos].set(token_of, mode="drop")
    return flat_e, pos, disp


def _with_shared(cfg: ModelConfig, params: Params, x2d: jax.Array,
                 out: jax.Array) -> jax.Array:
    """``out`` plus the shared expert, under its sigmoid gate where the
    config has one (Qwen2-MoE; Trinity-Mini's is ungated)."""
    if cfg.n_shared_experts == 0:
        return out
    sp = params["shared"]
    y = shared_expert(x2d, sp, acc=x2d.dtype)
    if cfg.moe_shared_gate:
        y = y * jax.nn.sigmoid(
            jnp.einsum("td,do->to", x2d.astype(jnp.float32), sp["gate"].astype(jnp.float32))
        ).astype(x2d.dtype)
    return out + y


def moe_gather(
    cfg: ModelConfig, params: Params, x2d: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based gather dispatch. x2d [T, d] -> ([T, d], aux)."""
    t = x2d.shape[0]
    capacity = int(np.ceil(t * cfg.top_k * cfg.moe_capacity_factor / cfg.n_experts))
    capacity = max(4, min(t, (capacity + 3) // 4 * 4))
    _, top_w, top_i, aux = _routing(cfg, params, x2d)
    out = experts_capacity(x2d, top_i, top_w, params, capacity, acc=x2d.dtype,
                           activation=cfg.activation)
    return _with_shared(cfg, params, x2d, out), aux


def moe_grouped(
    cfg: ModelConfig, params: Params, x2d: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """No-drop sorted dispatch (``ragged_dot``). x2d [T, d] -> ([T, d], aux)."""
    _, top_w, top_i, aux = _routing(cfg, params, x2d)
    out = experts_grouped(x2d, top_i, top_w, params, ragged_matmul,
                          activation=cfg.activation)
    return _with_shared(cfg, params, x2d, out.astype(x2d.dtype)), aux


def moe_dense(
    cfg: ModelConfig, params: Params, x2d: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Dense dispatch: all tokens x all experts, combine by routing weight."""
    _, top_w, top_i, aux = _routing(cfg, params, x2d)
    out = experts_dense(x2d, top_i, top_w, params, block=x2d.shape[0], acc=x2d.dtype,
                        activation=cfg.activation)
    return _with_shared(cfg, params, x2d, out.astype(x2d.dtype)), aux


def apply_moe(
    cfg: ModelConfig,
    params: Params,
    x: jax.Array,                  # [b, s, d]
    dispatch: str = "gather",
    shardings=None,
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch per GROUP (= batch row), GShard-style.

    Flattening b*s and dispatching over GLOBAL tokens makes the
    position-cumsum a cross-shard dependency, so GSPMD de-shards the whole
    dispatch (audited on qwen2-moe train_4k: 2 TB/device gathered tokens +
    460 GB/device scatter-add all-reduces). Group-local dispatch keeps the
    batch dim sharded end-to-end; per-group capacity is the standard GShard
    load-balancing semantics. A config that drops no token has no capacity,
    and its ``gather`` sorts the batch's assignments together
    (``ragged_dot`` does not batch over shared expert weights).

    ``shardings`` (dict wi/wg/wo -> NamedSharding) pins the COMPUTE-time
    weight layout: expert weights are ZeRO-stored with d_model sharded over
    'data', and without the pin GSPMD sometimes resolves the d-contraction
    by all-reducing f32 partial sums (audited: 260 GB/device per AR on
    qwen2-moe) instead of gathering the ~1 GB of weights.
    """
    b, s, d = x.shape
    # Apply the compute-layout pin OUTSIDE the vmap: a constraint inside the
    # vmapped body broadcasts the (unbatched) weights across groups
    # (refuted §Perf iteration: 64x weight materialisation, tc x6).
    if shardings:
        params = dict(params)
        for k in ("wi", "wg", "wo"):
            if k in shardings and shardings[k] is not None:
                params[k] = jax.lax.with_sharding_constraint(params[k], shardings[k])
    if dispatch == "gather" and cfg.moe_drop_tokens:
        y, aux = jax.vmap(
            lambda xr: moe_gather(cfg, params, xr), in_axes=0, out_axes=0
        )(x)
        return y, jnp.mean(aux)
    if dispatch in ("gather", "dense"):
        layer = moe_grouped if dispatch == "gather" else moe_dense
        y, aux = layer(cfg, params, x.reshape(b * s, d))
        return y.reshape(b, s, d), aux
    raise ValueError(f"unknown MoE dispatch {dispatch!r}")


# ------------------------------------------------------------ expert paths ---

#: ``(rows [M, K], weights [E, K, N], rows per expert [E] int32) -> [M, N]``
#: float32: one GEMM per expert over its run of the sorted rows
GroupedMatmul = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def ragged_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """The grouped GEMM as ``jax.lax.ragged_dot``, accumulated in float32."""
    return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                              preferred_element_type=jnp.float32)


def _gated(g: jax.Array, h: jax.Array, activation: str, dtype) -> jax.Array:
    """act(g) * h (gelu for ``geglu``, else silu) in the dtype of g and h,
    rounded to ``dtype`` for the down projection."""
    act = jax.nn.gelu(g, approximate=True) if activation == "geglu" else jax.nn.silu(g)
    return (act * h).astype(dtype)


def experts_dense(x: jax.Array, top_i: jax.Array, top_w: jax.Array, w: Params,
                  block: int = 512, acc=jnp.float32, activation: str = "swiglu") -> jax.Array:
    """Every expert on every token, ``block`` tokens at a time, combined in
    float32 by the routing weights (zero off the top-k). x [T, d] -> [T, d]
    float32."""
    t, d = x.shape
    e = w["wg"].shape[0]
    block = min(block, t)
    if t % block:
        raise ValueError(f"{t} tokens are not a multiple of the dense block {block}")
    combine = jnp.zeros((t, e), jnp.float32).at[jnp.arange(t)[:, None], top_i].add(
        top_w.astype(jnp.float32))
    wg, wi, wo = (w[n].astype(x.dtype) for n in ("wg", "wi", "wo"))

    def one(xc):
        xb, cb = xc
        g = jnp.einsum("td,edf->etf", xb, wg, preferred_element_type=acc)
        h = jnp.einsum("td,edf->etf", xb, wi, preferred_element_type=acc)
        y = jnp.einsum("etf,efd->etd", _gated(g, h, activation, x.dtype), wo,
                       preferred_element_type=acc)
        return jnp.sum(y * cb.T[:, :, None], axis=0)

    if block == t:
        return one((x, combine))
    out = jax.lax.map(one, (x.reshape(t // block, block, d),
                            combine.reshape(t // block, block, e)))
    return out.reshape(t, d)


def experts_capacity(x: jax.Array, top_i: jax.Array, top_w: jax.Array, w: Params,
                     capacity: int, acc=jnp.float32, activation: str = "swiglu") -> jax.Array:
    """The capacity gather dispatch at ``capacity`` slots per expert,
    combined in ``acc``; nothing drops where no expert holds more.
    x [T, d] -> [T, d] in ``acc``."""
    t, d = x.shape
    e = w["wg"].shape[0]
    flat_e, pos, disp = _dispatch_table(top_i, t, e, capacity)
    xe = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)[disp]   # [E, C, d]
    wg, wi, wo = (w[n].astype(x.dtype) for n in ("wg", "wi", "wo"))
    g = jnp.einsum("ecd,edf->ecf", xe, wg, preferred_element_type=acc)
    h = jnp.einsum("ecd,edf->ecf", xe, wi, preferred_element_type=acc)
    ye = jnp.einsum("ecf,efd->ecd", _gated(g, h, activation, x.dtype), wo,
                    preferred_element_type=acc)
    # combine: weight per (e, c) slot = routing weight of its assignment
    w_slot = jnp.zeros((e, capacity), acc).at[flat_e, pos].set(
        top_w.reshape(-1).astype(acc), mode="drop")
    out = jnp.zeros((t + 1, d), acc).at[disp.reshape(-1)].add(
        (ye * w_slot[..., None]).reshape(-1, d), mode="drop")
    return out[:t]


def experts_grouped(x: jax.Array, top_i: jax.Array, top_w: jax.Array, w: Params,
                    matmul: GroupedMatmul, activation: str = "swiglu") -> jax.Array:
    """The assignments sorted by expert, the three expert GEMMs as grouped
    GEMMs over the sorted rows (``matmul``), the rows put back in token
    order and combined. x [T, d] -> [T, d] float32."""
    t, d = x.shape
    k = top_i.shape[1]
    e = w["wg"].shape[0]
    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                        # [T*k]
    sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    xs = x[order // k]
    g = matmul(xs, w["wg"], sizes)
    h = matmul(xs, w["wi"], sizes)
    ys = matmul(_gated(g, h, activation, x.dtype), w["wo"], sizes)  # [T*k, d]
    back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
    y = ys[back].reshape(t, k, d)
    return jnp.sum(y * top_w.astype(jnp.float32)[..., None], axis=1)


def shared_expert(x: jax.Array, sw: Params, acc=jnp.float32) -> jax.Array:
    """The shared expert's silu FFN, ungated: x [T, d] -> [T, d] in ``acc``."""
    g = jnp.einsum("td,df->tf", x, sw["wg"].astype(x.dtype), preferred_element_type=acc)
    h = jnp.einsum("td,df->tf", x, sw["wi"].astype(x.dtype), preferred_element_type=acc)
    return jnp.einsum("tf,fd->td", _gated(g, h, "swiglu", x.dtype), sw["wo"].astype(x.dtype),
                      preferred_element_type=acc)
