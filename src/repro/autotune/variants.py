"""Variant sites: sets of mathematically equivalent implementations.

A :class:`VariantSite` is the framework's unit of algorithm choice — the
exact object the paper's methodology ranks. Every variant carries an
analytic FLOP count, so the FLOPs-discriminant test applies directly:

* ``attention_impl``     — reference / chunked:
  equal math; chunked wastes masked-block FLOPs, reference materialises the
  score matrix (memory). Neither FLOPs nor bytes alone predicts the winner
  across shapes — the paper's anomaly regime.
* ``gqa_mode``           — grouped vs broadcast: EQUAL FLOPs, different
  memory traffic (K/V repeated g times). Pure equal-FLOPs regime
  (paper Instance B analogue).
* ``moe_dispatch``       — gather vs dense: identical outputs, dense costs
  ~E/top_k x the FLOPs but has no scatter/gather — FLOPs *should*
  discriminate; when it doesn't, that's a textbook anomaly.
* ``ssd_chunk``          — Mamba-2 chunk length: equal leading-order FLOPs.
* ``matmul_blocks``      — Pallas GEMM tile shapes: equal FLOPs exactly;
  native on a TPU, interpreted elsewhere (:func:`pallas_interpret`).
* matrix chains          — the paper's own site (repro.expressions).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import named
from repro.models import ModelConfig
from repro.models.flops import param_counts

Thunk = Callable[[], Any]


@dataclasses.dataclass(frozen=True)
class Variant:
    name: str
    flops: float                     # analytic, per workload execution
    build: Callable[..., Thunk]      # (*arrays) -> zero-arg timed thunk
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class VariantSite:
    name: str
    variants: tuple
    make_inputs: Callable[[int], List[jax.Array]]   # seed -> arrays

    def flops_table(self) -> Dict[str, float]:
        return {v.name: v.flops for v in self.variants}

    def workloads(self, seed: int = 0, warmup: bool = True) -> Dict[str, Thunk]:
        arrays = self.make_inputs(seed)
        table: Dict[str, Thunk] = {}
        for v in self.variants:
            thunk = v.build(*arrays)
            if warmup:
                thunk()
            table[v.name] = thunk
        return table


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


def _thunk(fn, *arrays):
    """``fn`` jitted, compiled and run once; ``fn``'s name names the program
    (:func:`repro.core.spans.named`). jax caches the compiled program by
    ``fn`` itself, so a function built once (``_xla_dot``) is traced once per
    process, while a closure built per instance is traced per instance."""
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*arrays))

    def run():
        return jax.block_until_ready(jitted(*arrays))

    return run


#: XLA's dot as the program ``jit_xla_dot``, built once so that every
#: instance's jit finds it compiled
_xla_dot = named("xla_dot", jnp.dot)


# ------------------------------------------------------- attention site ----

def attention_site(
    b: int = 2, s: int = 1024, h: int = 8, kv: int = 2, d: int = 64,
    dtype=jnp.float32,
) -> VariantSite:
    from repro.models.attention import attention_chunked, attention_reference

    def inputs(seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
        v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
        return [q, k, v]

    # score FLOPs: rectangle for both impls (masked blocks computed);
    # the Pallas kernel variant (TPU) would halve this — listed via meta.
    f_scores = 2.0 * b * h * s * s * d * 2
    f_ref = f_scores
    f_chunk = f_scores

    def ref_grouped(q, k, v):
        return _thunk(
            named("reference_grouped",
                  lambda q, k, v: attention_reference(q, k, v, gqa="grouped")),
            q, k, v,
        )

    def ref_broadcast(q, k, v):
        return _thunk(
            named("reference_broadcast",
                  lambda q, k, v: attention_reference(q, k, v, gqa="broadcast")),
            q, k, v,
        )

    def chunked(q, k, v):
        return _thunk(
            named("chunked_flash", lambda q, k, v: attention_chunked(
                q, k, v, q_block=min(256, s), kv_block=min(512, s)
            )),
            q, k, v,
        )

    return VariantSite(
        name=f"attention[b{b} s{s} h{h}kv{kv} d{d}]",
        variants=(
            Variant("reference_grouped", f_ref, ref_grouped),
            Variant("reference_broadcast", f_ref, ref_broadcast,
                    {"extra_traffic": "K/V repeated to H heads"}),
            Variant("chunked_flash", f_chunk, chunked,
                    {"memory": "O(s*block) not O(s^2)"}),
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

    def gather(x):
        return _thunk(named("gather", lambda x: moe_gather(cfg, params, x)[0]), x)

    def dense(x):
        return _thunk(named("dense", lambda x: moe_dense(cfg, params, x)[0]), x)

    return VariantSite(
        name=f"moe_dispatch[T{tokens} E{e} k{top_k}]",
        variants=(
            Variant("gather", f_gather, gather, {"traffic": "scatter/gather"}),
            Variant("dense", f_dense, dense, {"flops": f"{e/top_k:.0f}x active"}),
        ),
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
        def build(x, dt, a_log, bm, cm):
            return _thunk(
                named(f"chunk_{chunk}", lambda x, dt, a_log, bm, cm:
                      ssd_chunked(x, dt, a_log, bm, cm, chunk)[0]),
                x, dt, a_log, bm, cm,
            )
        return build

    def flops(q):
        per_tok = 2.0 * q * (n + h * p / h) + 4.0 * h * p * n / h
        return b * s * h * (2.0 * q * n + 2.0 * q * p + 4.0 * p * n)

    return VariantSite(
        name=f"ssd_chunk[s{s} h{h} p{p} n{n}]",
        variants=tuple(
            Variant(f"chunk_{q}", flops(q), make(q), {"chunk": q}) for q in chunks
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
    # from the defining module: the package-level name can be shadowed by
    # the like-named subpackage after a dotted import (see repro.kernels)
    from repro.kernels.matmul.ops import matmul

    interpret = pallas_interpret(interpret)

    def inputs(seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        a = jax.random.normal(ks[0], (m, k), dtype)
        b_ = jax.random.normal(ks[1], (k, n), dtype)
        return [a, b_]

    f = 2.0 * m * k * n

    def make(bm, bn, bk):
        def build(a, b_):
            def run():
                return jax.block_until_ready(
                    matmul(a, b_, block_m=bm, block_n=bn, block_k=bk,
                           use_kernel=True, interpret=interpret)
                )
            run()  # warm
            return run
        return build

    variants = tuple(
        Variant(f"blocks_{bm}x{bn}x{bk}", f, make(bm, bn, bk),
                {"tiles": (bm, bn, bk)})
        for bm, bn, bk in blocks
    ) + (
        Variant("xla_dot", f, lambda a, b_: _thunk(_xla_dot, a, b_)),
    )
    return VariantSite(
        name=f"matmul[{m}x{k}x{n}]", variants=variants, make_inputs=inputs
    )
