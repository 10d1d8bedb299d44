"""AlgorithmFamily — the census's one algorithm-source seam.

The paper's methodology ranks *any* set of FLOP-equivalent algorithms; the
census should therefore not hard-code where algorithms come from. This
module is the single registry every layer resolves through:

* :class:`SweepSpec` validation and grid expansion (:mod:`repro.core.sweep`)
* the planner's CLI grid flags (:mod:`repro.launch.sweep`)
* the explainer's kernel decomposition (:mod:`repro.explain.decompose`)
  and whole-algorithm re-measurement (:mod:`repro.explain.runner`)
* the markdown reports' family annotations (:mod:`repro.launch.report_md`)

An :class:`AlgorithmFamily` supplies, for one family name:

``expand_grid``
    deterministic grid expansion into :class:`InstanceSpec` rows (stable
    uids; global indices are assigned by the sweep after concatenation).
``entry``
    the instance's analytic FLOP table, descriptive meta (size, dims, and
    the per-algorithm kernel decomposition — the explainer's rebuild
    pointer), and a lazy workload builder. Everything except the builder
    must be computable WITHOUT importing jax: the deterministic cost-model
    hooks (:func:`repro.core.sweep.synthetic_instance_model`) consume only
    the FLOP table and kernel counts, so cost-model census workers never
    build a single jax array.
``variant_site``
    the one build hook: the instance's algorithms as a
    :class:`~repro.core.programs.VariantSite`. The builder ``entry``
    returns is its ``workloads(seed)``, and ``explain_workloads`` builds
    the same table for only the algorithms an explanation involves.
``decompose``
    kernels per algorithm purely from the instance's ``params`` row — the
    explainer's offline rebuild path (no jax, no re-measurement).
``grid_from_args``
    the family's slice of the planner's CLI namespace (None = the family
    is not part of this plan).

Registered here: the paper's chain, the four beyond-chain identity
families, and ``kernel_variants``, whose algorithms are kernel variants
(Pallas matmul tile shapes, fused vs unfused attention, SSD chunk lengths)
of the same math, the sites of :mod:`repro.autotune.variants`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

#: (flops table, descriptive meta, workload-builder thunk) — the shape
#: `instance_entry` has always returned.
Entry = Tuple[Dict[str, float], Dict[str, Any], Callable[[], Dict[str, Callable[[], Any]]]]


@dataclass(frozen=True)
class InstanceSpec:
    """One census row: an expression instance, fully determined by JSON."""

    index: int                #: position in the expanded grid (global order)
    uid: str                  #: stable identifier, unique within the sweep
    family: str               #: a registered family name
    params: Dict[str, Any]    #: family-specific (dims / size / seed)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "index": self.index, "uid": self.uid,
            "family": self.family, "params": dict(self.params),
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "InstanceSpec":
        return cls(
            index=int(d["index"]), uid=str(d["uid"]),
            family=str(d["family"]), params=dict(d["params"]),
        )


class AlgorithmFamily:
    """Base class: one source of FLOP-comparable algorithm sets."""

    #: registry key; also the ``family`` field of every record it produces
    name: str = ""
    #: one-line description (report footnotes, CLI help)
    description: str = ""

    # ------------------------------------------------------------- grid ---

    def expand_grid(self, grid: Mapping[str, Any]) -> List[InstanceSpec]:
        """Deterministic expansion of this family's grid dict into
        InstanceSpec rows with ``index=0`` placeholders (the sweep assigns
        global indices after concatenating all families)."""
        raise NotImplementedError

    def grid_from_args(self, args: Any) -> Optional[Dict[str, Any]]:
        """This family's grid dict from the planner's argparse namespace,
        or None when the arguments exclude the family from the plan."""
        return None

    # --------------------------------------------------------- instances ---

    def entry(self, inst: InstanceSpec) -> Entry:
        """(flops table, meta, workload-builder). ``meta`` must carry
        ``size`` (scalar for the census's size buckets), ``dims`` (or
        None) and ``kernels`` (compact per-algorithm decomposition). Only
        calling the returned builder may import jax."""
        raise NotImplementedError

    def variant_site(self, params: Mapping[str, Any]):
        """The instance's algorithms as a
        :class:`~repro.core.programs.VariantSite`; may import jax (workload
        build time only)."""
        raise NotImplementedError

    def decompose(self, params: Mapping[str, Any]) -> Dict[str, Any]:
        """KernelSpecs per algorithm, purely from the params row."""
        raise NotImplementedError

    def explain_workloads(
        self, inst: InstanceSpec, involved: Sequence[str]
    ) -> Dict[str, Callable[[], Any]]:
        """Warmed workloads of ONLY the involved algorithms, on the inputs
        the census measured: a chain enumerates dozens of algorithms, and
        an explanation needs its winner and loser."""
        site = self.variant_site(inst.params).only(involved)
        return site.workloads(int(inst.params["seed"]))


# --------------------------------------------------------------- registry ---


_REGISTRY: Dict[str, AlgorithmFamily] = {}


def register_family(family: AlgorithmFamily) -> AlgorithmFamily:
    """Register (or replace) a family under its ``name``."""
    if not family.name:
        raise ValueError("family must define a non-empty name")
    _REGISTRY[family.name] = family
    return family


def get_family(name: str) -> AlgorithmFamily:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown algorithm family {name!r}; one of {family_names()}"
        ) from None


def family_names() -> Tuple[str, ...]:
    """Registered family names, in registration order."""
    return tuple(_REGISTRY)


# ---------------------------------------------------------- chain family ---


class ChainFamily(AlgorithmFamily):
    """The paper's Expression 1: matrix-chain parenthesizations x
    instruction orders (:mod:`repro.expressions.instances`)."""

    name = "chain"
    description = (
        "matrix-chain parenthesizations x instruction orders "
        "(the paper's Expression 1), random dims per instance"
    )

    def expand_grid(self, grid: Mapping[str, Any]) -> List[InstanceSpec]:
        count = int(grid.get("count", 0))
        n_list = [int(n) for n in grid.get("n_matrices", [4])]
        lo, hi = int(grid.get("lo", 32)), int(grid.get("hi", 512))
        out: List[InstanceSpec] = []
        for i in range(count):
            n = n_list[i % len(n_list)]
            out.append(InstanceSpec(
                index=0,
                uid=f"chain-n{n}-i{i:05d}",
                family="chain",
                params={"n_matrices": n, "lo": lo, "hi": hi, "seed": i},
            ))
        return out

    def grid_from_args(self, args: Any) -> Optional[Dict[str, Any]]:
        if int(getattr(args, "chains", 0)) <= 0:
            return None
        return {
            "count": args.chains, "n_matrices": args.chain_sizes,
            "lo": args.lo, "hi": args.hi,
        }

    def entry(self, inst: InstanceSpec) -> Entry:
        """Expression generators are imported lazily so cost-model workers
        never build a single jax array. ``meta["kernels"]`` carries the
        per-algorithm kernel decomposition (computed here, where the
        enumerated algorithms already exist) — the AnomalyExplainer's
        rebuild pointer."""
        from repro.explain.decompose import decompose_chain, kernels_to_compact
        from repro.expressions.chain import flops_table
        from repro.expressions.instances import random_instance

        p = inst.params
        chain = random_instance(
            int(p["n_matrices"]), int(p["lo"]), int(p["hi"]), seed=int(p["seed"])
        )
        algs = chain.algorithms()
        flops = flops_table(algs)
        dims = list(chain.dims)
        size = int(round(float(np.exp(np.mean(np.log(dims))))))  # geometric mean
        kernels = kernels_to_compact(
            {a.name: decompose_chain(dims, a.steps) for a in algs}
        )
        meta = {"size": size, "dims": dims, "kernels": kernels}
        return flops, meta, lambda: self.variant_site(p).workloads(int(p["seed"]))

    def variant_site(self, params: Mapping[str, Any]):
        from repro.expressions.algorithms import chain_site
        from repro.expressions.instances import random_instance

        return chain_site(random_instance(
            int(params["n_matrices"]), int(params["lo"]), int(params["hi"]),
            seed=int(params["seed"])).dims)

    def decompose(self, params: Mapping[str, Any]) -> Dict[str, Any]:
        from repro.explain.decompose import _chain_instance_dims, decompose_chain_dims

        dims = _chain_instance_dims(
            int(params["n_matrices"]), int(params["lo"]), int(params["hi"]),
            int(params["seed"]),
        )
        return decompose_chain_dims(dims)


# ---------------------------------------------------- generalized families ---


class GeneralizedFamily(AlgorithmFamily):
    """A beyond-chain identity family from
    :mod:`repro.expressions.generalized` (gram / distributive / solve /
    bilinear): ``per_size`` seeded instances at each grid size."""

    def __init__(self, name: str, description: str) -> None:
        self.name = name
        self.description = description

    def expand_grid(self, grid: Mapping[str, Any]) -> List[InstanceSpec]:
        sizes = [int(s) for s in grid.get("sizes", ())]
        per_size = int(grid.get("per_size", 1))
        out: List[InstanceSpec] = []
        for size in sizes:
            for s in range(per_size):
                out.append(InstanceSpec(
                    index=0,
                    uid=f"{self.name}-n{size}-s{s:03d}",
                    family=self.name,
                    params={"size": size, "seed": s},
                ))
        return out

    def grid_from_args(self, args: Any) -> Optional[Dict[str, Any]]:
        return {"sizes": args.sizes, "per_size": args.per_size}

    def entry(self, inst: InstanceSpec) -> Entry:
        from repro.explain.decompose import decompose_generalized, kernels_to_compact

        p = inst.params
        size = int(p["size"])
        flops = self.variant_site(p).flops_table()
        kernels = kernels_to_compact(decompose_generalized(inst.family, size))
        meta = {"size": size, "dims": None, "kernels": kernels}
        return flops, meta, lambda: self.variant_site(p).workloads(int(p["seed"]))

    def variant_site(self, params: Mapping[str, Any]):
        from repro.expressions.generalized import FAMILIES as GEN

        return GEN[self.name](n=int(params["size"]))

    def decompose(self, params: Mapping[str, Any]) -> Dict[str, Any]:
        from repro.explain.decompose import decompose_generalized

        return decompose_generalized(self.name, int(params["size"]))


# ------------------------------------------------- kernel_variants family ---

#: sites the family can census, in CLI order; ``moe`` runs a named model
#: config's MoE layers only
KERNEL_SITES = ("matmul", "attention", "ssd", "moe")
#: the sites of a grid that names none: those with toy instances
TOY_KERNEL_SITES = ("matmul", "attention", "ssd")


def _kernel_site_config(site: str, size: int) -> Dict[str, Any]:
    """Pure (no-jax) per-site metadata at one grid size: algorithm names,
    the shared-math kernel decomposition, and the VariantSite constructor
    (``site``, a function of :mod:`repro.autotune.variants`) and its
    arguments. The decomposition describes the *shared math* once — every
    variant computes the same function, so every variant carries the same
    kernel list and the same analytic FLOP count (FLOP-identical by
    construction; implementation overhead — masked blocks, chunk-quadratic
    terms, tile padding — is exactly what the census measures). A model
    layer's attention site (:func:`_attention_layer_config`) counts each
    variant's own FLOPs instead.
    """
    from repro.explain.decompose import KernelSpec

    size = int(size)
    if site == "matmul":
        # Pallas GEMM tile shapes (+ the XLA dot baseline): 2mkn exactly,
        # for every tiling. A TPU block's last two dims must be multiples
        # of (8, 128) or the whole array, so tiles are 128/256/512 capped
        # at the size, and a smaller size is one whole-array tile
        m = k = n = size
        blocks = [(b, b, b) for b in (128, 256, 512) if b <= size] or [(size,) * 3]
        names = [f"blocks_{bm}x{bn}x{bk}" for bm, bn, bk in blocks] + ["xla_dot"]
        return {
            "names": names,
            "kernels": [KernelSpec("gemm", (m, k, n))],
            "site": "matmul_blocks_site",
            "site_kwargs": {"m": m, "k": k, "n": n, "blocks": blocks},
        }
    if site == "attention":
        # fused (chunked flash-style) vs unfused reference blocks: the
        # shared math is the scores GEMM + the output GEMM, batch*heads
        # folded into the row dimension
        b, h, kv, d = 1, 2, 1, 16
        s = size
        names = ["reference_grouped", "reference_broadcast", "chunked_flash"]
        return {
            "names": names,
            "kernels": [
                KernelSpec("gemm", (b * h * s, d, s)),   # scores  Q @ K^T
                KernelSpec("gemm", (b * h * s, s, d)),   # output  P @ V
            ],
            "site": "attention_site",
            "site_kwargs": {"b": b, "s": s, "h": h, "kv": kv, "d": d},
        }
    if site == "ssd":
        # Mamba-2 SSD chunk lengths: the shared math at the reference
        # chunk q0 — intra-chunk scores (C @ B^T), their application to x,
        # and the two state GEMMs (build B^T x, apply C) — aggregated over
        # batch*heads*tokens
        b, h, p, n = 1, 2, 8, 8
        s = size
        chunks = [c for c in (8, 16, 32, 64) if c <= s and s % c == 0]
        if len(chunks) < 2:
            raise ValueError(
                f"kernel_variants ssd site needs >= 2 chunk lengths dividing "
                f"size {s} (have {chunks}); use a size that is a multiple of 16"
            )
        q0 = chunks[0]
        return {
            "names": [f"chunk_{q}" for q in chunks],
            "kernels": [
                KernelSpec("gemm", (b * h * s, n, q0)),  # scores   C @ B^T
                KernelSpec("gemm", (b * h * s, q0, p)),  # apply    G @ X
                KernelSpec("gemm", (b * h * s, n, p)),   # state    B^T @ X
                KernelSpec("gemm", (b * h * s, p, n)),   # output   S @ C
            ],
            "site": "ssd_chunk_site",
            "site_kwargs": {"b": b, "s": s, "h": h, "p": p, "n": n,
                            "chunks": chunks},
        }
    raise ValueError(f"unknown kernel site {site!r}; one of {KERNEL_SITES}")


def attention_layer_kinds(config: str) -> List[str]:
    """The attention layer kinds of a registered model config, in the order
    its pattern unit first has them: ``sliding`` (window attention) and
    ``full``."""
    from repro.configs import get_config
    from repro.models.config import LayerKind

    names = {LayerKind.ATTN_LOCAL: "sliding", LayerKind.ATTN: "full"}
    kinds: List[str] = []
    for spec in get_config(config).pattern_unit():
        if spec.kind in names and names[spec.kind] not in kinds:
            kinds.append(names[spec.kind])
    return kinds


#: instance params that restate a model layer's attention widths
_WIDTH_PARAMS = ("heads", "kv_heads", "head_dim", "window")


def _attention_layer_config(params: Mapping[str, Any]) -> Dict[str, Any]:
    """Metadata of one attention layer of a registered model config at its
    published widths (params ``config``, ``layer``, ``size`` = sequence
    length; batch 1): algorithm names, each variant's own kernel
    decomposition (executed FLOPs, by the site's counting rule
    :func:`repro.autotune.variants.attention_score_tiles`), the shapes
    (``dims``), and the site with its arguments. Params that restate a
    width must agree with the config. Reading a model config imports the
    model stack, and so jax."""
    from repro.autotune.variants import (attention_algorithms, attention_score_tiles,
                                         check_attention_size)
    from repro.configs import get_config
    from repro.explain.decompose import decompose_attention

    config, layer, s = str(params["config"]), str(params["layer"]), int(params["size"])
    kinds = attention_layer_kinds(config)
    if layer not in kinds:
        raise ValueError(f"{config} has no {layer!r} attention layer; one of {kinds}")
    model = get_config(config)
    b, h, kv, d = 1, model.n_heads, model.n_kv_heads, model.resolved_head_dim
    window = model.sliding_window if layer == "sliding" else None
    widths = {"heads": h, "kv_heads": kv, "head_dim": d, "window": window}
    stated = {k: params[k] for k in _WIDTH_PARAMS if k in params}
    if any(stated[k] != widths[k] for k in stated):
        raise ValueError(f"instance widths {stated} disagree with {config}'s {widths}")
    check_attention_size(s, window)
    names = attention_algorithms(b, s, h, window)
    return {
        "names": names,
        "kernels": {n: decompose_attention(b, h, d, *attention_score_tiles(n, s, window))
                    for n in names},
        "dims": {"b": b, "s": s, **widths},
        "site": "attention_layer_site",
        "site_kwargs": {"s": s, "h": h, "kv": kv, "d": d, "window": window, "b": b},
    }


def moe_layers(config: str) -> List[int]:
    """Indices of a registered model config's layers that hold an MoE FFN."""
    from repro.configs import get_config
    from repro.models.config import FFNKind

    model = get_config(config)
    unit = model.pattern_unit()
    return [i for i in range(model.n_layers) if unit[i % len(unit)].ffn is FFNKind.MOE]


#: instance params that restate a model's MoE widths and routing
_MOE_PARAMS = ("d_model", "experts", "top_k", "expert_d_ff", "shared_d_ff", "route_scale")


def _moe_widths(config: str) -> Dict[str, Any]:
    """A registered model config's MoE widths and route scale, by the names
    of :data:`_MOE_PARAMS`; raises unless its MoE is the layer the moe site
    runs."""
    from repro.configs import get_config

    model = get_config(config)
    if (model.n_experts == 0 or model.n_shared_experts != 1 or model.moe_shared_gate
            or model.moe_score_func != "sigmoid"):
        raise ValueError(f"{config}'s MoE is not a sigmoid-routed layer with one ungated "
                         "shared expert, the layer the moe site runs")
    return {"d_model": model.d_model, "experts": model.n_experts, "top_k": model.top_k,
            "expert_d_ff": model.resolved_moe_d_ff, "shared_d_ff": model.resolved_shared_d_ff,
            "route_scale": model.moe_route_scale}


def _moe_layer_config(params: Mapping[str, Any]) -> Dict[str, Any]:
    """Metadata of one MoE sublayer of a registered model config at its
    published widths (params ``config``, ``layer_index``, ``size`` = tokens,
    ``seed``, ``bias_scale`` the routing skew): algorithm names, each
    variant's kernels (executed rows by the instance's own routing,
    :func:`repro.autotune.variants.moe_executed_rows`), the shapes
    (``dims``), and the site with its arguments. The routing is computed
    on the host once per instance. Params that restate a width must agree
    with the config."""
    from repro.autotune.variants import (MOE_BIAS_SCALE, check_moe_size, moe_algorithms,
                                         moe_executed_rows, moe_routing, moe_rows_moved)
    from repro.configs import get_config
    from repro.explain.decompose import decompose_moe

    config, layer, t = str(params["config"]), int(params["layer_index"]), int(params["size"])
    widths = _moe_widths(config)
    model = get_config(config)
    layers = moe_layers(config)
    if layer not in layers:
        raise ValueError(f"{config}'s layer {layer} has no MoE FFN; one of {layers}")
    stated = {k: params[k] for k in _MOE_PARAMS if k in params}
    if any(stated[k] != widths[k] for k in stated):
        raise ValueError(f"instance widths {stated} disagree with {config}'s {widths}")
    d, e, k, f, sf = (widths[n] for n in _MOE_PARAMS[:5])
    check_moe_size(t, k)
    bias_scale = float(params.get("bias_scale", MOE_BIAS_SCALE))
    routing = moe_routing(t, e, k, int(params["seed"]), layer=layer, bias_scale=bias_scale,
                          route_scale=model.moe_route_scale, norm_topk=model.moe_norm_topk)
    names = moe_algorithms()
    return {
        "names": names,
        "kernels": {n: decompose_moe(t, d, f, sf, e, moe_executed_rows(n, t, e, k, routing),
                                     moe_rows_moved(n, t, e, k, routing))
                    for n in names},
        "dims": {"t": t, **widths, "layer_index": layer, "bias_scale": bias_scale},
        "site": "moe_layer_site",
        "site_kwargs": {"t": t, "d": d, "e": e, "k": k, "f": f, "shared_f": sf, "layer": layer,
                        "seed": int(params["seed"]), "route_scale": model.moe_route_scale,
                        "norm_topk": model.moe_norm_topk, "bias_scale": bias_scale},
    }


def _instance_site_config(params: Mapping[str, Any]) -> Dict[str, Any]:
    """The site metadata of one instance row, with ``kernels`` per variant."""
    site = str(params["site"])
    if site == "attention" and "config" in params:
        return _attention_layer_config(params)
    if site == "moe":
        return _moe_layer_config(params)
    cfg = _kernel_site_config(site, int(params["size"]))
    return {**cfg, "kernels": {n: list(cfg["kernels"]) for n in cfg["names"]}, "dims": None}


class KernelVariantsFamily(AlgorithmFamily):
    """The repo's own kernels as a census family: every algorithm is a
    kernel variant of the same math (Pallas matmul tile shapes, fused vs
    unfused attention blocks, SSD chunk lengths), wrapping the autotuner's
    :func:`~repro.autotune.variants` sites. At the toy widths all variants
    of an instance share one analytic FLOP count and one kernel
    decomposition (the shared math), so the whole instance sits in ``S_F``
    and **every** rank difference is an anomaly the explainer must
    attribute. An attention instance that names a model ``config`` runs one
    of its layers at the published widths, and each variant carries the
    FLOPs it executes. Toy metadata is jax-free (a named model config
    imports the model stack); only building workloads runs jax — measured
    through the ``wall_clock`` backend (Pallas compiled on a TPU,
    interpreted on any other backend:
    :func:`~repro.autotune.variants.pallas_interpret`),
    while the deterministic backends exercise the same grid through the
    synthetic cost hooks."""

    name = "kernel_variants"
    description = (
        "the repo's Pallas/JAX kernel variants (matmul tiles, fused vs "
        "unfused attention, SSD chunk lengths) — FLOP-identical by "
        "construction, except a model's attention layers, whose variants "
        "carry the FLOPs they execute — censused on wall clock"
    )

    def expand_grid(self, grid: Mapping[str, Any]) -> List[InstanceSpec]:
        """``sites`` x ``sizes`` x ``per_size`` seeds; with a ``config`` (a
        registered model config) the attention site runs that model's
        layers instead, one instance per attention layer kind and seed, and
        the moe site its MoE layers, one instance per layer index and seed
        (the moe site has no toy instances)."""
        sites = [str(x) for x in grid.get("sites", TOY_KERNEL_SITES)]
        sizes = [int(s) for s in grid.get("sizes", ())]
        per_size = int(grid.get("per_size", 1))
        config = grid.get("config")
        out: List[InstanceSpec] = []
        for site in sites:
            if site not in KERNEL_SITES:
                raise ValueError(
                    f"unknown kernel site {site!r}; one of {KERNEL_SITES}"
                )
            if site == "attention" and config:
                out.extend(self._layer_instances(str(config), sizes, per_size))
                continue
            if site == "moe":
                if not config:
                    raise ValueError("the moe site runs a model's MoE layers: name a config")
                out.extend(self._moe_instances(str(config), sizes, per_size))
                continue
            for size in sizes:
                _kernel_site_config(site, size)  # validate shape constraints
                for s in range(per_size):
                    out.append(InstanceSpec(
                        index=0,
                        uid=f"kernel_variants-{site}-n{size}-s{s:03d}",
                        family=self.name,
                        params={"site": site, "size": size, "seed": s},
                    ))
        return out

    def _layer_instances(self, config: str, sizes: Sequence[int],
                         per_size: int) -> List[InstanceSpec]:
        out: List[InstanceSpec] = []
        for size in sizes:
            for layer in attention_layer_kinds(config):
                params = {"site": "attention", "config": config, "layer": layer,
                          "size": size}
                dims = _attention_layer_config(params)["dims"]
                widths = {k: dims[k] for k in _WIDTH_PARAMS}
                for s in range(per_size):
                    out.append(InstanceSpec(
                        index=0,
                        uid=f"kernel_variants-attention-{config}-{layer}-n{size}-s{s:03d}",
                        family=self.name,
                        params={**params, "seed": s, **widths},
                    ))
        return out

    def _moe_instances(self, config: str, sizes: Sequence[int],
                       per_size: int) -> List[InstanceSpec]:
        from repro.autotune.variants import check_moe_size

        widths = _moe_widths(config)
        out: List[InstanceSpec] = []
        for size in sizes:
            check_moe_size(int(size), widths["top_k"])
            for layer in moe_layers(config):
                for s in range(per_size):
                    params = {"site": "moe", "config": config, "layer": "moe",
                              "layer_index": layer, "size": int(size), "seed": s, **widths}
                    out.append(InstanceSpec(
                        index=0,
                        uid=f"kernel_variants-moe-{config}-l{layer:02d}-n{size}-s{s:03d}",
                        family=self.name, params=params,
                    ))
        return out

    def grid_from_args(self, args: Any) -> Optional[Dict[str, Any]]:
        sites = [s for s in getattr(args, "kernel_sites", "").split(",") if s]
        grid = {
            "sites": sites or list(TOY_KERNEL_SITES),
            "sizes": args.sizes,
            "per_size": args.per_size,
        }
        if getattr(args, "kernel_config", ""):
            grid["config"] = args.kernel_config
        return grid

    def entry(self, inst: InstanceSpec) -> Entry:
        from repro.explain.decompose import kernels_to_compact

        p = inst.params
        cfg = _instance_site_config(p)
        flops = {name: sum(k.flops for k in ks) for name, ks in cfg["kernels"].items()}
        kernels = kernels_to_compact(cfg["kernels"])

        meta = {"size": int(p["size"]), "dims": cfg["dims"], "kernels": kernels}
        return flops, meta, lambda: self.variant_site(p).workloads(int(p["seed"]))

    def variant_site(self, params: Mapping[str, Any]):
        from repro.autotune import variants

        cfg = _instance_site_config(params)
        return getattr(variants, cfg["site"])(**cfg["site_kwargs"])

    def decompose(self, params: Mapping[str, Any]) -> Dict[str, Any]:
        return _instance_site_config(params)["kernels"]


# ------------------------------------------------------- the default seam ---

register_family(ChainFamily())
register_family(GeneralizedFamily(
    "gram", "A^T A B — gram product, left/right/syrk associations"))
register_family(GeneralizedFamily(
    "distributive", "(A + B) C — factored vs expanded distribution"))
register_family(GeneralizedFamily(
    "solve", "A^-1 b — explicit inverse vs LU vs Cholesky solve"))
register_family(GeneralizedFamily(
    "bilinear", "x^T A y — left-first vs right-first association"))
register_family(KernelVariantsFamily())
