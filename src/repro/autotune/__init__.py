"""repro.autotune — the paper's ranking methodology as the framework's
variant selector (measured or cost-modelled), campaign-capable via the
core ExperimentEngine.

The package imports lazily (PEP 562): both submodules import jax at module
scope (``variants`` builds jax arrays, ``tuner`` drives them), but census
workers on the deterministic backends only need the kernel_variants
family's *metadata* (FLOP tables, grids) — which :mod:`repro.core.family`
computes without touching this package. Importing ``repro.autotune``
itself therefore stays jax-free until an attribute is actually resolved.
"""

from typing import TYPE_CHECKING

#: attribute name -> defining submodule
_EXPORTS = {
    # tuner (imports jax via the engine's workload builders)
    "CampaignSite": "tuner",
    "TuneReport": "tuner",
    "build_session": "tuner",
    "prepare_site": "tuner",
    "rank_site": "tuner",
    "rank_site_costmodel": "tuner",
    "rank_sites": "tuner",
    "report_from_session": "tuner",
    "reports_from_engine": "tuner",
    # variants (imports jax at module scope)
    "Variant": "variants",
    "VariantSite": "variants",
    "attention_layer_site": "variants",
    "attention_site": "variants",
    "matmul_blocks_site": "variants",
    "moe_dispatch_site": "variants",
    "moe_layer_site": "variants",
    "ssd_chunk_site": "variants",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        import importlib

        module = importlib.import_module(f".{_EXPORTS[name]}", __name__)
        value = getattr(module, name)
        globals()[name] = value  # cache for subsequent lookups
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))


if TYPE_CHECKING:  # pragma: no cover - static analysis only
    from .tuner import (
        CampaignSite,
        TuneReport,
        build_session,
        prepare_site,
        rank_site,
        rank_site_costmodel,
        rank_sites,
        report_from_session,
        reports_from_engine,
    )
    from .variants import (
        Variant,
        VariantSite,
        attention_layer_site,
        attention_site,
        matmul_blocks_site,
        moe_dispatch_site,
        moe_layer_site,
        ssd_chunk_site,
    )
