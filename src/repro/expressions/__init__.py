"""repro.expressions — Linnea-like variant generation for linear algebra.

Enumerates mathematically equivalent algorithms (parenthesizations ×
instruction orders, plus beyond-chain identity families) with exact analytic
FLOP counts and executable JAX implementations. This is the substrate the
paper's ranking methodology is demonstrated on.

The package imports lazily (PEP 562): the *analytic* layer (``chain``,
``instances``, family FLOP tables) is pure numpy, and jax is only imported
when an executable workload is actually built. DiscriminantSweep census
workers on the cost-model backend therefore start without paying the jax
import at all.
"""

from typing import TYPE_CHECKING

#: attribute name -> defining submodule
_EXPORTS = {
    # algorithms (imports jax)
    "chain_site": "algorithms",
    "make_chain_inputs": "algorithms",
    "reference_product": "algorithms",
    "verify_algorithms": "algorithms",
    # chain (pure python/numpy)
    "ChainAlgorithm": "chain",
    "algorithms_for_tree": "chain",
    "dp_optimal_flops": "chain",
    "enumerate_trees": "chain",
    "flops_table": "chain",
    "generate_chain_algorithms": "chain",
    "linear_extensions": "chain",
    "tree_dims": "chain",
    "tree_flops": "chain",
    "tree_label": "chain",
    # generalized (jax deferred to workload build time)
    "FAMILIES": "generalized",
    "bilinear_family": "generalized",
    "distributive_family": "generalized",
    "gram_family": "generalized",
    "solve_family": "generalized",
    # instances (numpy only)
    "ANOMALY_331": "instances",
    "FIG3_75": "instances",
    "INSTANCE_A": "instances",
    "INSTANCE_B": "instances",
    "PAPER_INSTANCES": "instances",
    "SMOKE_INSTANCES": "instances",
    "ChainInstance": "instances",
    "get_instance": "instances",
    "instance_grid": "instances",
    "random_instance": "instances",
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
    from .algorithms import (
        chain_site,
        make_chain_inputs,
        reference_product,
        verify_algorithms,
    )
    from .chain import (
        ChainAlgorithm,
        algorithms_for_tree,
        dp_optimal_flops,
        enumerate_trees,
        flops_table,
        generate_chain_algorithms,
        linear_extensions,
        tree_dims,
        tree_flops,
        tree_label,
    )
    from .generalized import (
        FAMILIES,
        bilinear_family,
        distributive_family,
        gram_family,
        solve_family,
    )
    from .instances import (
        ANOMALY_331,
        FIG3_75,
        INSTANCE_A,
        INSTANCE_B,
        PAPER_INSTANCES,
        SMOKE_INSTANCES,
        ChainInstance,
        get_instance,
        instance_grid,
        random_instance,
    )
