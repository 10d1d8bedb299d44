"""Tests for the Linnea-like expression layer (chains + families)."""

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import relative_flops
from repro.expressions import (
    ANOMALY_331,
    FIG3_75,
    chain_site,
    dp_optimal_flops,
    enumerate_trees,
    flops_table,
    generate_chain_algorithms,
    get_instance,
    linear_extensions,
    make_chain_inputs,
    reference_product,
    solve_family,
    tree_flops,
    verify_algorithms,
)


def test_catalan_counts():
    assert [len(enumerate_trees(n)) for n in (1, 2, 3, 4, 5, 6)] == [1, 1, 2, 5, 14, 42]


def test_chain4_has_six_algorithms():
    """Paper Sec. I: 5 parenthesizations -> at least 6 algorithms
    ((AB)(CD) has two instruction orders)."""
    algs = generate_chain_algorithms((8, 9, 10, 11, 12))
    assert len(algs) == 6
    labels = [a.label for a in algs]
    assert sum("(AB)(CD)" in l for l in labels) == 2


def test_paper_table1_rf_reproduced():
    algs = generate_chain_algorithms(ANOMALY_331)
    rf = sorted(round(v, 2) for v in relative_flops(flops_table(algs)).values())
    assert rf == [0.0, 0.0, 0.04, 0.11, 0.27, 0.32]


def test_paper_table2_rf_reproduced():
    algs = generate_chain_algorithms(FIG3_75)
    rf = sorted(round(v, 2) for v in relative_flops(flops_table(algs)).values())
    expect = [0.0, 0.0, 2.78, 2.78, 5.59, 5.59]  # paper rounds differently by 0.01
    assert all(abs(a - b) <= 0.015 for a, b in zip(rf, expect)), rf


@given(st.lists(st.integers(2, 40), min_size=4, max_size=6))
@settings(max_examples=30, deadline=None)
def test_enumerated_min_matches_dp(dims):
    """Property: exhaustive enumeration minimum == DP optimum."""
    algs = generate_chain_algorithms(tuple(dims))
    assert min(a.flops for a in algs) == dp_optimal_flops(dims)


@given(st.lists(st.integers(2, 12), min_size=4, max_size=5), st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_all_algorithms_equivalent(dims, seed):
    """Property: every parenthesization/order computes the same product."""
    dims = tuple(dims)
    mats = make_chain_inputs(dims, seed=seed)
    verify_algorithms(generate_chain_algorithms(dims), mats, rtol=5e-3, atol=5e-3)


def test_instruction_orders_are_valid_toposorts():
    for tree in enumerate_trees(5):
        for ext in linear_extensions(tree):
            assert sorted(ext) == list(range(len(ext)))


def test_workloads_block_and_run():
    inst = get_instance("fig3_75", smoke=True)
    mats = make_chain_inputs(inst.dims, seed=0)
    table = chain_site(inst.dims).workloads(seed=0)
    ref = np.asarray(reference_product(mats))
    for name, fn in table.items():
        np.testing.assert_allclose(np.asarray(fn()), ref, rtol=2e-3, atol=2e-3)


def test_solve_family_flops_ordering():
    fam = solve_family(256)
    f = fam.flops_table()
    assert f["solve_chol"] < f["solve_lu"] < f["solve_inverse"]
    # variants compute the same solution
    import jax.numpy as jnp

    w = solve_family(64).workloads(seed=0)
    outs = {k: np.asarray(v()) for k, v in w.items()}
    for k in ("solve_lu", "solve_chol"):
        np.testing.assert_allclose(outs[k], outs["solve_inverse"], rtol=2e-2, atol=2e-2)
