"""The family adapters against the program, on the CPU at tiny sizes: the
adapters' FLOP tables are the program's, and their float64 references agree
with what the program's workloads compute."""

import numpy as np
import pytest

from bench.families import Gemm, chain, derive_seed, kernel_variants


def _program(family, uid, params):
    from repro.core.family import InstanceSpec
    from repro.core.sweep import instance_entry

    flops, _, build = instance_entry(InstanceSpec(0, uid, family.FAMILY, dict(params)))
    return flops, build


CASES = [
    (chain, {"n_matrices": 4, "lo": 24, "hi": 24, "seed": 5}),
    (chain, {"n_matrices": 3, "lo": 16, "hi": 16, "seed": 1}),
    (chain, {"n_matrices": 5, "lo": 8, "hi": 8, "seed": 2}),
    (kernel_variants, {"site": "matmul", "size": 128, "seed": 3}),
    (kernel_variants, {"site": "matmul", "size": 512, "seed": 4}),
    (kernel_variants, {"site": "matmul", "size": 64, "seed": 0}),
]


@pytest.mark.parametrize("family,params", CASES)
def test_flop_table_is_the_programs(family, params):
    flops, _ = _program(family, "x", params)
    mine = {name: sum(g.flops for g in gs) for name, gs in family.gemms(params).items()}
    assert mine == {k: float(v) for k, v in flops.items()}


@pytest.mark.parametrize("family,params", [CASES[0], CASES[3]])
def test_reference_agrees_with_the_programs_answers(family, params):
    _, build = _program(family, "x", params)
    answers = {name: np.asarray(fn(), np.float64) for name, fn in build().items()}
    ref = family.reference(params, "float32")
    assert set(answers) == set(ref)
    for name, out in answers.items():
        assert np.max(np.abs(out - ref[name])) / np.max(np.abs(ref[name])) < 1e-5, name


@pytest.mark.parametrize("family,params", [CASES[0], CASES[3]])
def test_control_is_the_reference_in_bfloat16(family, params):
    ref, ctl = family.reference(params, "float32"), family.control(params)
    for name in ref:
        err = np.max(np.abs(ctl[name] - ref[name])) / np.max(np.abs(ref[name]))
        assert 1e-4 < err < 1e-1, name
        bits = np.asarray(ctl[name], np.float32).view(np.uint32) & 0xFFFF
        assert not bits.any(), name


@pytest.mark.parametrize("family,params", [CASES[0], CASES[3]])
def test_bfloat16_operands_move_the_reference_by_a_bf16_pass(family, params):
    exact, rounded = family.reference(params, "float32"), family.reference(params, "bfloat16")
    for name in exact:
        err = np.linalg.norm(rounded[name] - exact[name]) / np.linalg.norm(exact[name])
        assert 5e-4 < err < 1e-2, name


def _bf16_float64(x):
    """float32 -> bfloat16 by round to nearest even, as float64, in numpy."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def test_bf16_rounding_is_round_to_nearest_even():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-8, 1.0 + 2**-9, -3.0000001], np.float32)
    assert _bf16_float64(x).tolist() == [1.0, 1.0, 1.0 + 4 * 2**-8, 1.0, -3.0]


@pytest.mark.parametrize("family,params", [
    (chain, {"n_matrices": 4, "lo": 96, "hi": 96, "seed": 7}),
    (kernel_variants, {"site": "matmul", "size": 256, "seed": 8}),
])
def test_reference_matches_a_float64_evaluation(family, params):
    """The device reference at bfloat16 operands against the same
    parenthesizations evaluated in float64 on the host."""
    mats = [np.asarray(m, np.float32) for m in family.inputs(params)]
    trees = (family.algorithms(params) if family is chain
             else {name: (0, 1) for name in family.algorithms(params)})

    def run(tree):
        if isinstance(tree, int):
            return mats[tree]
        return _bf16_float64(run(tree[0])) @ _bf16_float64(run(tree[1]))

    ref = family.reference(params, "bfloat16")
    for name, tree in trees.items():
        want = run(tree)
        assert np.linalg.norm(ref[name] - want) / np.linalg.norm(want) < 1e-6, name


def test_rows_fix_shapes_and_draw_data_from_the_seed():
    config, traffic = {"n_matrices": 4}, {"size": 1000, "pool": 8}
    a = chain.rows(config, traffic, 2**31 + 5, 0)
    b = chain.rows(config, traffic, 2**31 + 5, 0)
    c = chain.rows(config, traffic, 2**31 + 6, 0)
    assert a == b and len(a) == 8 and len({uid for uid, _ in a}) == 8
    assert [p["seed"] for _, p in a] != [p["seed"] for _, p in c]
    assert all(p["lo"] == p["hi"] == 1000 for _, p in a + c)
    assert len(chain.rows(config, traffic, 3, -1)) == 1
    assert all(0 <= derive_seed(s, 1, 2) < 2**31 for s in (0, 2**40, -3))


def test_gemm_roofline_terms():
    # at n=4096 the FLOP term bounds a float32 GEMM on a v5e, at n=1000 the bytes
    big, small = Gemm(4096, 4096, 4096), Gemm(1000, 1000, 1000)
    assert big.least_seconds(197e12, 819e9)[1] == "flops"
    assert small.least_seconds(197e12, 819e9)[1] == "bytes"
    assert big.least_seconds(197e12, 819e9)[0] == pytest.approx(2 * 4096**3 / 197e12)
    assert small.least_seconds(197e12, 819e9)[0] == pytest.approx(12e6 / 819e9)
