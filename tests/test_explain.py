"""AnomalyExplainer: decomposition exactness, machine registry, cause
recovery on the synthetic census (the acceptance scenario), kill/resume
byte-identity, and the CLI."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.sweep import SweepSpec, merge_shards, run_shard, synthetic_efficiencies
from repro.explain.attribution import attribute_algorithm, kernel_roofline
from repro.explain.classify import CAUSES, classify_anomaly, pick_winner_loser
from repro.explain.decompose import (
    KernelSpec,
    decompose_chain_dims,
    decompose_generalized,
    decompose_instance,
    kernel_name,
    kernels_from_record,
)
from repro.explain.runner import (
    ExplainSpec,
    explain_progress,
    explain_summary,
    explain_targets,
    merge_explained,
    resolve_machine,
    run_explain_shard,
)
from repro.roofline.terms import (
    DEFAULT_MACHINE,
    HBM_BW,
    PEAK_FLOPS,
    MachineSpec,
    get_machine,
    machine_for_device,
    synthetic_machine,
)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS"):
        env.setdefault(var, "1")
    return env


# ---------------------------------------------------------- decomposition ---

def test_generalized_decomposition_is_flop_exact():
    from repro.expressions.generalized import FAMILIES

    for fam in ("gram", "distributive", "solve", "bilinear"):
        for n in (32, 64, 100):
            table = FAMILIES[fam](n=n).flops_table()
            kernels = decompose_generalized(fam, n)
            assert set(kernels) == set(table)
            for alg, ks in kernels.items():
                assert sum(k.flops for k in ks) == pytest.approx(
                    table[alg], rel=1e-12
                ), (fam, alg)


def test_chain_decomposition_is_flop_exact():
    from repro.expressions.chain import generate_chain_algorithms

    dims = (37, 91, 12, 55, 73)
    kernels = decompose_chain_dims(dims)
    algs = generate_chain_algorithms(dims)
    assert set(kernels) == {a.name for a in algs}
    for a in algs:
        assert sum(k.flops for k in kernels[a.name]) == float(a.flops)
        assert all(k.op == "gemm" for k in kernels[a.name])
        assert len(kernels[a.name]) == a.n_products


def test_kernel_spec_compact_roundtrip_and_labels():
    k = KernelSpec("gemm", (8, 4, 2))
    assert KernelSpec.from_compact(k.to_compact()) == k
    assert k.label == "gemm[8,4,2]"
    assert k.flops == 2.0 * 8 * 4 * 2
    assert kernel_name("alg0", 1, k) == "alg0::01.gemm"
    with pytest.raises(ValueError):
        KernelSpec("quantum_gemm", (8,))


def test_kernels_from_record_pointer_and_fallbacks():
    rec = {"family": "bilinear", "size": 32}
    by_alg = kernels_from_record(rec)                     # family fallback
    assert set(by_alg) == {"bilinear_left", "bilinear_right"}
    rec2 = {"family": "chain", "dims": [8, 4, 2, 6], "size": 5}
    assert kernels_from_record(rec2)                      # dims fallback
    rec3 = {"family": "bilinear", "size": 32,
            "params": {"size": 32, "seed": 0},
            "kernels": {"only": [["gemv", [32, 32]]]}}
    assert set(kernels_from_record(rec3)) == {"only"}     # pointer wins
    # an EMPTY pointer (chunk built pre-pointer, recorded post-upgrade)
    # must fall through to params, not return nothing
    rec4 = {"family": "bilinear", "size": 32, "kernels": {},
            "params": {"size": 32, "seed": 0}}
    assert set(kernels_from_record(rec4)) == {"bilinear_left", "bilinear_right"}


# ---------------------------------------------------- machines / roofline ---

def test_machine_registry_and_backcompat_aliases():
    tpu = get_machine("tpu-v5e")
    assert tpu is DEFAULT_MACHINE
    assert PEAK_FLOPS == tpu.peak_flops and HBM_BW == tpu.hbm_bw
    assert get_machine("cpu-1core").dispatch_overhead_s > 0
    with pytest.raises(KeyError):
        get_machine("abacus")
    rt = MachineSpec.from_dict(tpu.to_dict())
    assert rt == tpu


def test_synthetic_machine_predicts_pure_compute():
    m = synthetic_machine("sweep:test", 5e10)
    k = KernelSpec("gemm", (64, 64, 64))
    t, bound = kernel_roofline(k, m)
    assert t == pytest.approx(k.flops / 5e10)
    assert bound == "compute"
    # no memory system: bytes never dominate
    assert m.t_memory(1e18) == 0.0


def test_memory_bound_detection():
    m = MachineSpec("mem-starved", peak_flops=1e15, hbm_bw=1e6)
    t, bound = kernel_roofline(KernelSpec("gemv", (64, 64)), m)
    assert bound == "memory"


# -------------------------------------------------------------- classify ---

def _attr(alg, t_total, rows, machine):
    kernels = [KernelSpec(op, tuple(shape)) for op, shape, _ in rows]
    times = {
        kernel_name(alg, i, k): t for i, (k, (_, _, t)) in
        enumerate(zip(kernels, rows))
    }
    return attribute_algorithm(alg, t_total, kernels, times, machine)


def test_pick_winner_loser_both_reasons():
    base = {
        "uid": "u", "min_flops_algs": ["a0", "a1"],
        "ranks": {"a0": 1, "a1": 2, "b": 1},
        "mean_ranks": {"a0": 1.2, "a1": 2.0, "b": 1.0},
    }
    w, l = pick_winner_loser({**base, "reason": "min_flops_split"})
    assert (w, l) == ("b", "a1")  # best rank, then best mean rank, wins
    rec1 = {
        "uid": "u", "reason": "faster_outside_min_flops",
        "min_flops_algs": ["a0"],
        "ranks": {"a0": 2, "b": 1}, "mean_ranks": {"a0": 2.0, "b": 1.0},
    }
    assert pick_winner_loser(rec1) == ("b", "a0")
    with pytest.raises(ValueError):
        pick_winner_loser({
            "uid": "u", "reason": "none", "min_flops_algs": ["a0"],
            "ranks": {"a0": 1, "b": 2}, "mean_ranks": {"a0": 1.0, "b": 2.0},
        })


def test_classify_kernel_efficiency_and_dispatch():
    m = synthetic_machine("s", 1e9)
    rec = {"uid": "u", "reason": "faster_outside_min_flops"}
    # loser's single kernel runs 2x over the roof; winner at the roof
    w = _attr("w", 1.0e-3, [("gemm", (100, 100, 50), 1.0e-3)], m)
    l = _attr("l", 2.0e-3, [("gemm", (100, 100, 50), 2.0e-3)], m)
    e = classify_anomaly(rec, w, l)
    assert e.cause == "shape_kernel_efficiency"
    assert e.offending_algorithm == "l"
    assert e.offending_kernel == "gemm[100,100,50]"
    assert e.evidence == pytest.approx(1.0)
    # same kernels, but the gap lives between kernels (residual)
    l2 = _attr("l", 3.0e-3, [("gemm", (100, 100, 50), 1.0e-3)], m)
    e2 = classify_anomaly(rec, w, l2)
    assert e2.cause == "dispatch_overhead"
    assert e2.offending_kernel is None
    # memory-bound offender
    mm = MachineSpec("m", peak_flops=1e15, hbm_bw=1e6)
    w3 = _attr("w", 1.0e-3, [("gemv", (64, 64), 1.0e-3)], mm)
    l3 = _attr("l", 9.0e-3, [("gemv", (64, 64), 9.0e-3)], mm)
    e3 = classify_anomaly(rec, w3, l3)
    assert e3.cause == "memory_bound_segment"
    # no gap: the census ranking is not reproduced (evidence 0 without a
    # probe; the runner attaches the measured flip probability)
    e4 = classify_anomaly(rec, l, w)
    assert e4.cause == "not_reproducible" and e4.evidence == 0.0
    e5 = classify_anomaly(rec, l, w, flip_probability=0.75)
    assert e5.cause == "not_reproducible" and e5.evidence == 0.75


def test_classify_cache_reuse_pair_and_calibrated_roofline_split():
    m = synthetic_machine("s", 1e9)
    rec = {"uid": "u", "reason": "min_flops_split"}
    # winner's whole run beats its own kernel sum (negative residual):
    # adjacent kernels share cache; the pair with the largest handed-over
    # intermediate is named
    rows = [("gemm", (100, 100, 50), 1.0e-3), ("gemm", (100, 50, 100), 1.0e-3)]
    w = _attr("w", 1.2e-3, rows, m)
    l = _attr("l", 2.0e-3, rows, m)
    e = classify_anomaly(rec, w, l)
    assert e.cause == "cache_reuse_pair"
    assert e.offending_algorithm == "w"
    assert e.offending_kernel == "gemm[100,100,50]+gemm[100,50,100]"
    assert e.evidence == pytest.approx(1.0)
    # calibrated dispatch: both algorithms at their (dispatch-inclusive)
    # floors, the loser simply needs one more launch
    md = MachineSpec("d", peak_flops=1e12, hbm_bw=0.0,
                     dispatch_overhead_s=1e-6)
    t_k = 1e-6 + 2.0 * 100 * 100 * 50 / 1e12
    w2 = _attr("w", 2 * t_k, [("gemm", (100, 100, 50), t_k)] * 2, md)
    l2 = _attr("l", 3 * t_k, [("gemm", (100, 100, 50), t_k)] * 3, md)
    e2 = classify_anomaly(rec, w2, l2)
    assert e2.cause == "dispatch_overhead"
    # half the gap is the extra launch, the other half the extra math
    assert e2.evidence == pytest.approx(0.5)
    # calibrated memory: equal dispatch count, the loser's floor is bytes
    mm = MachineSpec("m", peak_flops=1e15, hbm_bw=1e8,
                     dispatch_overhead_s=1e-9)
    t_mem = 4.0 * (64 * 64 + 64 + 64) / 1e8
    w3 = _attr("w", 1e-6, [("dot", (64,), 1e-6)], mm)
    l3 = _attr("l", t_mem, [("gemv", (64, 64), t_mem)], mm)
    e3 = classify_anomaly(rec, w3, l3)
    assert e3.cause == "memory_bound_segment"
    assert e3.offending_kernel == "gemv[64,64]"


def test_classify_frequency_bimodality_takes_precedence():
    from repro.explain.distributions import SessionBimodality

    m = synthetic_machine("s", 1e9)
    rec = {"uid": "u", "reason": "min_flops_split"}
    w = _attr("w", 1.0e-3, [("gemm", (100, 100, 50), 1.0e-3)], m)
    l = _attr("l", 2.0e-3, [("gemm", (100, 100, 50), 2.0e-3)], m)
    bi = SessionBimodality(n_names=6, n_bimodal=5, mean_separation=30.0)
    e = classify_anomaly(rec, w, l, bimodality=bi)
    assert e.cause == "frequency_bimodality"
    assert e.evidence == pytest.approx(5 / 6)
    uni = SessionBimodality(n_names=6, n_bimodal=1, mean_separation=9.0)
    assert classify_anomaly(rec, w, l, bimodality=uni).cause == \
        "shape_kernel_efficiency"


def test_classify_insignificant_gap_needs_probe_confirmation():
    m = synthetic_machine("s", 1e9)
    rec = {"uid": "u", "reason": "min_flops_split"}
    w = _attr("w", 1.00e-3, [("gemm", (100, 100, 50), 1.00e-3)], m)
    l = _attr("l", 1.01e-3, [("gemm", (100, 100, 50), 1.01e-3)], m)
    # tiny gap, z below threshold, probe confirms the flip
    e = classify_anomaly(rec, w, l, gap_zscore=0.4, flip_probability=0.5)
    assert e.cause == "not_reproducible" and e.evidence == 0.5
    # same gap but the probe says the ranking holds: fall through to
    # the component logic (the whole gap is the kernel's excess here)
    e2 = classify_anomaly(rec, w, l, gap_zscore=0.4, flip_probability=0.0)
    assert e2.cause == "shape_kernel_efficiency"
    # significant gap never probes its way out
    e3 = classify_anomaly(rec, w, l, gap_zscore=25.0, flip_probability=0.9)
    assert e3.cause == "shape_kernel_efficiency"


# ----------------------------------------------------------- distributions ---

def test_mode_mixture_detects_two_frequency_modes():
    from repro.explain.distributions import mode_mixture

    rng = np.random.default_rng(7)
    base = np.exp(rng.normal(0.0, 0.01, 12))
    mask = np.array([True] * 4 + [False] * 8)
    bimodal = np.where(mask, base * 1.5, base)
    v = mode_mixture(bimodal)
    assert v.is_bimodal and v.minority == 4
    assert v.separation > 8.0
    assert v.mu_hi > v.mu_lo
    uni = mode_mixture(base)
    assert not uni.is_bimodal
    # a lone outlier is not a mode
    one = np.where(np.arange(12) == 0, base * 1.5, base)
    assert not mode_mixture(one).is_bimodal
    # exact two-level repeats (noiseless slow mode) separate infinitely
    v2 = mode_mixture([1.0] * 8 + [1.5] * 4)
    assert v2.is_bimodal and v2.separation > 1e6
    # degenerate sizes never crash
    assert not mode_mixture([1.0]).is_bimodal
    assert not mode_mixture([]).is_bimodal


def test_mode_mixture_false_positive_rate_on_unimodal_samples():
    from repro.explain.distributions import mode_mixture

    rng = np.random.default_rng(0)
    hits = sum(
        mode_mixture(np.exp(rng.normal(0.0, 0.02, 12))).is_bimodal
        for _ in range(500)
    )
    assert hits == 0, f"{hits}/500 unimodal sample sets flagged bimodal"


def test_session_bimodality_majority_vote():
    from repro.explain.distributions import session_bimodality

    rng = np.random.default_rng(3)

    def bimodal():
        x = np.exp(rng.normal(0.0, 0.01, 12))
        return np.where(rng.random(12) < 0.4, x * 1.5, x)

    def unimodal():
        return np.exp(rng.normal(0.0, 0.01, 12))

    s = session_bimodality({f"n{i}": bimodal() for i in range(6)})
    assert s.is_bimodal and s.share == 1.0 and s.mean_separation > 8.0
    s2 = session_bimodality(
        {**{f"b{i}": bimodal() for i in range(2)},
         **{f"u{i}": unimodal() for i in range(4)}}
    )
    assert not s2.is_bimodal and 0.0 < s2.share < 0.5
    assert not session_bimodality({}).is_bimodal


def test_median_gap_zscore():
    from repro.explain.distributions import median_gap_zscore

    rng = np.random.default_rng(5)
    w = 1.0 * np.exp(rng.normal(0.0, 0.02, 12))
    l = 2.0 * np.exp(rng.normal(0.0, 0.02, 12))
    gap, se, z = median_gap_zscore(w, l)
    assert gap == pytest.approx(1.0, rel=0.1) and se > 0 and z > 10
    # indistinguishable samples: |z| small
    _, _, z2 = median_gap_zscore(w, 1.0 * np.exp(rng.normal(0.0, 0.02, 12)))
    assert abs(z2) < 3
    # noiseless backend: exact tie is z=0, any gap is z=inf
    assert median_gap_zscore([1.0, 1.0], [1.0, 1.0])[2] == 0.0
    assert median_gap_zscore([1.0, 1.0], [2.0, 2.0])[2] == float("inf")


# -------------------------------------------------------------- calibration ---

def test_machine_eff_curve_interpolation_and_roundtrip():
    m = MachineSpec("c", peak_flops=1e12, hbm_bw=0.0,
                    eff_curve=((1e3, 0.1), (1e6, 1.0)))
    assert m.efficiency_at(1e2) == pytest.approx(0.1)   # clamped low
    assert m.efficiency_at(1e9) == pytest.approx(1.0)   # clamped high
    mid = m.efficiency_at(10 ** 4.5)                    # log-midpoint
    assert mid == pytest.approx(0.55)
    assert m.t_compute(1e3) == pytest.approx(1e3 / 1e11)
    # JSON round-trip keeps the curve (lists -> tuples normalised)
    rt = MachineSpec.from_dict(json.loads(json.dumps(m.to_dict())))
    assert rt == m
    # no curve = nominal peak (the historical behaviour)
    assert synthetic_machine("s", 1e9).t_compute(1e9) == pytest.approx(1.0)


def test_calibration_fit_recovers_synthetic_truth(tmp_path):
    from repro.explain.calibrate import (
        fit_calibration,
        load_calibrated_machine,
        micro_points_synthetic,
        synthetic_truth,
    )

    base = MachineSpec("cpu-test", peak_flops=5e10, hbm_bw=0.0)
    truth = synthetic_truth(base, dispatch_s=2e-6, eff_knee=64.0)
    points = micro_points_synthetic(truth, reps=25, seed=0, rel_sigma=0.01)
    res = fit_calibration(base, points)
    # a curved true efficiency bends the small-size points, so the linear
    # intercept carries an irreducible bias — dispatch and eff(flops) are
    # only jointly identifiable (with a flat truth the fit is exact, see
    # the tiny-instance acceptance test)
    assert res.dispatch_s == pytest.approx(2e-6, rel=0.35)
    assert res.r2 > 0.9
    # the fitted efficiency curve tracks eff(n) = n/(n+64) at the large
    # sizes (small ones are dispatch-dominated, so their math time — and
    # hence their efficiency — is poorly constrained by construction)
    for p in res.points:
        if p.n >= 64:
            assert p.efficiency == pytest.approx(p.n / (p.n + 64.0), rel=0.3)
    # calibrated spec round-trips through the save file
    path = str(tmp_path / "cal.json")
    res.save(path)
    loaded = load_calibrated_machine(path)
    assert loaded == res.machine
    assert loaded.dispatch_overhead_s == res.dispatch_s
    # the split is now meaningful below n=256: a tiny GEMM's floor is
    # mostly dispatch, a big one's is math
    tiny = loaded.t_compute(KernelSpec("gemm", (16, 16, 16)).flops)
    big = loaded.t_compute(KernelSpec("gemm", (256, 256, 256)).flops)
    assert loaded.dispatch_overhead_s > tiny
    assert loaded.dispatch_overhead_s < big


# --------------------------------------------- the census under explanation ---

#: Deterministic cost-model census with strong injected per-algorithm
#: efficiency factors (eff_sigma) and weak measurement noise — the
#: acceptance scenario's ground truth.
def _census_spec(**overrides):
    kwargs = dict(
        name="t",
        families={
            "chain": {"count": 20, "n_matrices": [3, 4], "lo": 24, "hi": 128},
            "bilinear": {"sizes": [32, 64], "per_size": 4},
        },
        n_shards=2,
        backend="cost_model",
        eff_sigma=0.25,
        noise_sigma=0.01,
        max_measurements=9,
        chunk_size=4,
        save_every=5,
    )
    kwargs.update(overrides)
    return SweepSpec(**kwargs)


@pytest.fixture(scope="module")
def census(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("census"))
    spec = _census_spec()
    spec.save(os.path.join(root, "spec.json"))
    for s in range(spec.n_shards):
        run_shard(spec, root, s)
    records = merge_shards(spec, root)
    anomalies = [r for r in records if r["is_anomaly"]]
    assert len(anomalies) >= 5, "fixture census must produce anomalies"
    return root, spec, records


def test_census_records_carry_explain_pointers(census):
    _, spec, records = census
    for r in records:
        assert r["params"], r["uid"]
        assert r["base_seed"] == spec.base_seed
        assert set(r["flops"]) == set(r["kernels"])
        # the pointer reproduces the pure-function decomposition
        assert r["kernels"] == {
            alg: [k.to_compact() for k in ks]
            for alg, ks in decompose_instance(r["family"], r["params"]).items()
        }


def test_explainer_recovers_injected_cause(census, tmp_path):
    """Acceptance: >= 90% of anomalies classified as shape-dependent kernel
    efficiency with the offending kernel identified, against the ground
    truth reconstructed from the synthetic machine's injected factors."""
    root, spec, records = census
    espec = ExplainSpec(census=root, n_shards=2, chunk_size=4, save_every=5)
    eroot = str(tmp_path / "explain")
    for s in range(espec.n_shards):
        run_explain_shard(espec, eroot, s)
    explained = merge_explained(espec, eroot)
    anomalies = [r for r in records if r["is_anomaly"]]
    assert [e["uid"] for e in explained] == [r["uid"] for r in anomalies]

    by_uid = {r["uid"]: r for r in records}
    n_cause = n_kernel = 0
    for e in explained:
        assert e["cause"] in CAUSES
        assert 0.0 <= e["evidence"] <= 1.0
        rec = by_uid[e["uid"]]
        if e["cause"] != "shape_kernel_efficiency":
            continue
        n_cause += 1
        # ground truth: redraw the injected efficiency factors and find the
        # kernel with the largest expected deviation from the roofline
        eff = synthetic_efficiencies(
            rec["flops"],
            np.random.default_rng([rec["base_seed"], rec["index"], 1]),
            spec.eff_sigma,
        )
        kernels = kernels_from_record(rec)
        expected = max(
            (
                (abs(k.flops * (eff[alg] - 1.0)), alg, k.label)
                for alg in (e["winner"], e["loser"])
                for k in kernels[alg]
            ),
            key=lambda t: t[0],
        )
        if (e["offending_algorithm"], e["offending_kernel"]) == expected[1:]:
            n_kernel += 1
    assert n_cause >= 0.9 * len(explained), (n_cause, len(explained))
    assert n_kernel >= 0.9 * n_cause, (n_kernel, n_cause)


def test_explain_resume_is_bit_identical(census, tmp_path):
    root, _, _ = census
    espec = ExplainSpec(census=root, n_shards=2, chunk_size=3, save_every=3)
    straight, chopped = str(tmp_path / "a"), str(tmp_path / "b")
    run_explain_shard(espec, straight, 0)
    for _ in range(300):
        run_explain_shard(espec, chopped, 0, max_steps=3)
        manifest = os.path.join(chopped, "shard-0000.manifest.json")
        if (os.path.exists(manifest)
                and json.load(open(manifest)).get("done")):
            break
    else:
        pytest.fail("explain shard did not finish in 300 slices")
    assert (open(os.path.join(chopped, "shard-0000.jsonl")).read()
            == open(os.path.join(straight, "shard-0000.jsonl")).read())


def test_explain_targets_and_progress(census, tmp_path):
    root, _, records = census
    espec = ExplainSpec(census=root, n_shards=3)
    _, targets = explain_targets(espec)
    assert [t["uid"] for t in targets] == [
        r["uid"] for r in records if r["is_anomaly"]
    ]
    eroot = str(tmp_path / "explain")
    prog = explain_progress(espec, eroot)
    assert prog["anomalies"] == len(targets) and prog["completed"] == 0
    run_explain_shard(espec, eroot, 1)
    prog = explain_progress(espec, eroot)
    assert prog["completed"] == prog["shards"][1]["done"] > 0


def test_resolve_machine_follows_backend(census):
    root, spec, _ = census
    espec = ExplainSpec(census=root)
    m = resolve_machine(espec, spec, {})
    assert m.peak_flops == spec.flop_rate and m.hbm_bw == 0.0
    espec2 = ExplainSpec(census=root, machine="tpu-v5e")
    assert resolve_machine(espec2, spec, {}).name == "tpu-v5e"
    # wall clock: the machine of the device kind that measured the record
    wall = _census_spec(backend="wall_clock")
    assert resolve_machine(espec, wall, {"device_kind": "cpu"}).name == "cpu-1core"
    assert resolve_machine(
        espec, wall, {"device_kind": "TPU v5 lite"}).name == "tpu-v5e"
    with pytest.raises(ValueError, match="device"):
        resolve_machine(espec, wall, {})


def test_unknown_device_kind_raises():
    """A device with no published peaks has no roofline: an error, never
    another device's floor."""
    assert machine_for_device("TPU v5 lite") is get_machine("tpu-v5e")
    with pytest.raises(KeyError, match="abacus"):
        machine_for_device("abacus")
    wall = _census_spec(backend="wall_clock")
    with pytest.raises(KeyError, match="abacus"):
        resolve_machine(ExplainSpec(), wall, {"device_kind": "abacus"})


def test_explain_summary_and_tables(census, tmp_path):
    root, _, _ = census
    espec = ExplainSpec(census=root, n_shards=1)
    eroot = str(tmp_path / "explain")
    run_explain_shard(espec, eroot, 0)
    explained = merge_explained(espec, eroot)
    s = explain_summary(explained)
    assert s["total"] == len(explained)
    assert abs(sum(a["share"] for a in s["by_cause"].values()) - 1.0) < 1e-9
    assert 0.0 <= s["mean_evidence"] <= 1.0

    from repro.launch.report_md import explain_tables

    md = explain_tables(explained, name="t")
    assert "anomaly root causes" in md
    assert "| cause |" in md and "shape_kernel_efficiency" in md


# ------------------------------------------------ taxonomy v2 ground truth ---

def _run_census(root, **overrides):
    spec = _census_spec(**overrides)
    spec.save(os.path.join(root, "spec.json"))
    for s in range(spec.n_shards):
        run_shard(spec, root, s)
    return spec, merge_shards(spec, root)


def _run_explain(root, eroot, **espec_overrides):
    espec = ExplainSpec(census=root, n_shards=2, chunk_size=4, save_every=5,
                        **espec_overrides)
    for s in range(espec.n_shards):
        run_explain_shard(espec, eroot, s)
    return espec, merge_explained(espec, eroot)


def test_explainer_recovers_injected_bimodality(tmp_path):
    """Acceptance: anomalies of a turbo-regime (bimodal simulated) census
    come back >= 90% frequency_bimodality with evidence > 0 — the
    mode-mixture test sees the regime in the segment distributions."""
    root = str(tmp_path / "census")
    os.makedirs(root)
    spec, records = _run_census(
        root,
        families={
            "chain": {"count": 60, "n_matrices": [3, 4], "lo": 24, "hi": 128},
            "bilinear": {"sizes": [32, 48, 64], "per_size": 10},
        },
        backend="simulated", eff_sigma=0.02, noise_sigma=0.01,
        bimodal_shift=0.5, bimodal_prob=0.35, bimodal_frac=1.0,
        max_measurements=12,
    )
    anomalies = [r for r in records if r["is_anomaly"]]
    assert len(anomalies) >= 5, "bimodal census must produce anomalies"
    # eps < 0: every session runs its full budget, so each measured name
    # holds max_measurements samples for the mixture test
    _, explained = _run_explain(root, str(tmp_path / "explain"),
                                eps=-1.0, max_measurements=12)
    hits = [e for e in explained
            if e["cause"] == "frequency_bimodality" and e["evidence"] > 0]
    assert len(hits) >= 0.9 * len(explained), (len(hits), len(explained))
    for e in hits:
        assert e["bimodality"]["is_bimodal"]
        assert e["bimodality"]["mean_separation"] >= 8.0


def test_explainer_recovers_injected_cache_reuse_pair(tmp_path):
    """Acceptance: anomalies whose winner carries an injected whole-run
    cache-reuse saving (and whose loser does not) come back >= 90%
    cache_reuse_pair, with the pair named from the winner's kernels."""
    from repro.core.sweep import synthetic_instance_model

    root = str(tmp_path / "census")
    os.makedirs(root)
    spec, records = _run_census(
        root,
        families={
            "chain": {"count": 40, "n_matrices": [3, 4], "lo": 24, "hi": 128},
            "bilinear": {"sizes": [32, 48, 64], "per_size": 8},
        },
        eff_sigma=0.0, noise_sigma=0.01,
        cache_reuse_frac=0.5, cache_reuse_saving=0.4,
        max_measurements=12,
    )
    _, explained = _run_explain(root, str(tmp_path / "explain"))
    by_uid = {r["uid"]: r for r in records}
    truth = []
    for e in explained:
        r = by_uid[e["uid"]]
        model = synthetic_instance_model(
            spec, r["index"], r["flops"],
            {a: len(ks) for a, ks in r["kernels"].items()},
            base_seed=r["base_seed"],
        )
        if (model.cache_saving[e["winner"]] > 0
                and model.cache_saving[e["loser"]] == 0):
            truth.append(e)
    assert len(truth) >= 5, "census must produce winner-reused anomalies"
    hits = [e for e in truth
            if e["cause"] == "cache_reuse_pair" and e["evidence"] > 0]
    assert len(hits) >= 0.9 * len(truth), (len(hits), len(truth))
    for e in hits:
        # the pair is named, belongs to the winner, and is adjacent
        assert e["offending_algorithm"] == e["winner"]
        a, b = e["offending_kernel"].split("+")
        labels = [k["kernel"] for k in e["attribution"]["winner"]["kernels"]]
        i = labels.index(a)
        assert labels[i + 1] == b
        # and the winner's whole run beats its kernel sum
        assert e["attribution"]["winner"]["residual"] < 0


def test_explainer_flags_pure_noise_flips_not_reproducible(tmp_path):
    """Acceptance: anomalies of an eff_sigma=0 census (equal-FLOPs ties
    ranked on measurement noise alone) come back >= 90% not_reproducible,
    each backed by a probed flip probability > 0."""
    root = str(tmp_path / "census")
    os.makedirs(root)
    spec, records = _run_census(
        root,
        families={"bilinear": {"sizes": [32, 48, 64, 96], "per_size": 10}},
        eff_sigma=0.0, noise_sigma=0.05, max_measurements=12,
    )
    anomalies = [r for r in records if r["is_anomaly"]]
    assert len(anomalies) >= 5, "noise census must produce anomalies"
    _, explained = _run_explain(root, str(tmp_path / "explain"))
    hits = [e for e in explained
            if e["cause"] == "not_reproducible" and e["evidence"] > 0]
    assert len(hits) >= 0.9 * len(explained), (len(hits), len(explained))
    for e in hits:
        assert e["flip_probability"] is not None
        assert e["evidence"] == e["flip_probability"]


def test_explainer_calibrated_dispatch_split_on_tiny_instances(tmp_path):
    """Acceptance: a dispatch-dominated tiny-instance census is
    misattributed to kernel efficiency against the nominal (dispatch-free)
    roofline, and comes back >= 90% dispatch_overhead once the explain
    campaign reconciles against a machine calibrated from
    micro-measurements — the calibrated memory-vs-dispatch split."""
    from repro.explain.calibrate import (
        fit_calibration,
        micro_points_synthetic,
        synthetic_truth,
    )

    root = str(tmp_path / "census")
    os.makedirs(root)
    spec, records = _run_census(
        root,
        families={"solve": {"sizes": [8, 12, 16, 24, 32], "per_size": 6}},
        eff_sigma=0.0, noise_sigma=0.01, dispatch_s=2e-6,
        max_measurements=12,
    )
    anomalies = [r for r in records if r["is_anomaly"]]
    assert len(anomalies) >= 5, "dispatch census must produce anomalies"

    # uncalibrated: the per-kernel dispatch masquerades as inefficiency
    _, naive = _run_explain(root, str(tmp_path / "naive"))
    assert any(e["cause"] != "dispatch_overhead" for e in naive)

    # calibrate the census's machine from synthetic micro-measurements of
    # the same ground truth (flat efficiency, 2us dispatch), then explain
    # against the fitted spec
    base = MachineSpec(f"sweep:{spec.name}", peak_flops=spec.flop_rate,
                       hbm_bw=0.0)
    truth = synthetic_truth(base, dispatch_s=spec.dispatch_s, eff_knee=0.0)
    points = micro_points_synthetic(
        truth, sizes=(8, 12, 16, 24, 32, 48, 64, 96, 128),
        reps=25, seed=0, rel_sigma=0.01,
    )
    result = fit_calibration(base, points)
    assert result.dispatch_s == pytest.approx(spec.dispatch_s, rel=0.2)
    cal_path = str(tmp_path / "cal.json")
    result.save(cal_path)

    _, explained = _run_explain(root, str(tmp_path / "explain"),
                                machine_file=cal_path)
    hits = [e for e in explained
            if e["cause"] == "dispatch_overhead" and e["evidence"] > 0]
    assert len(hits) >= 0.9 * len(explained), (len(hits), len(explained))
    # the dispatch term of the roofline difference carries the gap
    for e in hits:
        assert e["components"]["roofline_dispatch"] > 0


# -------------------------------------------------------- CLI + kill/resume ---

#: Census grid for the CLI tests: enough anomalies that a mid-run SIGKILL
#: lands while explain shards are in flight.
CLI_CENSUS = [
    "--chains", "40", "--chain-sizes", "3,4", "--lo", "24", "--hi", "160",
    "--families", "bilinear", "--sizes", "32,64", "--per-size", "6",
    "--shards", "4", "--eff-sigma", "0.3", "--noise-sigma", "0.01",
    "--max-measurements", "9", "--chunk-size", "4", "--save-every", "5",
]
#: eps < 0 never converges: every explanation runs its full measurement
#: budget, keeping the campaign long enough to kill deterministically.
CLI_EXPLAIN = ["--eps", "-1.0", "--max-measurements", "24",
               "--shards", "4", "--chunk-size", "2", "--save-every", "4"]


def _cli(module, args, **kwargs):
    cmd = [sys.executable, "-m", module] + args
    return subprocess.run(
        cmd, env=_env(), capture_output=True, text=True, timeout=300, **kwargs
    )


@pytest.fixture(scope="module")
def cli_census(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("cli") / "census")
    done = _cli("repro.launch.sweep",
                ["run", "--out", out, "--workers", "2"] + CLI_CENSUS)
    assert done.returncode == 0, done.stderr
    return out


def test_cli_kill_resume_explain_identical(cli_census, tmp_path):
    """The acceptance scenario: multi-worker explain, SIGKILL of the whole
    process group mid-campaign, resume, merged explanations identical to an
    uninterrupted run."""
    straight, killed = str(tmp_path / "straight"), str(tmp_path / "killed")

    done = _cli("repro.launch.explain",
                ["run", "--census", cli_census, "--out", straight,
                 "--workers", "2"] + CLI_EXPLAIN)
    assert done.returncode == 0, done.stderr
    n_anoms = open(os.path.join(straight, "merged.jsonl")).read().count("\n")
    assert n_anoms >= 8, "census produced too few anomalies; enlarge CLI_CENSUS"

    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.explain", "run",
         "--census", cli_census, "--out", killed, "--workers", "2"]
        + CLI_EXPLAIN,
        env=_env(), start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            if proc.poll() is not None:
                break
            jsonls = [f for f in os.listdir(killed)
                      if f.endswith(".jsonl")] if os.path.isdir(killed) else []
            if any(os.path.getsize(os.path.join(killed, f)) > 0 for f in jsonls):
                break
            time.sleep(0.005)
        was_running = proc.poll() is None
        os.killpg(proc.pid, signal.SIGKILL)
    finally:
        proc.wait()
    assert was_running, "explain finished before the kill; enlarge the grid"

    resumed = _cli("repro.launch.explain",
                   ["run", "--out", killed, "--workers", "2"])
    assert resumed.returncode == 0, resumed.stderr
    assert (open(os.path.join(killed, "merged.jsonl")).read()
            == open(os.path.join(straight, "merged.jsonl")).read())

    report = _cli("repro.launch.explain", ["report", "--out", killed])
    assert report.returncode == 0, report.stderr
    assert "anomaly root causes" in report.stdout


def test_cli_status_merge_and_plan_guard(cli_census, tmp_path):
    out = str(tmp_path / "explain")
    plan = _cli("repro.launch.explain",
                ["plan", "--census", cli_census, "--out", out, "--shards", "2"])
    assert plan.returncode == 0, plan.stderr
    assert "anomaly explanations over 2 shards" in plan.stdout
    # out == census would interleave census and explain shard files
    clash = _cli("repro.launch.explain",
                 ["plan", "--census", cli_census, "--out", cli_census])
    assert clash.returncode != 0
    run = _cli("repro.launch.explain", ["run", "--out", out, "--workers", "2"])
    assert run.returncode == 0, run.stderr
    status = _cli("repro.launch.explain", ["status", "--out", out])
    assert status.returncode == 0 and "anomalies explained" in status.stdout
    merge = _cli("repro.launch.explain", ["merge", "--out", out])
    assert merge.returncode == 0 and "explanations ->" in merge.stdout
    rj = _cli("repro.launch.explain", ["report", "--out", out, "--json"])
    assert rj.returncode == 0
    summary = json.loads(rj.stdout)
    assert summary["total"] > 0 and "by_cause" in summary


def test_cli_status_on_partially_merged_shard_store(cli_census, tmp_path):
    """`status` must stay truthful while the campaign is part-way done:
    some shards fully explained, one paused mid-chunk (engine state on
    disk), others untouched — and again after a partial `merge`."""
    out = str(tmp_path / "explain")
    plan = _cli("repro.launch.explain",
                ["plan", "--census", cli_census, "--out", out,
                 "--shards", "3"] + CLI_EXPLAIN[:2])
    assert plan.returncode == 0, plan.stderr
    # shard 0: complete; shard 1: paused mid-chunk; shard 2: untouched
    done = _cli("repro.launch.explain", ["work", "--out", out, "--shards", "0"])
    assert done.returncode == 0, done.stderr
    paused = _cli("repro.launch.explain",
                  ["work", "--out", out, "--shards", "1",
                   "--max-steps-per-shard", "3"])
    assert paused.returncode == 0, paused.stderr
    status = _cli("repro.launch.explain", ["status", "--out", out])
    assert status.returncode == 0, status.stderr
    lines = status.stdout.splitlines()
    assert "anomalies explained" in lines[0]
    shard_lines = [l for l in lines if "shard" in l]
    assert len(shard_lines) == 3
    import re

    counts = {}
    for line in shard_lines:
        m = re.search(r"shard\s+(\d+): (\d+)/(\d+)", line)
        counts[int(m.group(1))] = (int(m.group(2)), int(m.group(3)))
    assert counts[0][0] == counts[0][1] > 0      # complete
    assert counts[1][0] < counts[1][1]           # paused part-way
    assert "chunk in flight" in [l for l in shard_lines if "shard    1" in l][0]
    assert counts[2] == (0, counts[2][1])        # untouched
    # merging the partial store works and reports only completed shards
    merge = _cli("repro.launch.explain", ["merge", "--out", out])
    assert merge.returncode == 0, merge.stderr
    n_merged = int(merge.stdout.split("merged ")[1].split(" ")[0])
    assert n_merged == counts[0][0] + counts[1][0]
    # status is unchanged by the merge (shard JSONLs stay authoritative)
    status2 = _cli("repro.launch.explain", ["status", "--out", out])
    assert status2.returncode == 0, status2.stderr
    assert [l for l in status2.stdout.splitlines() if "shard" in l] == shard_lines


def test_cli_calibrate_synthetic_roundtrip(tmp_path):
    out_file = str(tmp_path / "cal.json")
    cal = _cli("repro.launch.explain",
               ["calibrate", "--out-file", out_file,
                "--backend", "synthetic", "--peak-flops", "5e10",
                "--machine", "synthcal", "--truth-dispatch-us", "2.0",
                "--truth-eff-knee", "64", "--reps", "25"])
    assert cal.returncode == 0, cal.stderr
    assert "dispatch" in cal.stdout and "--machine-file" in cal.stdout
    from repro.explain.calibrate import load_calibrated_machine

    m = load_calibrated_machine(out_file)
    assert m.name == "synthcal:calibrated"
    assert m.dispatch_overhead_s == pytest.approx(2e-6, rel=0.3)
    assert len(m.eff_curve) >= 3


def test_sweep_status_reports_running_anomaly_counts(cli_census):
    status = _cli("repro.launch.sweep", ["status", "--out", cli_census])
    assert status.returncode == 0, status.stderr
    assert "anomalies so far:" in status.stdout
    assert "chain=" in status.stdout and "bilinear=" in status.stdout
