"""AlgorithmFamily registry: the census's one algorithm-source seam.

Covers the registry contract, byte-identity of the ported synthetic
families against a pre-refactor golden store, the kernel_variants
family's FLOP-identical-by-construction invariants, the store-kind
registry behind queue/fsck auto-detection, and the jax-free metadata
guarantee for cost-model census workers."""

import json
import os
import subprocess
import sys

import pytest

from repro.core.family import (
    AlgorithmFamily,
    InstanceSpec,
    KERNEL_SITES,
    TOY_KERNEL_SITES,
    family_names,
    get_family,
    register_family,
)
from repro.core.sweep import SweepSpec, instance_entry, run_shard, write_merged

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "census_small.jsonl")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS"):
        env.setdefault(var, "1")
    return env


# -------------------------------------------------------------- registry ---

def test_registry_contents_and_order():
    assert family_names() == (
        "chain", "gram", "distributive", "solve", "bilinear",
        "kernel_variants",
    )
    for name in family_names():
        fam = get_family(name)
        assert fam.name == name
        assert fam.description  # the report footnotes render these


def test_get_family_unknown_raises_listing_known():
    with pytest.raises(KeyError, match="kernel_variants"):
        get_family("strassen")


def test_register_family_requires_name():
    with pytest.raises(ValueError):
        register_family(AlgorithmFamily())


def test_sweep_spec_rejects_unregistered_family():
    with pytest.raises(ValueError, match="unknown families"):
        SweepSpec(families={"strassen": {}})


def test_instance_spec_roundtrip():
    inst = InstanceSpec(index=3, uid="chain-n3-i00003", family="chain",
                        params={"n_matrices": 3, "lo": 24, "hi": 96, "seed": 3})
    assert InstanceSpec.from_dict(inst.to_dict()) == inst
    # core.sweep re-exports the moved class unchanged
    from repro.core import sweep
    assert sweep.InstanceSpec is InstanceSpec


# ----------------------------------- synthetic expansion (byte-identity) ---

def test_expansion_snapshot_uids_and_params():
    """The exact pre-refactor uid/params rows for every synthetic family —
    any drift here silently orphans existing census stores."""
    spec = SweepSpec(families={
        "chain": {"count": 3, "n_matrices": [3, 4], "lo": 24, "hi": 96},
        "gram": {"sizes": [24], "per_size": 2},
        "bilinear": {"sizes": [40], "per_size": 1},
    })
    rows = [(i.index, i.uid, i.family, i.params) for i in spec.expand()]
    assert rows == [
        (0, "bilinear-n40-s000", "bilinear", {"size": 40, "seed": 0}),
        (1, "chain-n3-i00000", "chain",
         {"n_matrices": 3, "lo": 24, "hi": 96, "seed": 0}),
        (2, "chain-n4-i00001", "chain",
         {"n_matrices": 4, "lo": 24, "hi": 96, "seed": 1}),
        (3, "chain-n3-i00002", "chain",
         {"n_matrices": 3, "lo": 24, "hi": 96, "seed": 2}),
        (4, "gram-n24-s000", "gram", {"size": 24, "seed": 0}),
        (5, "gram-n24-s001", "gram", {"size": 24, "seed": 1}),
    ]


def test_golden_census_byte_identical(tmp_path):
    """A small all-families cost-model census, run through the registry,
    must merge byte-identical to the committed pre-refactor golden store
    (captured before the AlgorithmFamily seam existed)."""
    spec = SweepSpec(
        name="census",
        families={
            "chain": {"count": 8, "n_matrices": [3, 4], "lo": 24, "hi": 96},
            "gram": {"sizes": [24, 40], "per_size": 2},
            "distributive": {"sizes": [24, 40], "per_size": 2},
            "solve": {"sizes": [24, 40], "per_size": 2},
            "bilinear": {"sizes": [24, 40], "per_size": 2},
        },
        n_shards=4,
        backend="cost_model",
        max_measurements=12,
    )
    root = str(tmp_path / "census")
    for shard in range(spec.n_shards):
        run_shard(spec, root, shard)
    merged = write_merged(spec, root)
    with open(merged, "rb") as fh:
        got = fh.read()
    with open(GOLDEN, "rb") as fh:
        want = fh.read()
    assert got == want


# ------------------------------------------------------- kernel_variants ---

def _kv_inst(site, size, seed=0):
    return InstanceSpec(
        index=0, uid=f"kernel_variants-{site}-n{size}-s{seed:03d}",
        family="kernel_variants",
        params={"site": site, "size": size, "seed": seed},
    )


def test_kernel_variants_expansion():
    fam = get_family("kernel_variants")
    rows = fam.expand_grid({"sites": ["matmul", "ssd"], "sizes": [32, 64],
                            "per_size": 2})
    assert [i.uid for i in rows] == [
        "kernel_variants-matmul-n32-s000", "kernel_variants-matmul-n32-s001",
        "kernel_variants-matmul-n64-s000", "kernel_variants-matmul-n64-s001",
        "kernel_variants-ssd-n32-s000", "kernel_variants-ssd-n32-s001",
        "kernel_variants-ssd-n64-s000", "kernel_variants-ssd-n64-s001",
    ]
    # the Pallas mode follows the backend, so no row carries one
    assert all(set(i.params) == {"site", "size", "seed"} for i in rows)
    with pytest.raises(ValueError, match="unknown kernel site"):
        fam.expand_grid({"sites": ["conv"], "sizes": [32]})
    with pytest.raises(ValueError, match="chunk lengths"):
        # 24 only divides by chunk 8 -> fewer than 2 ssd variants
        fam.expand_grid({"sites": ["ssd"], "sizes": [24]})


@pytest.mark.parametrize("size,tiles", [
    (64, ["blocks_64x64x64"]),
    (256, ["blocks_128x128x128", "blocks_256x256x256"]),
    (4096, ["blocks_128x128x128", "blocks_256x256x256", "blocks_512x512x512"]),
])
def test_matmul_site_tiles_are_tpu_legal(size, tiles):
    """A TPU block's last two dims are multiples of (8, 128) or the whole
    array: tiles of 128/256/512 capped at the size, else one whole-array
    tile, always beside the XLA dot baseline."""
    flops, _, _ = instance_entry(_kv_inst("matmul", size))
    assert sorted(flops) == tiles + ["xla_dot"]


def test_kernel_variants_flop_identical_by_construction():
    """Every variant of an instance carries the same analytic FLOP count
    and the same kernel decomposition (the shared math), so the whole
    instance sits in S_F and can never be RT-filtered apart. (The moe site
    has no toy instances: it runs a named model's layers.)"""
    assert set(KERNEL_SITES) - set(TOY_KERNEL_SITES) == {"moe"}
    for site in TOY_KERNEL_SITES:
        for size in (32, 64):
            inst = _kv_inst(site, size)
            flops, meta, _ = instance_entry(inst)
            assert len(flops) >= 2, (site, size)
            assert len(set(flops.values())) == 1, (site, flops)
            kernel_rows = set(map(str, meta["kernels"].values()))
            assert len(kernel_rows) == 1  # one shared decomposition
            decomp = get_family("kernel_variants").decompose(inst.params)
            assert set(decomp) == set(flops)
            for alg, ks in decomp.items():
                assert sum(k.flops for k in ks) == pytest.approx(flops[alg])
                assert all(k.op == "gemm" for k in ks)


def test_kernel_variants_decompose_via_decompose_instance():
    from repro.explain.decompose import decompose_instance

    inst = _kv_inst("attention", 32)
    ks = decompose_instance(inst.family, inst.params)
    assert set(ks) == {"reference_grouped", "reference_broadcast",
                      "chunked_flash"}
    b, h, s, d = 1, 2, 32, 16
    total = sum(k.flops for k in ks["chunked_flash"])
    assert total == pytest.approx(2.0 * b * h * s * s * d * 2)


def test_kernel_config_expands_the_models_attention_layers():
    """With a model config the attention site runs one instance per
    attention layer kind of the model and seed, the widths in the row; the
    other sites are as before."""
    fam = get_family("kernel_variants")
    rows = fam.expand_grid({"sites": ["attention", "matmul"], "sizes": [8192],
                            "per_size": 2, "config": "trinity-mini"})
    assert [i.uid for i in rows] == [
        "kernel_variants-attention-trinity-mini-sliding-n8192-s000",
        "kernel_variants-attention-trinity-mini-sliding-n8192-s001",
        "kernel_variants-attention-trinity-mini-full-n8192-s000",
        "kernel_variants-attention-trinity-mini-full-n8192-s001",
        "kernel_variants-matmul-n8192-s000", "kernel_variants-matmul-n8192-s001",
    ]
    assert rows[0].params == {"site": "attention", "config": "trinity-mini",
                              "layer": "sliding", "size": 8192, "seed": 0, "heads": 32,
                              "kv_heads": 4, "head_dim": 128, "window": 2048}
    assert rows[2].params["window"] is None
    with pytest.raises(ValueError, match="not a multiple"):
        fam.expand_grid({"sites": ["attention"], "sizes": [1000], "config": "trinity-mini"})


def test_census_plan_names_a_kernel_config(tmp_path):
    """The normal CLI path: ``census plan --kernel-config`` writes a spec
    whose grid censuses the model's layer kinds."""
    out = str(tmp_path / "census")
    run = subprocess.run(
        [sys.executable, "-m", "repro", "census", "plan", "--out", out, "--chains", "0",
         "--families", "kernel_variants", "--kernel-sites", "attention",
         "--kernel-config", "trinity-mini", "--sizes", "8192", "--per-size", "1",
         "--shards", "1", "--backend", "wall_clock"],
        env=_env(), capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr
    with open(os.path.join(out, "spec.json")) as fh:
        spec = SweepSpec(**{k: v for k, v in json.load(fh).items()
                            if k in SweepSpec.__dataclass_fields__})
    assert spec.families["kernel_variants"]["config"] == "trinity-mini"
    assert [i.params["layer"] for i in spec.expand()] == ["sliding", "full"]


def test_kernel_variants_metadata_needs_no_jax():
    """A cost-model census worker building kernel_variants sessions (and
    stepping them) must never import jax — the family's FLOP tables and
    kernel decompositions are pure metadata."""
    code = """
import sys
from repro.core.sweep import SweepSpec, build_sweep_session, record_from_session
spec = SweepSpec(
    name="kv", backend="cost_model", n_shards=1, max_measurements=6,
    families={"kernel_variants": {"sites": ["matmul", "attention", "ssd"],
                                  "sizes": [32], "per_size": 1}},
)
for inst in spec.expand():
    session = build_sweep_session(spec, inst)
    while session.step():
        pass
    record = record_from_session(session, spec)
    assert record["family"] == "kernel_variants"
assert "jax" not in sys.modules, "jax imported on the cost_model path"
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], env=_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_lazy_package_imports_need_no_jax():
    """Satellite: importing repro.autotune / repro.kernels themselves (the
    kernel family's metadata neighbours) must not pull in jax until an
    attribute is resolved."""
    code = """
import sys
import repro.autotune
import repro.kernels
assert "jax" not in sys.modules, "package import pulled in jax"
assert sorted(repro.kernels.__all__) == [
    "chain_matmul", "flash_attention", "matmul", "ssd_mix"]
assert "VariantSite" in repro.autotune.__all__
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], env=_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_lazy_package_attributes_resolve():
    import repro.autotune
    import repro.kernels

    assert callable(repro.autotune.matmul_blocks_site)
    # `chain_matmul`/`ssd_mix` have no like-named subpackage, so the lazy
    # resolution is import-order-immune in-suite; `matmul` and
    # `flash_attention` can be shadowed by their subpackages after a
    # dotted import (pytest collection imports test_kernels.py), so their
    # clean-order behaviour is asserted in a fresh interpreter below
    assert callable(repro.kernels.chain_matmul)
    assert callable(repro.kernels.ssd_mix)


def test_lazy_kernel_callables_resolve_in_clean_order():
    """In a fresh interpreter, every exported kernel name resolves to a
    callable through the lazy ``__getattr__`` — including the two that
    share their name with a subpackage."""
    code = """
import repro.kernels
for name in repro.kernels.__all__:
    assert callable(getattr(repro.kernels, name)), name
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], env=_env(),
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


# ------------------------------------------------------------ explainer ---

def test_explain_workloads_defaults_to_entry_filter():
    """An explanation builds, and warms, only the involved variants of the
    family's site, on the instance's seed."""
    from repro.core.programs import Variant, VariantSite

    built, seeds = [], []

    def variant(name, out):
        def build(x):
            built.append(name)
            return lambda: out + x
        return Variant(name, 1.0, build)

    class Toy(AlgorithmFamily):
        name = "toy-test-family"
        description = "toy"

        def variant_site(self, params):
            return VariantSite("toy", (variant("a", 1), variant("b", 2), variant("c", 3)),
                               lambda seed: seeds.append(seed) or [10])

    fam = Toy()
    out = fam.explain_workloads(
        InstanceSpec(index=0, uid="t", family="toy-test-family", params={"seed": 4}),
        ["c", "b"],
    )
    assert list(out) == ["c", "b"] and built == ["c", "b"] and seeds == [4]
    assert out["b"]() == 12


# ------------------------------------------------------------ store kinds ---

def test_store_kind_detection(tmp_path):
    from repro.core.stores import (
        AmbiguousStore,
        detect_store_kind,
        store_kinds,
    )

    assert [k.name for k in store_kinds()] == ["sweep", "explain", "oracle"]
    root = str(tmp_path)
    assert detect_store_kind(root) is None
    with open(os.path.join(root, "spec.json"), "w") as fh:
        json.dump({}, fh)
    assert detect_store_kind(root).name == "sweep"
    os.replace(os.path.join(root, "spec.json"),
               os.path.join(root, "espec.json"))
    assert detect_store_kind(root).name == "explain"
    os.replace(os.path.join(root, "espec.json"),
               os.path.join(root, "ocache.json"))
    assert detect_store_kind(root).name == "oracle"
    os.replace(os.path.join(root, "ocache.json"),
               os.path.join(root, "espec.json"))
    with open(os.path.join(root, "spec.json"), "w") as fh:
        json.dump({}, fh)
    with pytest.raises(AmbiguousStore, match="multiple campaign kinds"):
        detect_store_kind(root)


def test_store_kind_registry_rejects_spec_file_collision():
    from repro.core.stores import StoreKind, register_store_kind

    with pytest.raises(ValueError, match="already claimed"):
        register_store_kind(StoreKind(name="other-sweep",
                                      spec_file="spec.json"))


def test_open_queue_routes_through_registry(tmp_path):
    from repro.launch.queue import open_queue

    with pytest.raises(SystemExit, match="known store kinds"):
        open_queue(str(tmp_path))
    # an ambiguous root refuses instead of silently draining as a sweep
    for name in ("spec.json", "espec.json"):
        with open(os.path.join(str(tmp_path), name), "w") as fh:
            json.dump({}, fh)
    with pytest.raises(SystemExit, match="multiple campaign kinds"):
        open_queue(str(tmp_path))


def test_fsck_store_kind_reports_ambiguous(tmp_path):
    from repro.launch.fsck import _detect_n_shards, _store_kind

    root = str(tmp_path)
    assert _store_kind(root) == "unknown"
    for name in ("spec.json", "espec.json"):
        with open(os.path.join(root, name), "w") as fh:
            json.dump({}, fh)
    assert _store_kind(root) == "ambiguous"
    # n-shard detection falls back to scanning shard files
    open(os.path.join(root, "shard-0002.jsonl"), "w").close()
    assert _detect_n_shards(root) == 3


# ---------------------------------------------------------------- report ---

def test_census_report_carries_family_footnotes():
    from repro.launch.report_md import census_tables

    records = [{
        "uid": "kernel_variants-matmul-n32-s000", "index": 0,
        "family": "kernel_variants", "size": 32, "is_anomaly": True,
        "reason": "min_flops_split", "converged": True,
    }]
    md = census_tables(records, name="kv")
    assert "*kernel_variants*:" in md
    assert "Pallas" in md
