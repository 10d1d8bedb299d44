"""VariantSite invariants for the sites the kernel_variants census family
wraps: analytic FLOP counts cross-checked against the explainer's roofline
kernel table, variant-output equivalence in Pallas interpret mode on CPU
(the wall-clock CI lane's correctness precondition — ranking variants
that compute different things would be meaningless), and the rule that
ties the Pallas mode to the backend."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.autotune import attention_site, matmul_blocks_site, ssd_chunk_site
from repro.explain.decompose import KernelSpec


def _outputs(site, seed=0):
    arrays = site.make_inputs(seed)
    return {v.name: np.asarray(v.build(*arrays)()) for v in site.variants}


# ----------------------------------------------------------------- matmul ---

def test_matmul_site_flops_match_roofline_gemm():
    m, k, n = 48, 32, 64
    site = matmul_blocks_site(m=m, k=k, n=n, blocks=[(16, 16, 16)])
    want = KernelSpec("gemm", (m, k, n)).flops  # the roofline table's 2mkn
    assert want == 2.0 * m * k * n
    for name, f in site.flops_table().items():
        assert f == pytest.approx(want), name


def test_matmul_variants_equivalent_interpret():
    site = matmul_blocks_site(m=32, k=32, n=32,
                              blocks=[(16, 16, 16), (32, 32, 32)])
    outs = _outputs(site)
    assert set(outs) == {"blocks_16x16x16", "blocks_32x32x32", "xla_dot"}
    ref = outs["xla_dot"]
    for name, out in outs.items():
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("backend,interpret", [("cpu", False), ("tpu", True)])
def test_pallas_mode_mismatch_raises(monkeypatch, backend, interpret):
    """Pallas runs natively on a TPU and interpreted elsewhere; a site
    asked for the other mode would time the wrong program."""
    from repro.autotune import variants

    monkeypatch.setattr(variants.jax, "default_backend", lambda: backend)
    assert variants.pallas_interpret() is (backend != "tpu")
    with pytest.raises(ValueError, match="Pallas"):
        matmul_blocks_site(m=32, k=32, n=32, blocks=[(32, 32, 32)],
                           interpret=interpret)


# -------------------------------------------------------------- attention ---

def test_attention_site_flops_match_roofline_pair():
    b, s, h, kv, d = 1, 32, 2, 1, 16
    site = attention_site(b=b, s=s, h=h, kv=kv, d=d)
    # the shared math is the scores GEMM + the output GEMM with batch*heads
    # folded into rows — the decomposition the census family publishes
    want = (KernelSpec("gemm", (b * h * s, d, s)).flops
            + KernelSpec("gemm", (b * h * s, s, d)).flops)
    assert want == 2.0 * b * h * s * s * d * 2
    for name, f in site.flops_table().items():
        assert f == pytest.approx(want), name


def test_attention_variants_equivalent():
    site = attention_site(b=1, s=32, h=2, kv=1, d=16)
    outs = _outputs(site)
    assert set(outs) == {"reference_grouped", "reference_broadcast",
                         "chunked_flash"}
    ref = outs["reference_grouped"]
    for name, out in outs.items():
        np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3,
                                   err_msg=name)


# ------------------------------------------------- attention layer site ---

# Trinity-Mini at s=8192: executed FLOPs over the 4 s^2 h d rectangle
TRINITY_SHARES = {
    "sliding": {"flash_128x512": 280 / 1024, "flash_256x512": 140 / 512,
                "flash_512x1024": 42 / 128, "local_chunked": 2304 / 8192, "chunked": 1.0},
    "full": {"flash_128x512": 544 / 1024, "flash_256x512": 272 / 512,
             "flash_512x1024": 72 / 128, "chunked": 1.0},
}


def _layer(config, layer, size):
    from repro.core.family import InstanceSpec, get_family

    params = {"site": "attention", "config": config, "layer": layer, "size": size, "seed": 0}
    fam = get_family("kernel_variants")
    flops, meta, _ = fam.entry(InstanceSpec(0, "x", "kernel_variants", params))
    return flops, meta, fam.decompose(params)


@pytest.mark.parametrize("layer", ["sliding", "full"])
def test_attention_layer_flops_match_decomposition_at_trinity_widths(layer):
    """Each variant's executed FLOPs, as the site counts them, are the sum
    of the explainer's GEMM decomposition, and at Trinity-Mini's widths
    they are the shares of the rectangle the kernel's predicate gives."""
    from repro.autotune.variants import attention_flops

    s, h, d = 8192, 32, 128
    flops, meta, decomp = _layer("trinity-mini", layer, s)
    assert meta["dims"] == {"b": 1, "s": s, "heads": h, "kv_heads": 4, "head_dim": d,
                            "window": 2048 if layer == "sliding" else None}
    assert set(flops) == set(decomp) == set(TRINITY_SHARES[layer])
    for name, f in flops.items():
        assert f == sum(k.flops for k in decomp[name]) == attention_flops(
            name, b=1, s=s, h=h, d=d, window=meta["dims"]["window"]), name
        assert f == 4.0 * s * s * h * d * TRINITY_SHARES[layer][name], name


@pytest.mark.parametrize("layer", ["sliding", "full"])
def test_attention_layer_site_flops_match_family_table(tiny_attention_model, layer):
    from repro.autotune.variants import attention_layer_site

    flops, meta, decomp = _layer("tiny-attention", layer, 512)
    site = attention_layer_site(s=512, h=8, kv=1, d=128, window=meta["dims"]["window"])
    assert site.flops_table() == flops
    for name, ks in decomp.items():
        assert sum(k.flops for k in ks) == flops[name]
    # the score buffer of 8 heads at s=512 fits: the reference pair runs
    assert {"reference_grouped", "reference_broadcast"} <= set(flops)


def test_attention_layer_site_stores_bf16_and_matches_the_oracle(tiny_attention_model):
    """Every variant of a sliding layer agrees with the kernel package's
    pure-jnp oracle (GQA repeated, f32 softmax) to bf16 output rounding."""
    from repro.autotune.variants import attention_layer_site
    from repro.kernels.flash_attention.ops import flash_attention

    site = attention_layer_site(s=512, h=8, kv=1, d=128, window=128)
    q, k, v = site.make_inputs(3)
    assert {x.dtype for x in (q, k, v)} == {jnp.dtype(jnp.bfloat16)}
    ref = np.asarray(flash_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                     window=128, use_kernel=False))
    for name, out in _outputs(site, seed=3).items():
        out = np.asarray(out, np.float32)
        err = np.linalg.norm(out - ref) / np.linalg.norm(ref)
        assert err < 3e-3, (name, err)


def test_attention_layer_rejects_widths_its_config_does_not_have():
    with pytest.raises(ValueError, match="disagree"):
        from repro.core.family import _attention_layer_config

        _attention_layer_config({"site": "attention", "config": "trinity-mini",
                                 "layer": "sliding", "size": 8192, "heads": 16})
    with pytest.raises(ValueError, match="no 'sliding'"):
        _layer("granite-8b", "sliding", 8192)


# -------------------------------------------------------------------- ssd ---

def test_ssd_site_flops_match_family_decomposition():
    b, s, h, p, n = 1, 32, 2, 8, 8
    site = ssd_chunk_site(b=b, s=s, h=h, p=p, n=n, chunks=[8, 16, 32])
    table = site.flops_table()
    for q in (8, 16, 32):
        # the site's per-chunk analytic count...
        want = b * s * h * (2.0 * q * n + 2.0 * q * p + 4.0 * p * n)
        assert table[f"chunk_{q}"] == pytest.approx(want)
    # ...and the census family's shared-math decomposition reproduces the
    # reference chunk's count exactly, as a sum of roofline gemms
    q0 = 8
    kernels = [
        KernelSpec("gemm", (b * h * s, n, q0)),
        KernelSpec("gemm", (b * h * s, q0, p)),
        KernelSpec("gemm", (b * h * s, n, p)),
        KernelSpec("gemm", (b * h * s, p, n)),
    ]
    assert sum(k.flops for k in kernels) == pytest.approx(table["chunk_8"])


def test_ssd_variants_equivalent():
    site = ssd_chunk_site(b=1, s=32, h=2, p=8, n=8, chunks=[8, 16, 32])
    outs = _outputs(site)
    assert set(outs) == {"chunk_8", "chunk_16", "chunk_32"}
    ref = outs["chunk_32"]
    for name, out in outs.items():
        np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3,
                                   err_msg=name)


# ------------------------------------------- the family's workload bridge ---

def test_family_workloads_are_site_workloads():
    """The kernel_variants family's build_workloads must produce exactly
    the site's variant names (warmed, blocking thunks the WallClockTimer
    accepts)."""
    from repro.core.family import InstanceSpec
    from repro.core.sweep import instance_entry

    inst = InstanceSpec(
        index=0, uid="kernel_variants-matmul-n32-s000",
        family="kernel_variants",
        params={"site": "matmul", "size": 32, "seed": 0},
    )
    flops, _, build = instance_entry(inst)
    wl = build()
    assert set(wl) == set(flops)
    for fn in wl.values():
        fn()  # already warmed; must run
