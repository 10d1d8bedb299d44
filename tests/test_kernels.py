"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.expressions import generate_chain_algorithms, make_chain_inputs, reference_product
from repro.kernels import chain_matmul, flash_attention, matmul, ssd_mix
from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.matmul.ref import matmul_ref


# --------------------------------------------------------- flash attention -

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize(
    "bh,sq,skv,d,causal,win,cap,bq,bk",
    [
        (2, 256, 256, 64, True, None, None, 128, 128),
        (1, 128, 128, 128, False, None, None, 64, 128),
        (2, 128, 512, 64, True, None, None, 64, 128),    # decode-ish sq<skv
        (1, 256, 256, 64, True, 64, None, 64, 64),       # sliding window
        (1, 256, 256, 64, True, None, 50.0, 128, 64),    # gemma softcap
    ],
)
def test_flash_kernel_sweep(bh, sq, skv, d, causal, win, cap, bq, bk, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (bh, sq, d), dtype)
    k = jax.random.normal(ks[1], (bh, skv, d), dtype)
    v = jax.random.normal(ks[2], (bh, skv, d), dtype)
    out = flash_attention_kernel(
        q, k, v, causal=causal, window=win, logit_cap=cap,
        block_q=bq, block_k=bk, interpret=True,
    )
    ref = flash_attention_ref(q, k, v, causal=causal, window=win, logit_cap=cap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_flash_ops_gqa_broadcast():
    """ops wrapper: [b,s,h,d] layout + kv-head broadcast == model reference."""
    from repro.models.attention import attention_reference

    b, s, h, kv, d = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def _brute_force_steps(sq, skv, bq, bk, causal, window):
    """(live, total) blocks of a [bq, bk] tiling holding a query, key pair
    the mask lets through, counted pair by pair."""
    i = np.arange(sq)[:, None] + (skv - sq if causal else 0)
    j = np.arange(skv)[None, :]
    seen = np.ones((sq, skv), bool)
    if causal:
        seen &= j <= i
    if window is not None:
        seen &= j > i - window
    blocks = seen.reshape(sq // bq, bq, skv // bk, bk).any(axis=(1, 3))
    return int(blocks.sum()), blocks.size


@pytest.mark.parametrize(
    "sq,skv,bq,bk,causal,window",
    [
        (8192, 8192, 128, 512, True, 2048),
        (8192, 8192, 256, 512, True, 2048),
        (8192, 8192, 512, 1024, True, 2048),
        (8192, 8192, 128, 512, True, None),
        (512, 512, 128, 512, True, 128),
        (256, 256, 64, 64, True, 64),
        (128, 512, 64, 128, True, None),
        (256, 256, 64, 128, False, None),
    ],
)
def test_grid_steps_match_a_brute_force_count(sq, skv, bq, bk, causal, window):
    from repro.kernels.flash_attention.flash_attention import grid_steps

    got = grid_steps(sq, skv, block_q=bq, block_k=bk, causal=causal, window=window)
    assert got == _brute_force_steps(sq, skv, bq, bk, causal, window)


def test_grid_steps_at_trinity_widths():
    """The live share of each tiling at s=8192: 27.3% sliding (window
    2048), 53.1-56.3% full."""
    from repro.kernels.flash_attention.flash_attention import grid_steps

    assert grid_steps(8192, 8192, block_q=128, block_k=512, window=2048) == (280, 1024)
    assert grid_steps(8192, 8192, block_q=512, block_k=1024, window=2048) == (42, 128)
    assert grid_steps(8192, 8192, block_q=512, block_k=1024) == (72, 128)


def test_flash_pallas_call_names_its_mask_and_blocks():
    from repro.kernels.flash_attention.flash_attention import kernel_name

    assert kernel_name(True, 2048, 128, 512) == "flash_swa2048_128x512"
    assert kernel_name(True, None, 512, 1024) == "flash_causal_512x1024"
    q = jnp.zeros((2, 256, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention_kernel(
        q, k, v, window=64, block_q=64, block_k=128, interpret=True))(q, kv, kv)
    assert "flash_swa64_64x128" in str(jaxpr)


def test_flash_kernel_reads_kv_heads_by_index():
    """q rows i read k/v row i // group: the same as repeating the kv heads."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 64), jnp.float32)
    out = flash_attention_kernel(q, k, v, window=96, block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, jnp.repeat(k, 2, axis=0), jnp.repeat(v, 2, axis=0), window=96)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


# ----------------------------------------------------------------- matmul --

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (256, 256, 256, 128, 128, 128),
        (300, 200, 450, 128, 128, 128),     # non-multiples (padding path)
        (64, 512, 128, 256, 256, 512),      # block > dim (clamping path)
        (128, 128, 1024, 128, 256, 128),
    ],
)
def test_matmul_kernel_sweep(m, k, n, bm, bn, bk, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    a = (jax.random.normal(ks[0], (m, k)) / np.sqrt(k)).astype(dtype)
    b = (jax.random.normal(ks[1], (k, n)) / np.sqrt(k)).astype(dtype)
    out = matmul(a, b, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@given(st.integers(1, 3), st.integers(1, 3), st.integers(1, 3))
@settings(max_examples=10, deadline=None)
def test_matmul_property_random_shapes(i, j, k_):
    """Property: kernel == oracle for irregular (non-aligned) shapes."""
    m, k, n = 17 * i, 23 * j, 13 * k_
    ks = jax.random.split(jax.random.PRNGKey(i * 100 + j * 10 + k_), 2)
    a = jax.random.normal(ks[0], (m, k), jnp.float32)
    b = jax.random.normal(ks[1], (k, n), jnp.float32)
    out = matmul(a, b, block_m=16, block_n=16, block_k=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(matmul_ref(a, b)), rtol=2e-4, atol=2e-4
    )


def test_chain_matmul_all_algorithms():
    """The paper's six algorithms, executed on the Pallas GEMM."""
    dims = (24, 16, 4, 20, 12)
    mats = make_chain_inputs(dims, seed=2)
    ref = np.asarray(reference_product(mats))
    for alg in generate_chain_algorithms(dims):
        out = chain_matmul(alg, mats, interpret=True, block_m=16, block_n=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=5e-4, atol=5e-4, err_msg=alg.name)


# -------------------------------------------------------------------- SSD --

@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4)])
def test_ssd_kernel_sweep(chunk, dtype, tol):
    b, s, h, p, n = 2, 128, 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bm = jax.random.normal(ks[3], (b, s, 1, n))
    cm = jax.random.normal(ks[4], (b, s, 1, n))
    out = ssd_mix(x, dt, a_log, bm, cm, chunk=chunk, use_kernel=True, interpret=True)
    ref = ssd_mix(x, dt, a_log, bm, cm, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


def test_ssd_kernel_groups():
    """g > 1 (grouped B/C) broadcast path."""
    b, s, h, p, n, g = 1, 64, 4, 16, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    out = ssd_mix(x, dt, a_log, bm, cm, chunk=32, use_kernel=True, interpret=True)
    ref = ssd_mix(x, dt, a_log, bm, cm, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)
