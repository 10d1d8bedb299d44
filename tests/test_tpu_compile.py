"""The census path's kernels compile for a TPU v5e, without the chip.

The TPU compiler ships with jaxlib and compiles for a described topology
that is not attached, so these tests catch what interpret mode cannot — a
block shape off the (8, 128) tiling, an op the kernel compiler lacks, a
kernel that overflows fast memory — at real widths and at no chip time.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # the library, or the topology, is unavailable
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip_shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_matmul_site_tile_compiles(chip_shape, tile):
    """Each tile the kernel_variants matmul site censuses, at n=4096 f32."""
    from repro.core.family import _kernel_site_config
    from repro.kernels.matmul.ops import matmul

    n = 4096
    assert (tile,) * 3 in _kernel_site_config("matmul", n)["site_kwargs"]["blocks"]
    a = chip_shape((n, n), jnp.float32)
    text = _compiled_text(
        lambda x, y: matmul(x, y, block_m=tile, block_n=tile, block_k=tile), a, a
    )
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_granite_8b_widths(chip_shape):
    """32 query heads over 8 KV heads of 128, 2048 tokens, bf16."""
    from repro.kernels.flash_attention.ops import flash_attention

    q = chip_shape((1, 2048, 32, 128), jnp.bfloat16)
    kv = chip_shape((1, 2048, 8, 128), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(flash_attention, q, kv, kv)


@pytest.mark.parametrize("window", [2048, None])
@pytest.mark.parametrize("blocks", [(128, 512), (512, 1024)])
def test_flash_attention_compiles_at_trinity_mini_widths(chip_shape, blocks, window):
    """The attention layer site's flash tilings at Trinity-Mini's widths:
    32 query heads over 4 KV heads of 128 read by index, 8192 tokens,
    sliding (2048) and full, bf16; the Pallas call carries its name."""
    import functools

    from repro.kernels.flash_attention.flash_attention import kernel_name
    from repro.kernels.flash_attention.ops import flash_attention

    bq, bk = blocks
    q = chip_shape((1, 8192, 32, 128), jnp.bfloat16)
    kv = chip_shape((1, 8192, 4, 128), jnp.bfloat16)
    fn = functools.partial(flash_attention, window=window, block_q=bq, block_k=bk)
    text = _compiled_text(fn, q, kv, kv)
    assert "tpu_custom_call" in text and kernel_name(True, window, bq, bk) in text


def test_ssd_kernel_compiles_at_mamba2_1p3b_widths(chip_shape):
    """64 heads of p=64 with state n=128, chunk 256, 2048 tokens."""
    from repro.kernels.ssd.ssd import ssd_scan_kernel

    bh, s, p, n = 64, 2048, 64, 128
    text = _compiled_text(
        lambda x, l, b, c: ssd_scan_kernel(x, l, b, c, chunk=256),
        chip_shape((bh, s, p), jnp.float32),
        chip_shape((bh, s), jnp.float32),
        chip_shape((bh, s, n), jnp.float32),
        chip_shape((bh, s, n), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_chain_workload_compiles_at_4096(chip_shape):
    """One jitted chain algorithm — the program a chain workload times —
    over four 4096x4096 matrices."""
    from repro.expressions.algorithms import algorithm_fn
    from repro.expressions.chain import generate_chain_algorithms

    dims = [4096] * 5
    alg = generate_chain_algorithms(dims)[0]
    mats = [chip_shape((dims[i], dims[i + 1]), jnp.float32) for i in range(4)]
    assert "dot" in _compiled_text(algorithm_fn(alg), *mats)


@pytest.mark.parametrize("tm", [128, 256, 512])
def test_grouped_matmul_compiles_at_trinity_mini_widths(chip_shape, tm):
    """The moe site's grouped matmul at each m tile over Trinity-Mini's
    assignments: 8192 tokens x top-8 rows of 2048 against 128 experts'
    [2048, 1024] weights, bf16; the Pallas call carries its name."""
    import functools

    from repro.kernels.gmm.gmm import gmm, kernel_name

    lhs = chip_shape((8192 * 8, 2048), jnp.bfloat16)
    rhs = chip_shape((128, 2048, 1024), jnp.bfloat16)
    sizes = chip_shape((128,), jnp.int32)
    text = _compiled_text(functools.partial(gmm, tiling=(tm, 512, 512)), lhs, rhs, sizes)
    assert "tpu_custom_call" in text and kernel_name(tm, 512, 512) in text
