"""GPipe pipeline executor vs sequential reference."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.distributed.pipeline import bubble_fraction, pipeline_apply  # noqa: E402


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def test_pipeline_matches_sequential():
    n_stages, m, mb, d = 4, 6, 2, 16
    mesh = jax.make_mesh(
        (n_stages,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "w": jax.random.normal(ks[0], (n_stages, d, d)) / np.sqrt(d),
        "b": jax.random.normal(ks[1], (n_stages, d)) * 0.1,
    }
    micro = jax.random.normal(ks[2], (m, mb, d))

    out = pipeline_apply(_stage_fn, params, micro, mesh)

    # sequential reference
    ref = micro
    for s in range(n_stages):
        ref = _stage_fn({"w": params["w"][s], "b": params["b"][s]}, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_bubble_fraction():
    assert bubble_fraction(4, 6) == 3 / 9
    assert bubble_fraction(1, 8) == 0.0
