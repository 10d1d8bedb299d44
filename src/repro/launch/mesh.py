"""Mesh construction for single-pod and multi-pod deployments.

``make_production_mesh`` builds the 16x16 (256-chip pod, axes data x model)
or 2x16x16 (two pods, axes pod x data x model) target mesh. Functions only —
importing this module never touches jax device state.

The builder generalises: ``make_mesh_shape(n_pods, dp, tp)`` supports
arbitrary pod counts for 1000+-node deployments (the 'pod' axis carries pure
data parallelism, so scaling pods never changes per-pod sharding — see
DESIGN.md §5).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    return make_mesh(n_pods=2 if multi_pod else 1)


def make_mesh(n_pods: int = 1, dp: int = 16, tp: int = 16):
    """General mesh: (pod, data, model) or (data, model) when n_pods == 1,
    every axis sharded automatically."""
    shape, axes = (dp, tp), ("data", "model")
    if n_pods > 1:
        shape, axes = (n_pods,) + shape, ("pod",) + axes
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(tp: Optional[int] = None):
    """Mesh over whatever devices exist (CPU smoke / tests).

    Picks (dp, tp) = (n // tp, tp) with tp the largest power of two <= n
    unless given. Falls back to (1, 1) on a single device.
    """
    n = len(jax.devices())
    if tp is None:
        tp = 1
        while tp * 2 <= n and tp * 2 <= 8:
            tp *= 2
    dp = max(n // tp, 1)
    return make_mesh(1, dp, tp)


def describe(mesh) -> str:
    return (
        f"mesh axes={mesh.axis_names} shape={tuple(mesh.shape[a] for a in mesh.axis_names)} "
        f"devices={mesh.devices.size}"
    )
