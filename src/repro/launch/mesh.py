"""Production mesh builders.

A function, not a module constant: importing this module must never touch
jax device state (the dry-run needs to set XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips).

    Axes: 'pod' = inter-pod DCN (the paper's inter-datacenter boundary),
    'data' = in-pod data parallelism, 'model' = tensor/expert parallelism.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this process has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
