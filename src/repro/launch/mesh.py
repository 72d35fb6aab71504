"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run must set
XLA_FLAGS before *any* jax initialization.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (sharded by propagation)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / CPU)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return make_mesh((n // mp, mp), ("data", "model"))
