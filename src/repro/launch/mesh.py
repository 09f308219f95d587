"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod; (2, 16, 16) pod x data x model for
    the 2-pod = 512-chip deployment."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly forced-host) devices exist;
    used by tests and CPU examples."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
