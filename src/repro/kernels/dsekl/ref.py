"""Pure-jnp oracles for the fused DSEKL kernel ops.

These are the semantic definition of the two hot-spot ops; the Pallas
kernels in ``block.py`` must match them (tests sweep shapes/dtypes and
``assert_allclose`` against these).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


def ref_kernel_matvec(kernel: Callable[[Array, Array], Array],
                      x: Array, z: Array, a: Array) -> Array:
    """f = K(x, z) @ a   — x (i, d), z (j, d), a (j,) -> (i,)."""
    return kernel(x, z) @ a


def ref_kernel_vecmat(kernel: Callable[[Array, Array], Array],
                      x: Array, z: Array, v: Array) -> Array:
    """g = K(x, z)^T @ v — x (i, d), z (j, d), v (i,) -> (j,)."""
    return kernel(x, z).T @ v


def ref_kernel_dual_pass(kernel: Callable[[Array, Array], Array],
                         x: Array, z: Array, a: Array, v: Array):
    """(f, g) = (K @ a, K^T @ v) with K evaluated ONCE.

    Semantic oracle for the fused dual-pass Pallas kernel; also the ref
    backend of ``ops.kernel_dual_pass`` (the single shared K evaluation is
    the whole point — two separately jitted matvec/vecmat calls evaluate
    the O(i*j*d) kernel block twice)."""
    km = kernel(x, z)
    return km @ a, km.T @ v


def ref_kernel_train_pass(kernel: Callable[[Array, Array], Array],
                          x: Array, z: Array, a: Array, y: Array,
                          loss_grad: Callable[[Array, Array], Array],
                          f_scale: float = 1.0):
    """Fused training step math, K evaluated ONCE:

        f = f_scale * K @ a;  v = loss_grad(f, y);  g = K^T @ v.

    Oracle for ``block.train_pass_pallas``."""
    km = kernel(x, z)
    f = f_scale * (km @ a)
    return f, km.T @ loss_grad(f, y)
