"""Seeded stand-in data, made on the device in one jitted call each.

Every size is fixed by the configuration, so every seed gives the same
shapes and the same amount of work; only the values move.

* ``covertype_like``: copied from the repository's covertype stand-in
  (D = 54: 10 continuous features and 44 sparse binary ones, a nonlinear
  boundary, classes about 57/43).
* ``sparse_alpha``: a dual vector with exactly ``nnz`` nonzero entries at
  seeded positions, magnitudes bounded away from zero so none is below the
  engine's truncation threshold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "d", "stream"))
def covertype_like(key, n: int, d: int = 54, stream: int = 0):
    """``stream`` > 0 draws further rows of the same distribution (the
    same boundary w1) from an independent row stream."""
    k1, k2, k3 = jax.random.split(key, 3)
    if stream:
        k1, k2 = jax.random.fold_in(k1, stream), jax.random.fold_in(k2, stream)
    x_cont = jax.random.normal(k1, (n, 10))
    x_bin = (jax.random.uniform(k2, (n, d - 10)) < 0.15).astype(jnp.float32)
    x = jnp.concatenate([x_cont, x_bin], axis=1)
    w1 = jax.random.normal(k3, (d,))
    score = (jnp.tanh(x @ w1 / jnp.sqrt(d)) + 0.5 * jnp.sin(2.0 * x[:, 0])
             + 0.25 * x[:, 1] * x[:, 2] + 0.18)
    return x, jnp.sign(score)


@functools.partial(jax.jit, static_argnames=("n", "nnz", "scale"))
def sparse_alpha(key, n: int, nnz: int, scale: float = 0.5):
    kp, ks, km = jax.random.split(key, 3)
    pos = jax.random.permutation(kp, n)[:nnz]
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (nnz,)), 1.0, -1.0)
    mag = scale * (0.05 + jnp.abs(jax.random.normal(km, (nnz,))))
    return jnp.zeros((n,), jnp.float32).at[pos].set(sign * mag)


GENERATORS = {"covertype_like": covertype_like}
