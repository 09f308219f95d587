"""Plain float32 ``jax.numpy`` reference of a data-parallel mesh fit,
independent of the program (nothing here imports ``repro``).

It re-derives the mesh step from DESIGN.md section 2 and the module
docstring of ``core/distributed.py``, on a mesh of ``n_data`` data shards
and one model shard, over N rows whose shard d owns rows
[d N / n_data, (d + 1) N / n_data):

* the fit's key chain: ``key, sub = split(key)`` before the first epoch,
  then per epoch ``key, next = split(key)``; an epoch of
  N // (n_grad n_data) steps draws its step keys as ``split(sub, steps)``;
* step key k: shard d's gradient rows I_d are ``randint`` over its N /
  n_data local rows with key ``fold_in(fold_in(k, 0), d)``, offset by the
  shard's first row; the expansion rows J are ``randint`` over all N rows
  with ``fold_in(fold_in(k, 1), 0)``;
* f_d = K(X[I_d], X[J]) alpha_J, v_d = hinge'(f_d, y_{I_d}),
  g = sum_d K(X[I_d], X[J])^T v_d + lam alpha_J (the regulariser once),
  alpha_J -= lr0 / epoch * g, duplicates in J adding up.

Departures from the paper's Algorithm 2, followed here because the
program makes them: the shards' gradients are summed, not averaged; no
AdaGrad dampening under ``inv_epoch``; I and J are drawn with replacement
per step, not as per-epoch partitions.

The shards' products are computed as one: stacking the shards' rows,
K(X[I], X[J]) alpha_J gives every f_d, and K(X[I], X[J])^T v is the sum
over d.  ``precision`` is ``chipbench.refs``'s: ``"highest"`` is the
reference, ``"high"`` (three bf16 passes) the control.  Faults for the
limits' calibration: ``rate="per_step"`` steps at lr0 / t (t counted over
the whole fit); ``exchange=False`` applies shard 0's gradient alone, the
state a replica keeps when the shards never sum their gradients;
``grad_rows`` < n_grad keeps that many of each shard's rows, the sum
scaled up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refs import dot, ref_rbf


@functools.partial(jax.jit, static_argnames=(
    "n_data", "n_grad", "n_expand", "gamma", "lam", "lr0", "precision",
    "grad_rows", "per_step", "exchange"))
def _ref_mesh_epoch(x, y, alpha, epoch, t0, key, *, n_data, n_grad,
                    n_expand, gamma, lam, lr0, precision, grad_rows,
                    per_step, exchange):
    n = x.shape[0]
    rows = n // n_data
    steps = max(n // (n_grad * n_data), 1)
    keys = jax.random.split(key, steps)
    shards = n_data if exchange else 1

    def body(carry, k):
        a, t = carry
        ii = jnp.concatenate([
            jax.random.randint(jax.random.fold_in(jax.random.fold_in(k, 0), d),
                               (n_grad,), 0, rows)[:grad_rows] + d * rows
            for d in range(shards)])
        jj = jax.random.randint(jax.random.fold_in(jax.random.fold_in(k, 1), 0),
                                (n_expand,), 0, n)
        xi, yi, xj, aj = x[ii], y[ii], x[jj], a[jj]
        kb = ref_rbf(xi, xj, gamma, precision)
        f = dot(kb, aj, precision)
        v = jnp.where(yi * f < 1.0, -yi, 0.0)                # hinge'(f, y)
        g = (n_grad / grad_rows) * dot(kb.T, v, precision) + lam * aj
        t = t + 1
        rate = t if per_step else epoch
        lr = lr0 / jnp.maximum(rate.astype(jnp.float32), 1.0)
        return (a.at[jj].add(-lr * g), t), None

    return jax.lax.scan(body, (alpha, t0), keys)[0]


def ref_mesh_fit_epochs(x, y, key, n_epochs, *, n_data, n_grad, n_expand,
                        gamma, lam, lr0, schedule, loss, kernel,
                        precision="highest", rate="epoch", exchange=True,
                        grad_rows=None):
    """Alpha after each of the first ``n_epochs`` epochs of a mesh fit from
    alpha = 0 driven by ``key`` (rbf kernel, hinge loss, ``inv_epoch``).
    ``rate``, ``exchange`` and ``grad_rows`` plant the faults (module
    docstring)."""
    if (kernel, loss, schedule) != ("rbf", "hinge", "inv_epoch"):
        raise NotImplementedError(
            f"reference covers rbf/hinge/inv_epoch, not "
            f"{kernel}/{loss}/{schedule}")
    if rate not in ("epoch", "per_step"):
        raise ValueError(f"unknown rate {rate!r}")
    alpha = jnp.zeros((x.shape[0],), jnp.float32)
    t = jnp.zeros((), jnp.int32)
    key, sub = jax.random.split(key)
    out = []
    for e in range(n_epochs):
        key, nxt = jax.random.split(key)
        alpha, t = _ref_mesh_epoch(
            x, y, alpha, jnp.asarray(e + 1, jnp.int32), t, sub,
            n_data=n_data, n_grad=n_grad, n_expand=n_expand, gamma=gamma,
            lam=lam, lr0=lr0, precision=precision,
            grad_rows=grad_rows or n_grad, per_step=rate == "per_step",
            exchange=exchange)
        out.append(alpha)
        sub = nxt
    return out
