"""Plain float32 ``jax.numpy`` references, independent of the program.

The kernel and the decision function are copied from the repository's
chip smoke test (``ref_rbf``, ``mv``, ``ref_decision``); the training
reference re-derives Algorithm 1 from the paper and the documented
sampling chain of ``fit`` (per fit: ``key, sub = split(key)`` per epoch;
per epoch: ``split(sub, N // n_grad)`` step keys; per step: ``split``
into the I and J keys, each drawing ``randint(0, N)``; alpha_J -= lr * g
with lr = lr0 / epoch).  Nothing here imports ``repro``.

``precision`` is the matmul precision: ``"highest"`` is the reference;
``"high"`` is the control, the nearest precision below the
float32-at-highest that the configurations state: three bf16 passes
(hi*hi + hi*lo + lo*hi of each operand split into two bf16 halves).  It is
spelled out here rather than left to ``jax.default_matmul_precision``, so
the control computes the same numbers on the TPU and on the CPU, whose
backend ignores that setting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SV_BLOCK = 8192


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def dot(a, b, precision="highest"):
    """``a @ b`` in float32 at ``highest``, or in three bf16 passes."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hp)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.matmul(ah, bh, precision=hp) + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


def ref_rbf(xq, xs, gamma, precision="highest"):
    sq = (jnp.sum(xq * xq, 1)[:, None] + jnp.sum(xs * xs, 1)[None, :]
          - 2.0 * dot(xq, xs.T, precision))
    return jnp.exp(-gamma * jnp.maximum(sq, 0.0))


def mv(k, a, precision="highest"):
    return dot(k, a, precision), dot(jnp.abs(k), jnp.abs(a))


@functools.partial(jax.jit, static_argnames=("gamma", "precision", "block"))
def ref_decision(xq, xs, a, *, gamma, precision="highest", block=SV_BLOCK):
    """(K(xq, xs) @ a, |K| @ |a|) with ``a`` of shape (N,) or (N, m), over
    ``block`` support rows at a time.  The last block is a clamped window
    whose rows already counted are masked out, so ``xs`` is never copied
    or padded (it may be most of the chip's memory)."""
    n = xs.shape[0]
    blk = min(block, n)
    steps = -(-n // blk)
    squeeze = a.ndim == 1
    a2 = a[:, None] if squeeze else a

    def body(acc, i):
        start = jnp.minimum(i * blk, n - blk)
        xb = jax.lax.dynamic_slice_in_dim(xs, start, blk, 0)
        ab = jax.lax.dynamic_slice_in_dim(a2, start, blk, 0)
        fresh = (start + jnp.arange(blk)) >= i * blk
        ab = jnp.where(fresh[:, None], ab, 0.0)
        f, fa = mv(ref_rbf(xq, xb, gamma, precision), ab, precision)
        return (acc[0] + f, acc[1] + fa), None

    zero = jnp.zeros((xq.shape[0], a2.shape[1]), jnp.float32)
    f, fa = jax.lax.scan(body, (zero, zero), jnp.arange(steps))[0]
    return (f[:, 0], fa[:, 0]) if squeeze else (f, fa)


def hinge(f, y):
    return jnp.maximum(0.0, 1.0 - y * f)


@functools.partial(jax.jit, static_argnames=(
    "n_grad", "n_expand", "gamma", "lam", "lr0", "precision", "grad_rows"))
def _ref_epoch(x, y, alpha, epoch, key, *, n_grad, n_expand, gamma, lam,
               lr0, precision, grad_rows):
    n = x.shape[0]
    keys = jax.random.split(key, max(n // n_grad, 1))
    lr = lr0 / jnp.maximum(epoch.astype(jnp.float32), 1.0)

    def body(a, k):
        ki, kj = jax.random.split(k)
        ii = jax.random.randint(ki, (n_grad,), 0, n)[:grad_rows]
        jj = jax.random.randint(kj, (n_expand,), 0, n)
        xi, yi, xj, aj = x[ii], y[ii], x[jj], a[jj]
        kb = ref_rbf(xi, xj, gamma, precision)
        f = dot(kb, aj, precision)
        v = jnp.where(yi * f < 1.0, -yi, 0.0)              # hinge'(f, y)
        g = (n_grad / grad_rows) * dot(kb.T, v, precision) + lam * aj
        return a.at[jj].add(-lr * g), None

    return jax.lax.scan(body, alpha, keys)[0]


def ref_fit_epochs(x, y, key, n_epochs, *, n_grad, n_expand, gamma, lam,
                   lr0, schedule, loss, kernel, precision="highest",
                   grad_rows=None):
    """Alpha after each of the first ``n_epochs`` epochs of a fit from
    alpha = 0 driven by ``key`` (rbf kernel, hinge loss, 1/epoch rate).
    ``grad_rows`` < ``n_grad`` is a planted fault for calibration: only
    that many of each step's gradient rows count, the sum scaled up."""
    if (kernel, loss, schedule) != ("rbf", "hinge", "inv_epoch"):
        raise NotImplementedError(
            f"reference covers rbf/hinge/inv_epoch, not "
            f"{kernel}/{loss}/{schedule}")
    alpha = jnp.zeros((x.shape[0],), jnp.float32)
    key, sub = jax.random.split(key)
    out = []
    for e in range(n_epochs):
        key, nxt = jax.random.split(key)
        alpha = _ref_epoch(x, y, alpha, jnp.asarray(e + 1, jnp.int32), sub,
                           n_grad=n_grad, n_expand=n_expand, gamma=gamma,
                           lam=lam, lr0=lr0, precision=precision,
                           grad_rows=grad_rows or n_grad)
        out.append(alpha)
        sub = nxt
    return out
