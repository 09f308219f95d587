"""Jit'd public wrappers around the fused DSEKL kernel ops.

``impl`` selects the backend:
  * ``"ref"``               — pure-jnp oracle (XLA).  Default on CPU; this is
                              also the path the dry-run compiles.
  * ``"pallas"``            — the TPU Pallas kernel (target hardware).
  * ``"pallas_interpret"``  — Pallas kernel body interpreted on CPU (tests).
  * ``"auto"``              — pallas on TPU, ref elsewhere.

Every kernel in the ``core/kernels_fn`` registry (rbf, laplacian, linear,
polynomial, sigmoid, matern32, matern52) has a fused Pallas tile
(``block.TILE_FNS``); an unregistered kernel name raises from the registry
lookup on the ref path and has no pallas path.

Ops:
  * ``kernel_matvec``    — f = K @ a
  * ``kernel_vecmat``    — g = K^T @ v
  * ``kernel_dual_pass`` — both products from ONE evaluation of K per tile;
    with ``loss=...`` the loss gradient v = dloss/df(f, y) is fused between
    the two products (the doubly stochastic training step in one op).
  * ``kernel_block``     — K materialized (ref only).  For deferred-reduction
    callers (the mesh step must psum f across devices before v exists, so
    the closed-form dual pass cannot apply; evaluating the block once and
    holding it is the fused form there).
  * ``kernel_matvec_tiled`` — f = K @ a consuming z in fixed row tiles under
    one ``lax.scan``: peak intermediate O(|x| * z_block) instead of the ref
    matvec's O(|x| * |z|).  The streaming primitive of the prediction engine
    (serving/dsekl_engine.py) and of core/dsekl.decision_function; pallas
    backends already tile internally and delegate to ``kernel_matvec``.

The row-tiling helpers (``pad_rows_to_block`` / ``tile_rows``) are shared by
the tiled matvec here, the streaming train pass in core/dsekl.py, and the
prediction engine — one padding convention everywhere (zero rows, which are
exact for every op because the padded a/v entries are zero).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import kernels_fn
from repro.core import losses as losses_lib
from repro.kernels.dsekl import block as _pk
from repro.kernels.dsekl import ref as _ref

Array = jax.Array


_IMPLS = ("auto", "ref", "pallas", "pallas_interpret")


def resolve_impl(impl: str, kernel_name: str) -> str:
    """Resolve an ``impl`` selector to the backend that will actually run.

    The public form of the backend resolver: callers that need to branch on
    the resolved backend (the streaming paths in ``core/dsekl.py`` and the
    mesh step in ``core/distributed.py``) use this instead of reaching into
    a private helper.  ``"auto"`` honours the ``REPRO_IMPL`` env override
    (the CI backend matrix — read at trace time, set it before the process
    compiles anything), then picks ``pallas`` on TPU for kernels with a
    fused tile and ``ref`` everywhere else.  An explicit Pallas backend
    for a kernel with no tile raises instead of running XLA in its place.
    """
    if impl == "auto":
        impl = os.environ.get("REPRO_IMPL", "auto") or "auto"
        if impl not in _IMPLS:
            raise ValueError(
                f"REPRO_IMPL={impl!r} is not one of {_IMPLS}")
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        impl = "pallas" if (on_tpu and kernel_name in _pk.TILE_FNS) else "ref"
    if impl in ("pallas", "pallas_interpret") and kernel_name not in _pk.TILE_FNS:
        raise ValueError(f"impl={impl!r}: no Pallas tile for kernel "
                         f"{kernel_name!r}; available: {sorted(_pk.TILE_FNS)}")
    return impl


# ---------------------------------------------------------------------------
# Row-tiling helpers (shared with the streaming train pass and the engine).
# ---------------------------------------------------------------------------

def pad_rows_to_block(x: Array, block: int) -> Array:
    """Zero-pad axis 0 up to the next multiple of ``block``."""
    return _pk._pad_rows(x, block)


def tile_rows(x: Array, block: int) -> Array:
    """(n, ...) -> (n_tiles, block, ...) with zero-padded tail rows."""
    xp = pad_rows_to_block(x, block)
    return xp.reshape((xp.shape[0] // block, block) + xp.shape[1:])


@functools.partial(jax.jit, static_argnames=("kernel_name", "kernel_params", "impl"))
def kernel_matvec(x: Array, z: Array, a: Array, *, kernel_name: str = "rbf",
                  kernel_params: tuple = (("gamma", 1.0),),
                  impl: str = "auto") -> Array:
    """f = K(x, z) @ a with K never materialized in HBM (pallas paths)."""
    params: Dict[str, Any] = dict(kernel_params)
    impl = resolve_impl(impl, kernel_name)
    if impl == "ref":
        k = kernels_fn.get_kernel(kernel_name, **params)
        return _ref.ref_kernel_matvec(k, x, z, a)
    # matvec keeps the x_I/output tile resident across the j sweep: give
    # the big block to I (see block.py's HBM-traffic model).
    bi, bj = _pk.choose_blocks(x.shape[0], z.shape[0], x.shape[1])
    return _pk.kernel_matvec_pallas(x, z, a, kernel_name=kernel_name,
                                    params=params, block_i=bi, block_j=bj,
                                    interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("kernel_name", "kernel_params", "impl"))
def kernel_vecmat(x: Array, z: Array, v: Array, *, kernel_name: str = "rbf",
                  kernel_params: tuple = (("gamma", 1.0),),
                  impl: str = "auto") -> Array:
    """g = K(x, z)^T @ v with K never materialized in HBM (pallas paths)."""
    params: Dict[str, Any] = dict(kernel_params)
    impl = resolve_impl(impl, kernel_name)
    if impl == "ref":
        k = kernels_fn.get_kernel(kernel_name, **params)
        return _ref.ref_kernel_vecmat(k, x, z, v)
    # vecmat keeps the g_J/output tile resident across the i sweep: the
    # big block goes to J (per-op orientation, §Perf iter 4).
    bj_big, bi_small = _pk.choose_blocks(z.shape[0], x.shape[0], x.shape[1])
    return _pk.kernel_vecmat_pallas(x, z, v, kernel_name=kernel_name,
                                    params=params, block_i=bi_small,
                                    block_j=bj_big,
                                    interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("kernel_name", "kernel_params",
                                             "loss", "f_scale", "impl"))
def kernel_dual_pass(x: Array, z: Array, a: Array, vy: Array, *,
                     kernel_name: str = "rbf",
                     kernel_params: tuple = (("gamma", 1.0),),
                     loss: Optional[str] = None, f_scale: float = 1.0,
                     impl: str = "auto"):
    """Both products of K(x, z) from ONE kernel-block evaluation.

    * ``loss=None``: ``vy`` is the dual-gradient vector v (i,).  Returns
      ``(f, g) = (f_scale * K @ a, K^T @ vy)``.
    * ``loss="hinge"`` (etc.): ``vy`` is the label vector y (i,).  Returns
      ``(f, g)`` with ``f = f_scale * K @ a`` and ``g = K^T @ v`` for
      ``v = loss.grad_f(f, y)`` — the entire doubly stochastic step body
      fused into one op (paper Alg. 1 lines 4-5 with K_{I,J} evaluated once
      instead of twice).

    ``f_scale`` implements the unbiased N/|J| empirical-map scaling *before*
    the loss gradient is taken.
    """
    params: Dict[str, Any] = dict(kernel_params)
    impl = resolve_impl(impl, kernel_name)
    loss_grad = losses_lib.get_loss(loss).grad_f if loss is not None else None

    if impl == "ref":
        k = kernels_fn.get_kernel(kernel_name, **params)
        if loss_grad is None:
            f, g = _ref.ref_kernel_dual_pass(k, x, z, a, vy)
            return f_scale * f, g
        return _ref.ref_kernel_train_pass(k, x, z, a, vy, loss_grad,
                                          f_scale=f_scale)

    interpret = impl == "pallas_interpret"
    if loss_grad is None:
        bi, bj = _pk.choose_blocks(x.shape[0], z.shape[0], x.shape[1])
        f, g = _pk.dual_pass_pallas(x, z, a, vy, kernel_name=kernel_name,
                                    params=params, block_i=bi, block_j=bj,
                                    interpret=interpret)
        return f_scale * f, g

    blocks = _pk.train_pass_blocks(x.shape[0], z.shape[0], x.shape[1])
    if blocks is None:
        # J too large for the K row-block scratch: fall back to two fused
        # single-product sweeps (still never materializes K in HBM; costs
        # one extra K evaluation, exactly the two-pass baseline).  Same
        # tuned per-op block orientations as kernel_matvec/kernel_vecmat.
        bi, bj = _pk.choose_blocks(x.shape[0], z.shape[0], x.shape[1])
        f = f_scale * _pk.kernel_matvec_pallas(
            x, z, a, kernel_name=kernel_name, params=params,
            block_i=bi, block_j=bj, interpret=interpret)
        v = loss_grad(f, vy)
        bj_big, bi_small = _pk.choose_blocks(z.shape[0], x.shape[0],
                                             x.shape[1])
        g = _pk.kernel_vecmat_pallas(
            x, z, v, kernel_name=kernel_name, params=params,
            block_i=bi_small, block_j=bj_big, interpret=interpret)
        return f, g
    bi, bj = blocks
    return _pk.train_pass_pallas(x, z, a, vy, loss_grad,
                                 kernel_name=kernel_name, params=params,
                                 f_scale=f_scale, block_i=bi, block_j=bj,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kernel_name", "kernel_params",
                                             "z_block", "impl"))
def kernel_matvec_tiled(x: Array, z: Array, a: Array, *,
                        kernel_name: str = "rbf",
                        kernel_params: tuple = (("gamma", 1.0),),
                        z_block: int = 4096, impl: str = "auto") -> Array:
    """f = K(x, z) @ a consuming z in ``z_block``-row tiles.

    One jitted ``lax.scan`` over the tiles: the compiled program's peak
    kernel-block intermediate is O(|x| * z_block) regardless of |z| (the
    full-block ref matvec materializes |x| * |z|).  Zero-padded tail rows
    carry zero ``a`` so they contribute exactly nothing.  This is the
    expansion-set streaming primitive: ``decision_function`` and the
    prediction engine run it over the (padded) support set, sharded callers
    run it per shard and psum.

    The pallas backends already stream K tile-by-tile inside the kernel, so
    they delegate to ``kernel_matvec`` with serving-oriented blocks.
    """
    params: Dict[str, Any] = dict(kernel_params)
    rimpl = resolve_impl(impl, kernel_name)
    if rimpl != "ref":
        bq, bs = _pk.choose_predict_blocks(x.shape[0], z.shape[0], x.shape[1])
        return _pk.kernel_matvec_pallas(x, z, a, kernel_name=kernel_name,
                                        params=params, block_i=bq, block_j=bs,
                                        interpret=(rimpl == "pallas_interpret"))
    k = kernels_fn.get_kernel(kernel_name, **params)
    z_tiles = tile_rows(z, z_block)
    a_tiles = tile_rows(a.astype(jnp.float32), z_block)

    def body(acc, tile):
        zt, at = tile
        return acc + _ref.ref_kernel_matvec(k, x, zt, at), ()

    f0 = jnp.zeros((x.shape[0],), jnp.float32)
    f, _ = jax.lax.scan(body, f0, (z_tiles, a_tiles))
    return f


@functools.partial(jax.jit, static_argnames=("kernel_name", "kernel_params"))
def kernel_block(x: Array, z: Array, *, kernel_name: str = "rbf",
                 kernel_params: tuple = (("gamma", 1.0),)) -> Array:
    """K(x, z) materialized — the one-evaluation form for callers that must
    interleave a cross-device reduction between the two products (see the
    mesh step in core/distributed.py).  Sized for sampled training blocks
    (|I| x |J|), not for full kernel matrices."""
    params: Dict[str, Any] = dict(kernel_params)
    return kernels_fn.get_kernel(kernel_name, **params)(x, z)
