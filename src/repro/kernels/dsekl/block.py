"""Fused empirical-kernel-map ops as Pallas TPU kernels — all kernels.

This generalizes ``rbf_block.py`` (the original RBF-only path) in two ways:

1. **Multi-kernel tiles.**  A static registry ``TILE_FNS`` maps every kernel
   in ``core/kernels_fn.KERNELS`` (rbf, laplacian, linear, polynomial,
   sigmoid, matern32, matern52) to a VMEM tile evaluator.  Dispatch happens
   at trace time (the kernel name is a static argument), so the Pallas body
   is specialized per kernel — no in-kernel branching.

2. **Dual-pass fusion.**  The DSEKL step needs both products of the sampled
   block K = K_{I,J}:

       f = K @ a        (decision values / empirical kernel map)
       g = K^T @ v      (dual gradient, v = dloss/df)

   The composed matvec+vecmat path evaluates every K tile twice — and the
   O(bi*bj*D) distance computation is the dominant cost.  The dual-pass
   kernels here evaluate each tile exactly ONCE and emit both reductions:

   * ``dual_pass_pallas``  — v given up front.  One (ni, nj) sweep; f is
     accumulated into a revisited output block over the inner j axis, and
     the per-i-block partial g rows land in an (ni, 1, J) output summed
     outside the kernel (each block written exactly once — no revisit
     hazards on the g output).
   * ``train_pass_pallas`` — v computed *inside* from the loss gradient
     (v depends elementwise on the completed f row-block, so a (ni, 2, nj)
     phase grid stashes the K row-block in VMEM scratch during the f sweep
     and replays it — never recomputing a tile — for the g sweep once
     v = dloss/df(f, y) is known).

   Tile-padding note: rows are zero-padded up to the block size.  Padded
   a/v entries are zero so they never contribute; for the train pass v is
   additionally masked by the true row count because it is derived in-kernel
   from garbage padded f rows.

Everything below keeps the TPU adaptations of the original RBF kernel:
128-aligned tiles for the MXU, f32 accumulation regardless of input dtype,
an optional bf16 MXU path for the distance cross-term, and the analytic
HBM-traffic model (``pass_hbm_bytes``) used by benchmarks/perf_dsekl.py.
Validated against ``ref.py`` in interpret mode (tests/test_dual_pass.py,
tests/test_kernels_dsekl.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Default MXU-aligned tile sizes.
BLOCK_I = 128
BLOCK_J = 128

# Bytes of VMEM one kernel's tile set may use: the TPU's default scoped
# VMEM limit is 16 MiB (v5e), less headroom for the compiler's own
# temporaries.  tests/test_tpu_compile.py holds the choosers to it.
VMEM_BUDGET = 12 * 1024 * 1024


def tile_vmem_bytes(bi: int, bj: int, d: int) -> int:
    """VMEM of one (bi, bj) tile set as the TPU lays it out: the (b, D)
    row tiles pad D to 128 lanes and every (b, 1) vector to a full 128-lane
    row, both double-buffered by the pipeline; about four (bi, bj) f32
    temporaries live at once (cross term, distances, K, and the split
    operands of the f32-precision MXU product)."""
    d_pad = -(-d // 128) * 128
    return 4 * (2 * (bi + bj) * (d_pad + 128) + 4 * bi * bj)


def choose_blocks(n_i: int, n_j: int, d: int):
    """Largest MXU-aligned (bi, bj) under the VMEM budget (see module
    docstring: HBM re-stream traffic falls ~1/bi)."""
    bj = 256 if n_j >= 256 else BLOCK_J
    bi = 1024
    while bi > 128:
        if tile_vmem_bytes(bi, bj, d) <= VMEM_BUDGET:
            break
        bi //= 2
    return max(bi, 128), bj


def pass_hbm_bytes(n_i: int, n_j: int, d: int, block_i: int,
                   block_j: int) -> int:
    """Analytic HBM reads per kernel pass (the §Perf memory-term model):
    x_I streamed once (resident across the inner j sweep) + X_J re-streamed
    once per i block + the in/out vectors."""
    ni = -(-n_i // block_i)
    return 4 * (n_i * d + ni * n_j * d + n_i + n_j)


def choose_predict_blocks(n_q: int, n_sv: int, d: int):
    """(bq, bs) for the serving matvec f = K(X_q, X_sv) @ a.

    Prediction is matvec-shaped with the output (query) tile resident across
    the support-vector sweep, so the traffic model is ``pass_hbm_bytes`` with
    I = queries: the support set is re-streamed once per query block and the
    re-stream shrinks ~1/bq.  Serving query blocks are fixed-size (the engine
    pads every micro-batch to its ``query_block``), so we push bq as high as
    the VMEM budget allows — queries are the small operand at serving time
    (n_q ~ 1k vs n_sv ~ 100k+) and a bigger bq directly divides the dominant
    X_sv re-stream — but never past the 128-aligned query count itself,
    which would only pad wasted tile evaluations."""
    bs = 256 if n_sv >= 256 else BLOCK_J
    bq = min(2048, max(128, -(-n_q // 128) * 128))
    while bq > 128:
        if tile_vmem_bytes(bq, bs, d) <= VMEM_BUDGET:
            break
        bq //= 2
    return max(bq, 128), bs


def predict_hbm_bytes(n_q: int, n_sv: int, d: int, block_q: int,
                      block_sv: int) -> int:
    """HBM traffic of one engine serve call (benchmarks/perf_dsekl.py):
    the matvec model with the query block resident."""
    return pass_hbm_bytes(n_q, n_sv, d, block_q, block_sv)


# ---------------------------------------------------------------------------
# Per-kernel tile evaluators.  Each takes f32 (bi, D) / (bj, D) tiles and
# returns the f32 (bi, bj) kernel block.  ``mxu_dtype=bf16`` runs the
# distance/inner-product cross-term matmul at the MXU's bf16 rate (f32
# accumulation) — norms and the nonlinearity stay f32.
# ---------------------------------------------------------------------------

def _dot(a: Array, b: Array, contract) -> Array:
    """MXU contraction with f32 accumulation.  f32 operands request full
    f32 precision explicitly (no silent bf16 passes); bf16 operands are the
    opt-in fast path."""
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(a, b, dimension_numbers=(contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _cross_term(xi: Array, xj: Array, mxu_dtype) -> Array:
    """xi @ xj^T on the MXU with f32 accumulation, (bi, bj)."""
    return _dot(xi.astype(mxu_dtype), xj.astype(mxu_dtype), ((1,), (1,)))


def _sq_dists_tile(xi: Array, xj: Array, mxu_dtype) -> Array:
    xy = _cross_term(xi, xj, mxu_dtype)
    xx = jnp.sum(xi * xi, axis=1, keepdims=True)        # (bi, 1)
    zz = jnp.sum(xj * xj, axis=1, keepdims=True).T      # (1, bj)
    return jnp.maximum(xx + zz - 2.0 * xy, 0.0)


def _l1_dists_tile(xi: Array, xj: Array) -> Array:
    """sum_d |xi_d - xj_d| without the (bi, bj, D) broadcast: a fori_loop
    over features keeps VMEM at O(bi*bj) (VPU work, no MXU form exists).

    Mosaic lowers no dynamic slice along the lane (feature) axis, so
    feature k is extracted by a masked lane reduction instead.  Features
    go in static 128-lane chunks so each extraction costs one vreg column
    whatever D is; the accumulation order is still k = 0..D-1."""
    bi, d = xi.shape
    bj = xj.shape[0]
    acc = jnp.zeros((bi, bj), jnp.float32)
    for c0 in range(0, d, 128):
        xic, xjc = xi[:, c0:c0 + 128], xj[:, c0:c0 + 128]
        lane_i = jax.lax.broadcasted_iota(jnp.int32, xic.shape, 1)
        lane_j = jax.lax.broadcasted_iota(jnp.int32, xjc.shape, 1)

        def body(k, acc, xic=xic, xjc=xjc, lane_i=lane_i, lane_j=lane_j):
            ci = jnp.sum(jnp.where(lane_i == k, xic, 0.0), axis=1,
                         keepdims=True)                         # (bi, 1)
            cj = jnp.sum(jnp.where(lane_j == k, xjc, 0.0), axis=1,
                         keepdims=True)                         # (bj, 1)
            return acc + jnp.abs(ci - cj.T)

        acc = jax.lax.fori_loop(0, xic.shape[1], body, acc)
    return acc


def _tile_rbf(xi, xj, mxu_dtype, *, gamma: float = 1.0):
    return jnp.exp(-gamma * _sq_dists_tile(xi, xj, mxu_dtype))


def _tile_laplacian(xi, xj, mxu_dtype, *, gamma: float = 1.0):
    del mxu_dtype  # no matmul in the L1 path
    return jnp.exp(-gamma * _l1_dists_tile(xi, xj))


def _tile_linear(xi, xj, mxu_dtype):
    return _cross_term(xi, xj, mxu_dtype)


def _tile_polynomial(xi, xj, mxu_dtype, *, gamma: float = 1.0,
                     coef0: float = 1.0, degree: int = 3):
    return (gamma * _cross_term(xi, xj, mxu_dtype) + coef0) ** degree


def _tile_sigmoid(xi, xj, mxu_dtype, *, gamma: float = 1.0,
                  coef0: float = 0.0):
    return jnp.tanh(gamma * _cross_term(xi, xj, mxu_dtype) + coef0)


def _tile_matern32(xi, xj, mxu_dtype, *, length_scale: float = 1.0):
    d = jnp.sqrt(_sq_dists_tile(xi, xj, mxu_dtype) + 1e-12) / length_scale
    z = jnp.sqrt(3.0) * d
    return (1.0 + z) * jnp.exp(-z)


def _tile_matern52(xi, xj, mxu_dtype, *, length_scale: float = 1.0):
    d = jnp.sqrt(_sq_dists_tile(xi, xj, mxu_dtype) + 1e-12) / length_scale
    z = jnp.sqrt(5.0) * d
    return (1.0 + z + z * z / 3.0) * jnp.exp(-z)


TILE_FNS: Dict[str, Callable[..., Array]] = {
    "rbf": _tile_rbf,
    "laplacian": _tile_laplacian,
    "linear": _tile_linear,
    "polynomial": _tile_polynomial,
    "sigmoid": _tile_sigmoid,
    "matern32": _tile_matern32,
    "matern52": _tile_matern52,
}


def make_tile_fn(kernel_name: str, params: Dict[str, Any],
                 mxu_dtype) -> Callable[[Array, Array], Array]:
    """Bind a registry kernel to a (xi_f32, xj_f32) -> (bi, bj) tile fn."""
    if kernel_name not in TILE_FNS:
        raise ValueError(f"no Pallas tile for kernel {kernel_name!r}; "
                         f"available: {sorted(TILE_FNS)}")
    return functools.partial(TILE_FNS[kernel_name], mxu_dtype=mxu_dtype,
                             **params)


def _pad_rows(x: Array, block: int) -> Array:
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


def _f32_col(x: Array, block: int) -> Array:
    """(n,) vector -> zero-padded f32 (n_pad, 1) column."""
    return _pad_rows(x.astype(jnp.float32)[:, None], block)


# ---------------------------------------------------------------------------
# Single-product sweeps (generalized matvec / vecmat).
# ---------------------------------------------------------------------------

def _matvec_kernel(xi_ref, xj_ref, a_ref, o_ref, *, tile_fn):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    k = tile_fn(xi_ref[...].astype(jnp.float32),
                xj_ref[...].astype(jnp.float32))        # (bi, bj)
    o_ref[...] += _dot(k, a_ref[...], ((1,), (0,)))


def _vecmat_kernel(xj_ref, xi_ref, v_ref, o_ref, *, tile_fn):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    k = tile_fn(xi_ref[...].astype(jnp.float32),
                xj_ref[...].astype(jnp.float32))        # (bi, bj)
    o_ref[...] += _dot(k, v_ref[...], ((0,), (0,)))


def kernel_matvec_pallas(x: Array, z: Array, a: Array, *,
                         kernel_name: str = "rbf",
                         params: Dict[str, Any] | None = None,
                         block_i: int = BLOCK_I, block_j: int = BLOCK_J,
                         mxu_dtype=jnp.float32,
                         interpret: bool = False) -> Array:
    """f = K(x, z) @ a.  x (I, D), z (J, D), a (J,) -> (I,)."""
    tile_fn = make_tile_fn(kernel_name, params or {}, mxu_dtype)
    n_i, d = x.shape
    xp, zp = _pad_rows(x, block_i), _pad_rows(z, block_j)
    ap = _f32_col(a, block_j)                           # zero rows are exact
    ni, nj = xp.shape[0] // block_i, zp.shape[0] // block_j

    out = pl.pallas_call(
        functools.partial(_matvec_kernel, tile_fn=tile_fn),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((block_i, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        interpret=interpret,
    )(xp, zp, ap)
    return out[:n_i, 0]


def kernel_vecmat_pallas(x: Array, z: Array, v: Array, *,
                         kernel_name: str = "rbf",
                         params: Dict[str, Any] | None = None,
                         block_i: int = BLOCK_I, block_j: int = BLOCK_J,
                         mxu_dtype=jnp.float32,
                         interpret: bool = False) -> Array:
    """g = K(x, z)^T @ v.  x (I, D), z (J, D), v (I,) -> (J,)."""
    tile_fn = make_tile_fn(kernel_name, params or {}, mxu_dtype)
    n_j, d = z.shape
    xp, zp = _pad_rows(x, block_i), _pad_rows(z, block_j)
    vp = _f32_col(v, block_i)                           # zero rows are exact
    ni, nj = xp.shape[0] // block_i, zp.shape[0] // block_j

    out = pl.pallas_call(
        functools.partial(_vecmat_kernel, tile_fn=tile_fn),
        grid=(nj, ni),
        in_specs=[
            pl.BlockSpec((block_j, d), lambda j, i: (j, 0)),
            pl.BlockSpec((block_i, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_i, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_j, 1), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((zp.shape[0], 1), jnp.float32),
        interpret=interpret,
    )(zp, xp, vp)
    return out[:n_j, 0]


# ---------------------------------------------------------------------------
# Dual pass: one K-tile evaluation, both products.
# ---------------------------------------------------------------------------

def _dual_kernel(xi_ref, xj_ref, a_ref, v_ref, f_ref, gp_ref, *, tile_fn):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)

    k = tile_fn(xi_ref[...].astype(jnp.float32),
                xj_ref[...].astype(jnp.float32))        # (bi, bj), ONCE
    f_ref[...] += _dot(k, a_ref[...], ((1,), (0,)))    # f_i += K @ a_j
    gj = _dot(k, v_ref[...], ((0,), (0,)))   # g partial K^T @ v_i, (bj, 1)
    gp_ref[...] = gj.T                                  # (1, bj), written once


def _g_parts_shape(ni: int, j_pad: int) -> jax.ShapeDtypeStruct:
    """The partial-g output: one (1, J_pad) row per i block.  The unit
    middle axis makes each (1, block_j) block span the array's full
    second-to-last dimension, which is what the TPU lowering requires of a
    block whose sublane extent is not a multiple of 8; the i axis is
    squeezed out of the block (``None``)."""
    return jax.ShapeDtypeStruct((ni, 1, j_pad), jnp.float32)


def dual_pass_pallas(x: Array, z: Array, a: Array, v: Array, *,
                     kernel_name: str = "rbf",
                     params: Dict[str, Any] | None = None,
                     block_i: int = BLOCK_I, block_j: int = BLOCK_J,
                     mxu_dtype=jnp.float32,
                     interpret: bool = False):
    """(f, g) = (K @ a, K^T @ v) with each K tile evaluated once.

    The g output is materialized as (n_i_blocks, 1, J) partial rows —
    O(ni * J) floats, tiny next to the O(I*J) block — and summed outside
    the kernel so every output block is written exactly once (no
    non-consecutive output revisits, which the TPU grid does not guarantee
    to accumulate)."""
    tile_fn = make_tile_fn(kernel_name, params or {}, mxu_dtype)
    n_i, d = x.shape
    n_j = z.shape[0]
    xp, zp = _pad_rows(x, block_i), _pad_rows(z, block_j)
    ap, vp = _f32_col(a, block_j), _f32_col(v, block_i)
    ni, nj = xp.shape[0] // block_i, zp.shape[0] // block_j

    f_out, g_parts = pl.pallas_call(
        functools.partial(_dual_kernel, tile_fn=tile_fn),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((block_i, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, block_j), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
            _g_parts_shape(ni, zp.shape[0]),
        ],
        interpret=interpret,
    )(xp, zp, ap, vp)
    return f_out[:n_i, 0], jnp.sum(g_parts, axis=(0, 1))[:n_j]


# ---------------------------------------------------------------------------
# Train pass: loss gradient fused between the two products.
# ---------------------------------------------------------------------------

def _train_kernel(xi_ref, xj_ref, a_ref, y_ref, f_ref, gp_ref,
                  kbuf, facc, vbuf, *, tile_fn, loss_grad, f_scale: float,
                  n_valid: int, block_i: int):
    i = pl.program_id(0)
    p = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(p == 0)
    def _f_sweep():
        @pl.when(j == 0)
        def _init():
            facc[...] = jnp.zeros_like(facc)

        k = tile_fn(xi_ref[...].astype(jnp.float32),
                    xj_ref[...].astype(jnp.float32))    # (bi, bj), ONCE
        kbuf[j] = k                                     # stash for the g sweep
        facc[...] += _dot(k, a_ref[...], ((1,), (0,)))

        @pl.when(j == nj - 1)
        def _loss():
            f = facc[...] * f_scale                     # (bi, 1)
            # Padded rows carry garbage f — mask their v to zero so they
            # cannot contribute to g (a/v padding elsewhere is exact).
            row = (i * block_i
                   + jax.lax.broadcasted_iota(jnp.int32, f.shape, 0))
            vbuf[...] = jnp.where(row < n_valid,
                                  loss_grad(f, y_ref[...]), 0.0)
            f_ref[...] = f

    @pl.when(p == 1)
    def _g_sweep():
        k = kbuf[j]                                     # replay, no recompute
        gj = _dot(k, vbuf[...], ((0,), (0,)))          # (bj, 1)
        gp_ref[...] = gj.T


def train_pass_blocks(n_i: int, n_j: int, d: int):
    """(bi, bj) for the train pass: the K row-block scratch (bi * J_pad f32)
    must fit the VMEM budget alongside the tiles.  Returns None if even the
    minimal 128-row block overflows (caller falls back to two fused
    single-product sweeps)."""
    bj = 256 if n_j >= 256 else BLOCK_J
    jp = -(-n_j // bj) * bj
    bi = 512
    while bi >= 128:
        # + the K row-block scratch and the f / v scratch columns.
        need = tile_vmem_bytes(bi, bj, d) + 4 * bi * (jp + 2 * 128)
        if need <= VMEM_BUDGET:
            return bi, bj
        bi //= 2
    return None


def train_pass_pallas(x: Array, z: Array, a: Array, y: Array,
                      loss_grad: Callable[[Array, Array], Array], *,
                      kernel_name: str = "rbf",
                      params: Dict[str, Any] | None = None,
                      f_scale: float = 1.0,
                      block_i: int = BLOCK_I, block_j: int = BLOCK_J,
                      mxu_dtype=jnp.float32,
                      interpret: bool = False):
    """(f, g) = (s * K @ a, K^T @ loss_grad(f, y)) — one K-tile evaluation.

    v depends elementwise on the *completed* f row-block, so the grid runs
    two phases per i block: phase 0 sweeps j computing each K tile once
    (stashed in VMEM scratch) while accumulating f, then derives
    v = loss_grad(f * f_scale, y); phase 1 replays the stashed tiles for
    the g partials.  Scratch cost: bi * J_pad f32 (see train_pass_blocks).
    """
    tile_fn = make_tile_fn(kernel_name, params or {}, mxu_dtype)
    n_i, d = x.shape
    n_j = z.shape[0]
    xp, zp = _pad_rows(x, block_i), _pad_rows(z, block_j)
    ap, yp = _f32_col(a, block_j), _f32_col(y, block_i)
    ni, nj = xp.shape[0] // block_i, zp.shape[0] // block_j

    f_out, g_parts = pl.pallas_call(
        functools.partial(_train_kernel, tile_fn=tile_fn,
                          loss_grad=loss_grad, f_scale=f_scale,
                          n_valid=n_i, block_i=block_i),
        grid=(ni, 2, nj),
        in_specs=[
            pl.BlockSpec((block_i, d), lambda i, p, j: (i, 0)),
            pl.BlockSpec((block_j, d), lambda i, p, j: (j, 0)),
            pl.BlockSpec((block_j, 1), lambda i, p, j: (j, 0)),
            pl.BlockSpec((block_i, 1), lambda i, p, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, 1), lambda i, p, j: (i, 0)),
            # Phase 0 parks on block (i, 0, 0), which phase 1 writes first,
            # so each partial-g block is visited in one consecutive run and
            # written back once.
            pl.BlockSpec((None, 1, block_j), lambda i, p, j: (i, 0, j * p)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
            _g_parts_shape(ni, zp.shape[0]),
        ],
        scratch_shapes=[
            pltpu.VMEM((nj, block_i, block_j), jnp.float32),
            pltpu.VMEM((block_i, 1), jnp.float32),
            pltpu.VMEM((block_i, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, zp, ap, yp)
    return f_out[:n_i, 0], jnp.sum(g_parts, axis=(0, 1))[:n_j]
