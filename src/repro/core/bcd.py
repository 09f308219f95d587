"""Block coordinate descent over the empirical kernel map (DESIGN.md §14).

Tu et al., *Large Scale Kernel Learning using Block Coordinate Descent*
(PAPERS.md), solve the regularized empirical-kernel-map system

    (1/2) ||K alpha - y||^2 + (lam * n / 2) alpha^T K alpha

by exact block solves: each round draws a without-replacement coordinate
block J and updates alpha_J by solving the |J| x |J| system

    (K_{J,.} K_{.,J} + lam*n * K_{J,J} + jitter*I) d = K_{J,.} (y - f)
                                                       - lam*n * f_J

where ``f = K alpha`` is the residual decision vector, maintained
INCREMENTALLY across rounds: after the solve, ``f += K_{.,J} d`` — the
only kernel evaluations a round pays are the two streamed passes over
``K_{.,J}`` (Gram/rhs accumulation, then the f update) plus the |J| x |J|
diagonal block.  That is ~2n|J| + |J|^2 kernel-tile entries per round,
against the doubly stochastic step's n_grad * n_expand per step — and a
round makes an EXACT block of progress, which is the whole head-to-head
(benchmarks/perf_dsekl.py, ``bcd`` cell).

Memory discipline matches the PR 2 streaming pass: ``K_{.,J}`` is never
materialized — rows stream through ``kops.kernel_block`` in
``(row_block, |J|)`` tiles gathered by the existing
``BlockPrefetcher`` / ``MeshPrefetcher`` data plane.

Bit-reproducibility across placements (the trainer contract): the row
range is partitioned into ``shards`` contiguous groups; each group's
Gram/rhs partial accumulates independently (sequentially on the serial
loop, one per data-axis device on the mesh) and the partials are
combined ON HOST in fixed index order — so a serial loop with
``bcd_shards = n_data`` is bit-identical to the mesh run, no psum
reduction-order caveats.  The solve itself always runs as one
single-device jitted Cholesky on the host-combined system, in both
placements.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dsekl import DSEKLConfig
from repro.kernels.dsekl import ops as kops

Array = jax.Array
P = jax.sharding.PartitionSpec

# Cholesky jitter escalation: multiples of the relative floor
# cfg.bcd_jitter * trace(A)/|J| tried in order until the factorization
# is finite.  Host-driven, so serial and mesh walk the identical ladder.
JITTER_LADDER = (1.0, 10.0, 100.0, 1e4, 1e6)


def block_size(cfg: DSEKLConfig, n: int) -> int:
    """|J| of one round: cfg.bcd_block, defaulting to n_expand, capped at n."""
    j = int(cfg.bcd_block or cfg.n_expand)
    return min(j, int(n))


def row_block_size(cfg: DSEKLConfig) -> int:
    """Streamed row-tile size: cfg.bcd_row_block, defaulting to n_grad."""
    return int(cfg.bcd_row_block or cfg.n_grad)


def kernel_tile_evals_per_round(n: int, j: int) -> int:
    """Kernel-map entries one BCD round evaluates: two streamed passes
    over K_{.,J} plus the K_{J,J} diagonal block."""
    return 2 * n * j + j * j


def sample_block(key: Array, n: int, j: int) -> np.ndarray:
    """Draw the round's coordinate block J WITHOUT replacement.

    With replacement (the stochastic step's ``sampler.sample_uniform``)
    a duplicated coordinate would make the Gram system singular and
    double-scatter its update — the exact solve needs distinct columns.
    """
    return np.asarray(jax.random.choice(key, n, shape=(j,), replace=False),
                      dtype=np.int64)


def row_plan(n: int, shards: int, row_block: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Round-invariant streaming plan over the n rows.

    Rows split into ``shards`` equal contiguous groups (``n % shards``
    must be 0 when shards > 1), each streamed in ``row_block``-row tiles;
    the tail tile clamps to the group's last row and masks the padding,
    so every group has the identical local tile structure (the mesh's
    per-device shape).  Returns ``idx (shards, blocks, row_block)``
    GLOBAL row indices and ``mask (blocks, row_block)`` float32 (shared
    across groups by construction).
    """
    if shards > 1 and n % shards:
        raise ValueError(
            f"bcd row groups need n divisible by shards (n={n}, "
            f"shards={shards})")
    n_loc = n // shards
    blocks = -(-n_loc // row_block)
    local = np.arange(blocks * row_block, dtype=np.int64)
    mask = (local < n_loc).astype(np.float32).reshape(blocks, row_block)
    local = np.minimum(local, n_loc - 1).reshape(blocks, row_block)
    idx = (np.arange(shards, dtype=np.int64)[:, None, None] * n_loc
           + local[None])
    return idx, mask


def combine_partials(parts: np.ndarray) -> np.ndarray:
    """Sum per-group augmented Gram/rhs partials on host in fixed index
    order.

    This replaces a device psum on purpose: host float32 adds in group
    order are placement-independent, so serial-with-shards and the mesh
    land on the same bits (module docstring).
    """
    out = parts[0].copy()
    for d in range(1, parts.shape[0]):
        out += parts[d]
    return out


# ---------------------------------------------------------------------------
# Tile cores shared by the serial and mesh rounds.
#
# Both products run as fixed-shape GEMMs — the Gram AND the rhs in one
# (|J|, rb) x (rb, |J|+1) augmented product, the f update as
# (rb, |J|) x (|J|, 1) — because a bare matvec's reduction can be
# reassociated differently by the serial and shard_map compilations,
# which would break the serial==mesh bitwise contract a GEMM keeps.
# ---------------------------------------------------------------------------

def _acc_tile(cfg: DSEKLConfig, xi: Array, yi: Array, xj: Array,
              f_rows: Array, mask: Array) -> Array:
    """One (row_block, |J|) tile's augmented Gram/rhs contribution:
    [K_b^T K_b | K_b^T (y_b - f_b)] as a (|J|, |J|+1) block, padding
    rows masked to zero."""
    kb = kops.kernel_block(xi, xj, kernel_name=cfg.kernel,
                           kernel_params=cfg.kernel_params)
    kbm = kb * mask[:, None]
    r = (yi - f_rows) * mask
    aug = jnp.concatenate([kbm, r[:, None]], axis=1)
    return kbm.T @ aug


def _fupd_tile(cfg: DSEKLConfig, xi: Array, xj: Array, delta: Array,
               mask: Array) -> Array:
    """Pass-2 tile contribution mask * (K_b @ delta), as a GEMM."""
    kb = kops.kernel_block(xi, xj, kernel_name=cfg.kernel,
                           kernel_params=cfg.kernel_params)
    return mask * (kb @ delta[:, None])[:, 0]


def split_gram(gb: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(|J|, |J|+1) augmented accumulator -> (Gram, rhs-partial)."""
    return np.ascontiguousarray(gb[:, :-1]), np.ascontiguousarray(gb[:, -1])


# ---------------------------------------------------------------------------
# Serial (single-device) round ops.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def acc_serial(cfg: DSEKLConfig, xi: Array, yi: Array, xj: Array, f: Array,
               idx: Array, mask: Array, gb: Array) -> Array:
    """Fold one tile into the (|J|, |J|+1) augmented accumulator."""
    return gb + _acc_tile(cfg, xi, yi, xj, f[idx], mask)


@functools.partial(jax.jit, static_argnames=("cfg",))
def fupd_serial(cfg: DSEKLConfig, xi: Array, xj: Array, delta: Array,
                f: Array, idx: Array, mask: Array) -> Array:
    """Pass-2 incremental residual update: f[rows] += K_b @ delta.
    Clamped tail duplicates carry mask 0, so they add exactly nothing."""
    return f.at[idx].add(_fupd_tile(cfg, xi, xj, delta, mask))


@jax.jit
def scatter_alpha(alpha: Array, idx_j: Array, delta: Array) -> Array:
    """alpha_J += delta (J has no duplicates — sample_block)."""
    return alpha.at[idx_j].add(delta)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _chol_solve(cfg: DSEKLConfig, xj: Array, g: Array, rhs: Array,
                lam_n: Array, mult: Array) -> Tuple[Array, Array]:
    """One jitter-ladder attempt on A = G + lam*n*K_JJ + jitter*I.

    Returns (delta, ok); a non-PD A surfaces as NaNs in the Cholesky
    factor (no exception under jit), which ``ok`` catches on host.
    """
    kjj = kops.kernel_block(xj, xj, kernel_name=cfg.kernel,
                            kernel_params=cfg.kernel_params)
    a = g + lam_n * kjj
    jitter = mult * cfg.bcd_jitter * (jnp.trace(a) / a.shape[0])
    a = a + jitter * jnp.eye(a.shape[0], dtype=a.dtype)
    chol = jax.scipy.linalg.cholesky(a, lower=True)
    delta = jax.scipy.linalg.cho_solve((chol, True), rhs)
    ok = jnp.all(jnp.isfinite(chol)) & jnp.all(jnp.isfinite(delta))
    return delta, ok


def solve_block(cfg: DSEKLConfig, xj: np.ndarray, g: np.ndarray,
                rhs: np.ndarray, lam_n: float) -> Tuple[Array, float]:
    """Solve the round's block system on device, escalating the jitter
    through ``JITTER_LADDER`` until the Cholesky is finite.

    Host-combined numpy inputs in, single-device delta out — the one
    code path both the serial loop and the mesh round call, which is
    what makes their solves bitwise-identical.
    """
    for mult in JITTER_LADDER:
        delta, ok = _chol_solve(cfg, jnp.asarray(xj), jnp.asarray(g),
                                jnp.asarray(rhs), np.float32(lam_n),
                                np.float32(mult))
        if bool(ok):
            return delta, mult
    raise RuntimeError(
        "BCD block solve failed: Cholesky not finite at the top of the "
        f"jitter ladder (bcd_jitter={cfg.bcd_jitter!r}; raise it, or "
        "shrink bcd_block)")


# ---------------------------------------------------------------------------
# Mesh round ops: row blocks shard over the data axis, x_J replicated.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshBCDOps:
    """The three jitted shard_map ops of a mesh BCD round plus the
    shardings its data plane places to (``MeshPrefetcher`` consumes
    ``shardings`` exactly like the stochastic step's)."""
    acc: callable
    fupd: callable
    scatter: callable
    shardings: tuple          # (xi, yi, xj, idx_j) for the prefetcher
    f_sharding: jax.sharding.NamedSharding
    gram_sharding: jax.sharding.NamedSharding
    rep_sharding: jax.sharding.NamedSharding


def make_mesh_bcd_ops(cfg: DSEKLConfig, mesh, *, data_axis: str = "data",
                      model_axis: str = "model") -> MeshBCDOps:
    """Build the mesh round: every data-axis device streams its local
    row tiles against the REPLICATED x_J and accumulates a private
    (|J|, |J|) Gram partial — no cross-device reduction on device; the
    (n_data, |J|, |J|) partial stack comes back to host and
    ``combine_partials`` sums it in fixed order (bit-identical to the
    serial loop with ``bcd_shards = n_data``).  f is P(data)-sharded,
    alpha stays P(model) so the stochastic step's psum'd eval
    (``make_mesh_eval``) serves BCD unchanged.
    """
    ns = functools.partial(jax.sharding.NamedSharding, mesh)
    xi_sh, yi_sh = ns(P(data_axis, None)), ns(P(data_axis))
    rep_sh = ns(P())
    f_sh = ns(P(data_axis))
    gram_sh = ns(P(data_axis, None, None))

    def _acc_body(xi, yi, xj, f_loc, idx, mask, gb):
        return gb + _acc_tile(cfg, xi, yi, xj, f_loc[idx], mask)[None]

    acc = jax.jit(jax.shard_map(
        _acc_body, mesh=mesh,
        in_specs=(P(data_axis, None), P(data_axis), P(), P(data_axis),
                  P(), P(), P(data_axis, None, None)),
        out_specs=P(data_axis, None, None),
        check_vma=False))

    def _fupd_body(xi, xj, delta, f_loc, idx, mask):
        return f_loc.at[idx].add(_fupd_tile(cfg, xi, xj, delta, mask))

    fupd = jax.jit(jax.shard_map(
        _fupd_body, mesh=mesh,
        in_specs=(P(data_axis, None), P(), P(), P(data_axis), P(), P()),
        out_specs=P(data_axis), check_vma=False))

    def _scatter_body(alpha_loc, idx_j, delta):
        # Global J -> this model shard's local rows; out-of-range
        # coordinates are dropped by the OOB scatter (the
        # _local_block_step_precond pattern in core/distributed.py).
        rows_m = alpha_loc.shape[0]
        local = idx_j - jax.lax.axis_index(model_axis) * rows_m
        safe = jnp.where((local >= 0) & (local < rows_m), local, rows_m)
        return alpha_loc.at[safe].add(delta)

    scatter = jax.jit(jax.shard_map(
        _scatter_body, mesh=mesh,
        in_specs=(P(model_axis), P(), P()), out_specs=P(model_axis),
        check_vma=False))

    return MeshBCDOps(acc=acc, fupd=fupd, scatter=scatter,
                      shardings=(xi_sh, yi_sh, rep_sh, rep_sh),
                      f_sharding=f_sh, gram_sharding=gram_sh,
                      rep_sharding=rep_sh)
