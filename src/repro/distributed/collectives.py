"""Hand-scheduled collectives for compute/communication overlap.

XLA's default for a sharded contraction is: all-gather the operand, THEN
run one big matmul — comm and compute serialize.  These shard_map-level
schedules decompose the same math into N ring steps where each step's
matmul overlaps the next step's ppermute (on TPU the ICI transfer runs on
the transfer cores concurrently with the MXU):

  * ``allgather_matmul_overlapped`` — y = all_gather(x) @ w, computed one
    source-shard block-row at a time while the next x shard is in flight.
  * ``ring_psum_matmul`` — y = psum_j(x_j @ w_j) for a contraction-sharded
    matmul: each device computes its partial once, then the accumulator
    rides the ring, adding the local partial at every hop (a bandwidth-
    optimal ring all-reduce whose hops overlap the partial matmuls of
    *other* layers in flight).

Exactness is asserted against the naive gathered versions in
tests/test_distributed_tricks.py; the §Perf hillclimb uses these as the
opt-in TP schedule.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _ring_perm(n_dev: int):
    return [(j, (j + 1) % n_dev) for j in range(n_dev)]


def allgather_matmul_overlapped(x: Array, w: Array, axis: str) -> Array:
    """Inside shard_map: x (m_loc, k) is this device's row-shard of the
    full (N*m_loc, k) activation; w (k, n) is replicated over ``axis``.
    Returns the FULL (N*m_loc, n) product, assembled ring-step by ring-step
    (block i computed as soon as shard i arrives)."""
    n_dev = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    m_loc = x.shape[0]
    out = jnp.zeros((n_dev * m_loc, w.shape[-1]), x.dtype)

    def body(i, carry):
        x_held, out = carry
        # perm sends j -> j+1, so after i hops we hold shard (me - i).
        src = (me - i) % n_dev
        block = jnp.einsum("mk,kn->mn", x_held, w)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, block.astype(out.dtype), src * m_loc, axis=0)
        x_next = jax.lax.ppermute(x_held, axis, _ring_perm(n_dev))
        return (x_next, out)

    _, out = jax.lax.fori_loop(0, n_dev, body, (x, out))
    return out


def ring_psum_matmul(x: Array, w: Array, axis: str) -> Array:
    """Inside shard_map: x (m, k_loc) and w (k_loc, n) are matching shards
    of a contraction dim sharded over ``axis``.  Returns the full (m, n)
    sum on every device via a ring all-reduce of the partial products."""
    n_dev = jax.lax.axis_size(axis)
    partial = jnp.einsum("mk,kn->mn", x, w).astype(jnp.float32)
    acc = partial
    for _ in range(n_dev - 1):              # unrolled: each hop overlappable
        acc = jax.lax.ppermute(acc, axis, _ring_perm(n_dev))
        acc = acc + partial
    return acc.astype(x.dtype)
