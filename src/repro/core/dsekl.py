"""Doubly Stochastic Empirical Kernel Learning — the paper's Algorithms 1 & 2.

Algorithm 1 (serial):  every step draws two independent uniform index sets
  I (gradient points) and J (kernel-map expansion points), computes the dual
  gradient on the sampled K_{I,J} block and updates alpha_J with rate 1/t.

Algorithm 2 (parallel, shared memory):  per epoch, fresh without-replacement
  partitions of {1..N} into gradient batches I^(k) and expansion batches
  J^(k); for each gradient batch, K workers jointly evaluate the kernel map
  over the union of their J^(k) (the partial decision values are summed
  across workers) and compute the block gradients; updates are dampened by
  the aggregated AdaGrad matrix  alpha <- alpha - lr * G^{-1/2} sum_k g^(k).

Both are pure jittable functions over an explicit ``DSEKLState`` and share
one step core: Algorithm 2's step is Algorithm 1's block step over the
union of its K worker blocks (``grad_block`` + ``apply_update``), with the
EigenPro correction an optional term of both.  The distributed 2-D mesh
variant lives in ``core/distributed.py`` and reuses the same block
computation (``_block_f`` / ``_block_grad``, ``apply_update``,
``precond_correction``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import losses as losses_lib
from repro.core import sampler
from repro.kernels.dsekl import ops as kops

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DSEKLConfig:
    """Hyperparameters of the doubly stochastic learner (hashable/static)."""
    n_grad: int = 128                 # |I|  — samples for the gradient
    n_expand: int = 128               # |J|  — samples for the kernel map (per worker)
    kernel: str = "rbf"
    kernel_params: Tuple[Tuple[str, float], ...] = (("gamma", 1.0),)
    loss: str = "hinge"               # paper Eq. 4
    lam: float = 1e-3                 # L2 on dual coefficients
    lr0: float = 1.0
    # "inv_t": paper Alg. 1 (1/t per step); "inv_epoch": paper §4.2 covertype;
    # "const"; "adagrad": paper Alg. 2 dampening (lr0 * G^{-1/2}).
    schedule: str = "inv_t"
    n_workers: int = 1                # K of Alg. 2
    # Beyond-paper: scale the J-expansion by N/|J| so f is an unbiased
    # estimate of the full empirical kernel map (the paper omits this).
    unbiased_scaling: bool = False
    impl: str = "auto"                # kernel op backend (see kernels/dsekl/ops.py)
    # Evaluate the sampled K_{I,J} block ONCE per step (fused dual pass:
    # f and g from the same kernel evaluation) instead of the paper-faithful
    # two-pass matvec+vecmat.  False keeps the two-pass path for A/B
    # comparison (benchmarks/perf_dsekl.py measures the speedup).
    fuse_dual_pass: bool = True
    # Beyond-paper (paper §5 future work): quantize the cross-device dual-
    # gradient reduction.  0 = exact psum; 8 = int8 stochastic-rounded psum
    # (4x less gradient traffic on the data axis).
    compress_bits: int = 0
    # Streaming dual pass (DESIGN.md §6): consume K_{I,J} in (row_block, |J|)
    # tiles instead of holding the whole |I| x |J| block — each tile is still
    # evaluated ONCE for both f and g.  0 = off (whole-block paths above);
    # > 0 = the I row-block size for the step core's ref path (either
    # algorithm) and the mesh step's fused form (peak kernel-block memory
    # O(row_block * |J|)).
    stream_row_block: int = 0
    # Training execution backend (core/trainer.py): "auto" resolves from
    # the data placement (mesh given -> mesh; host-resident DataSource ->
    # hosted; else the in-memory backend matching ``algorithm``);
    # "serial"/"parallel"/"hosted"/"mesh" force a specific ExecutionPlan.
    execution: str = "auto"
    # EigenPro preconditioning (DESIGN.md §10; core/precond.py): estimate
    # the top-k eigensystem of the kernel operator from a Nystrom subsample
    # once per fit and correct every step's gradient measure.  0 = off —
    # the default, and precondition-off fits trace to the identical
    # program (the bit-repro contract).
    precondition_k: int = 0
    # Nystrom subsample size for the one-time host-side eigensolve
    # (0 = auto: min(N, max(4 * (k + 1), 512))).
    precondition_m: int = 0
    # Spectral damping exponent rho of the EigenPro recipe.
    precondition_damping: float = 0.95
    # Under schedule="const" with a preconditioner, replace lr0 by the
    # recipe's auto step size — margin * 2N / (|J_union| * damped_top),
    # the stability cap of the DAMPED stochastic operator (precond.py);
    # False keeps the given lr0 (e.g. a matched-lr A/B).
    precondition_auto_lr: bool = True
    # Block coordinate descent (core/bcd.py; DESIGN.md §14).  Square-loss
    # only: each round draws a without-replacement coordinate block J,
    # streams K_{.,J} row-block by row-block and solves the |J| x |J|
    # regularized Gram system exactly.  bcd_block = |J| (0 -> n_expand);
    # bcd_row_block = streamed row-tile size (0 -> n_grad).
    bcd_block: int = 0
    bcd_row_block: int = 0
    # Number of contiguous row groups whose Gram/rhs partials are
    # accumulated independently and combined on host in fixed order.
    # 0 = auto (1 for the serial loop, the data-axis size on a mesh);
    # a serial fit pins it to a mesh's data-axis size to be bit-identical
    # to that mesh run (tests/test_bcd.py).  N must divide evenly when > 1.
    bcd_shards: int = 0
    # Relative Cholesky jitter floor: the solve adds
    # jitter_mult * bcd_jitter * trace(A)/|J| * I and escalates
    # jitter_mult through a fixed ladder until the factorization succeeds.
    bcd_jitter: float = 1e-6

    def replace(self, **kw) -> "DSEKLConfig":
        return dataclasses.replace(self, **kw)


class DSEKLState(NamedTuple):
    alpha: Array          # (N,) dual coefficients — the entire model
    accum: Array          # (N,) AdaGrad accumulator G_jj (Alg. 2; init 1)
    step: Array           # () int32, t of Alg. 1
    epoch: Array          # () int32, i of §4.2


def init_state(n: int, dtype=jnp.float32) -> DSEKLState:
    return DSEKLState(
        alpha=jnp.zeros((n,), dtype),
        accum=jnp.ones((n,), dtype),   # Alg. 2 line 4: G <- identity
        step=jnp.zeros((), jnp.int32),
        epoch=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Block computation shared by all variants.
# ---------------------------------------------------------------------------

def _block_f(cfg: DSEKLConfig, xi: Array, xj: Array, aj: Array, n: int) -> Array:
    """Partial decision values f_I from one expansion block (fused matvec)."""
    f = kops.kernel_matvec(xi, xj, aj, kernel_name=cfg.kernel,
                           kernel_params=cfg.kernel_params, impl=cfg.impl)
    if cfg.unbiased_scaling:
        f = f * (n / xj.shape[0])
    return f


def _block_grad(cfg: DSEKLConfig, xi: Array, xj: Array, aj: Array,
                v: Array) -> Array:
    """g_J = K_{I,J}^T v + lam * alpha_J for one block (fused vecmat)."""
    g = kops.kernel_vecmat(xi, xj, v, kernel_name=cfg.kernel,
                           kernel_params=cfg.kernel_params, impl=cfg.impl)
    return g + cfg.lam * aj


def _fused_f_and_grad(cfg: DSEKLConfig, xi: Array, yi: Array, xj: Array,
                      aj: Array, n: int) -> Tuple[Array, Array]:
    """f_I and g_J = K^T dloss/df + lam*alpha_J with K_{I,J} evaluated ONCE
    (the fused dual pass; the two-pass path evaluates K per product)."""
    f_scale = (n / xj.shape[0]) if cfg.unbiased_scaling else 1.0
    f, g = kops.kernel_dual_pass(
        xi, xj, aj, yi, kernel_name=cfg.kernel,
        kernel_params=cfg.kernel_params, loss=cfg.loss, f_scale=f_scale,
        impl=cfg.impl)
    return f, g + cfg.lam * aj


def streaming_train_pass(cfg: DSEKLConfig, xi: Array, yi: Array, xj: Array,
                         aj: Array, n: int, *, row_block: int,
                         f_reduce=None) -> Tuple[Array, Array]:
    """The fused training-step body consuming K_{I,J} row-block-by-row-block.

    A ``lax.scan`` over (row_block, |J|) tiles of the gradient batch: each
    tile K_b is evaluated ONCE (the dual-pass contract), giving

        f_b = f_reduce(K_b @ a_J)       # cross-device psum on the mesh
        v_b = dloss/df(f_b, y_b)
        g  += K_b^T @ v_b

    so the compiled step's peak kernel-block intermediate is
    O(row_block * |J|) — never the full |I| x |J| block the whole-block
    fused paths materialize (``kernel_block`` on the mesh,
    ``ref_kernel_train_pass`` on the serial ref path).

    ``f_reduce`` is the hook that lets the mesh step complete the model-axis
    reduction of the partial decision values *per row block*, before the
    loss gradient is taken; ``None`` is the single-device identity.  Padded
    tail rows get their v masked to zero, so they contribute nothing to g.

    Returns ``(f (|I|,), g_data (|J|,))`` — g without the lam*alpha_J term
    (mesh callers psum over the data axis first, exactly like the
    whole-block path).  Tiling helpers are shared with the prediction
    engine (``kops.tile_rows``).
    """
    loss = losses_lib.get_loss(cfg.loss)
    n_i = xi.shape[0]
    f_scale = (n / xj.shape[0]) if cfg.unbiased_scaling else 1.0
    xi_t = kops.tile_rows(xi, row_block)                    # (nb, rb, D)
    yi_t = kops.tile_rows(yi, row_block)                    # (nb, rb)
    valid = kops.tile_rows(jnp.ones((n_i,), jnp.float32), row_block)

    def body(g_acc, tile):
        xb, yb, mb = tile
        kb = kops.kernel_block(xb, xj, kernel_name=cfg.kernel,
                               kernel_params=cfg.kernel_params)  # ONCE
        fb = f_scale * (kb @ aj)
        if f_reduce is not None:
            fb = f_reduce(fb)
        vb = loss.grad_f(fb, yb) * mb
        return g_acc + kb.T @ vb, fb

    g0 = jnp.zeros((xj.shape[0],), jnp.float32)
    g, f_t = jax.lax.scan(body, g0, (xi_t, yi_t, valid))
    return f_t.reshape(-1)[:n_i], g


def _lr(cfg: DSEKLConfig, state: DSEKLState) -> Array:
    if cfg.schedule == "inv_t":
        return cfg.lr0 / jnp.maximum(state.step.astype(jnp.float32), 1.0)
    if cfg.schedule == "inv_epoch":
        return cfg.lr0 / jnp.maximum(state.epoch.astype(jnp.float32), 1.0)
    if cfg.schedule in ("const", "adagrad"):
        return jnp.asarray(cfg.lr0, jnp.float32)
    raise ValueError(f"unknown schedule {cfg.schedule!r}")


# ---------------------------------------------------------------------------
# Block-parametrized step core (the out-of-core data plane, DESIGN.md §8).
#
# The jittable inner body of Algorithms 1 & 2, parametrized by PRE-GATHERED
# blocks instead of the whole dataset: compile cost is a function of
# (n_grad, |J|, D) only, so ONE compiled gradient core serves any N and
# any dataset — the in-memory steps below trace through it, and the
# host-resident DataSource path (data/source.py, solver.fit) feeds it
# gathered blocks from storage.
#
# Algorithm 2's K workers jointly evaluate the kernel map over the union
# of their disjoint expansion blocks: sum_k K_{I,J^k} a_{J^k} ==
# K_{I,J_union} @ a_union, so its body IS Algorithm 1's over the union,
# flattened to (K*j, D) / (K*j,) in worker order.  Every caller passes
# whatever J its algorithm draws; the core never sees K.
# ---------------------------------------------------------------------------

def grad_block(cfg: DSEKLConfig, xi: Array, yi: Array, xj: Array, aj: Array,
               n: int = 0, pc: "PrecondBlock" = None,
               j_union: Optional[int] = None
               ) -> Tuple[Array, Optional[Array]]:
    """Dual gradient g_J (incl. lam*alpha_J) for one gathered I-batch
    against the J it is given, and the EigenPro correction of the step.

    Shapes: xi (n_grad, D), yi (n_grad,), xj (|J|, D), aj (|J|,) — one
    expansion block (Alg. 1) or the K-worker union (Alg. 2).  ``n`` is
    consumed ONLY by ``cfg.unbiased_scaling`` (the N/|J| empirical-map
    scale); with scaling off pass 0 so the jitted form never specializes
    on the dataset size.  Returns ``(g, delta)``: ``delta`` is the
    correction at ``pc``'s rows (``precond_correction`` scaled by
    ``j_union``, default |J|), None when ``pc`` is None — the
    unpreconditioned program.  Alg. 2 passes n_workers * n_expand, which
    is |J| unless an epoch holds fewer expansion batches than workers.
    """
    if cfg.stream_row_block > 0 and kops.resolve_impl(cfg.impl,
                                                      cfg.kernel) == "ref":
        # Streaming dual pass: K consumed in (row_block, |J|) tiles, each
        # evaluated once for f and g (the pallas backends stream in-kernel
        # already, so streaming only applies to the ref path).
        f, g = streaming_train_pass(cfg, xi, yi, xj, aj, n,
                                    row_block=cfg.stream_row_block)
        g = g + cfg.lam * aj
    elif cfg.fuse_dual_pass:
        f, g = _fused_f_and_grad(cfg, xi, yi, xj, aj, n)
    else:
        f = _block_f(cfg, xi, xj, aj, n)
        v = losses_lib.get_loss(cfg.loss).grad_f(f, yi)
        g = _block_grad(cfg, xi, xj, aj, v)
    if pc is None:
        return g, None
    # Every branch produced f on the way to g; the correction needs the
    # loss gradient v at the gradient rows.
    v = losses_lib.get_loss(cfg.loss).grad_f(f, yi)
    return g, precond_correction(cfg, xi, v, pc, j_union or xj.shape[0])


def apply_update(cfg: DSEKLConfig, state: DSEKLState, idx_j: Array,
                 g: Array, idx_p: Array = None,
                 delta: Array = None) -> DSEKLState:
    """Scatter one block gradient into the O(N) state, then the EigenPro
    correction ``delta`` at ``idx_p`` (when given).

    The only N-shaped piece of a step — pure scatter/gather arithmetic, no
    kernel work.  ``idx_j`` is the step's flat J (Alg. 2: the worker
    union).  The rate is ``_lr`` at the incremented step.  The G_jj
    accumulator is Alg. 2's AdaGrad matrix, touched ONLY under
    ``schedule="adagrad"`` (lines 11 + 14: G_jj += g_j^2;
    alpha -= lr * G^{-1/2} g); the correction takes the scalar rate alone
    — it is its own preconditioner.
    """
    state = state._replace(step=state.step + 1)
    lr = _lr(cfg, state)
    accum = state.accum
    if cfg.schedule == "adagrad":
        accum = accum.at[idx_j].add(g * g)
        damp = jax.lax.rsqrt(accum[idx_j])
        alpha = state.alpha.at[idx_j].add(-lr * damp * g)
    else:
        alpha = state.alpha.at[idx_j].add(-lr * g)
    if delta is not None:
        alpha = alpha.at[idx_p].add(lr * delta)
    return state._replace(alpha=alpha, accum=accum)


# ---------------------------------------------------------------------------
# EigenPro preconditioning (DESIGN.md §10).
#
# The correction is a small extra matmul after the dual pass: with U (m, k)
# the generalized eigenvectors of the squared Nystrom operator, q (k,) the
# per-unit damping and P the subsample rows, the step cancels the top-k
# K^2-eigendirection components of its expected update via
#
#     delta = U ((|J| q) * (U^T (K_{P,I} @ v)))    # (m,)
#     alpha_P += lr * delta                        # alongside alpha_J -= lr*g
#
# |J| is the step's J-union size (serial: n_expand; parallel: n_workers *
# n_expand; the mesh: n_model * n_expand): the main update covers only
# |J|/n of the effective operator per step in expectation while the
# correction fires deterministically, so the |J| multiplier (the 1/n lives
# in q) makes the cancellation exact in expectation.  K_{P,I} @ v is one
# kernel_vecmat over the gathered preconditioner rows — the rows travel
# with the step exactly like the expansion block, so the compiled shapes
# stay N-independent.  ``core/precond.py`` estimates the eigensystem and
# owns the auto step-size rule.
# ---------------------------------------------------------------------------

class PrecondBlock(NamedTuple):
    """Device-resident EigenPro preconditioner, shaped like any other block.

    rows (m, D) subsample rows; vectors (m, k) generalized eigenvectors of
    the squared Nystrom operator (B-orthonormal); damping (k,) the
    per-unit-J damped spectrum (``precond.py``); indices (m,) int32 global
    row ids the correction scatters into.
    """
    rows: Array
    vectors: Array
    damping: Array
    indices: Array


def precond_correction(cfg: DSEKLConfig, xi: Array, v: Array,
                       pc: PrecondBlock, j_union: int,
                       c_reduce=None) -> Array:
    """delta = U ((|J| q) * (U^T (K_{P,I} @ v))) — the EigenPro correction
    of one step's expected update (v = dloss/df at the gradient rows;
    ``j_union`` the number of expansion coordinates the step scatters).
    ``c_reduce`` completes K_{P,I} @ v over the gradient rows other
    devices hold (the mesh's data-axis psum); ``None`` is the identity."""
    c = kops.kernel_vecmat(xi, pc.rows, v, kernel_name=cfg.kernel,
                           kernel_params=cfg.kernel_params, impl=cfg.impl)
    if c_reduce is not None:
        c = c_reduce(c)
    return pc.vectors @ ((float(j_union) * pc.damping)
                         * (pc.vectors.T @ c))


def scale_n(cfg: DSEKLConfig, n: int) -> int:
    """The static ``n`` a gradient core needs: the dataset size when
    ``unbiased_scaling`` is on, else the 0 sentinel so the compiled core is
    N-independent (one compilation serves every dataset)."""
    return n if cfg.unbiased_scaling else 0


# Jitted entry points for host-driven (out-of-core) steps.  ``n`` is static
# but callers pass ``scale_n(cfg, n)`` — 0 unless unbiased_scaling, so the
# compile cache is keyed on (cfg, n_grad, |J|, D) only and N never
# retraces the kernel work (tests/test_outofcore_training.py asserts the
# compile count).  The N-shaped scatter is a separate dispatch
# (``trainer._apply_then_gather``).
grad_block_jit = jax.jit(grad_block, static_argnames=("cfg", "n", "j_union"))


# ---------------------------------------------------------------------------
# The matrix the in-memory steps gather rows from.
# ---------------------------------------------------------------------------

LANES = 128


@jax.tree_util.register_pytree_node_class
class PaddedRows:
    """X (N, D) held as ``rows`` (N, 128 * ceil(D / 128)): zero columns up
    to a whole number of lanes.  A TPU lays an f32 (N, D) matrix with D
    not a multiple of 128 out column-major (rows on the lanes), so each
    gathered row spans ceil(D / 8) tiles; the padded matrix is row-major
    and a row is one contiguous tile row.  Indexing gathers padded rows
    and drops the padding, so ``x[idx]`` is bit-for-bit the unpadded
    gather and the train pass sees (|I|, D) as before.  ``d`` is static
    (pytree aux data): the steps take either this or a plain array."""

    def __init__(self, rows: Array, d: int):
        self.rows, self.d = rows, int(d)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.rows.shape[0], self.d)

    def __getitem__(self, idx: Array) -> Array:
        return self.rows[idx][..., :self.d]

    def tree_flatten(self):
        return (self.rows,), self.d

    @classmethod
    def tree_unflatten(cls, d, children):
        return cls(children[0], d)


@jax.jit
def pad_lanes(x: Array) -> PaddedRows:
    """The lane-padded copy of x (N, D), as ``PaddedRows``."""
    d = x.shape[1]
    return PaddedRows(jnp.pad(x, ((0, 0), (0, -(-d // LANES) * LANES - d))),
                      d)


# ---------------------------------------------------------------------------
# Algorithms 1 & 2 on device-resident data.
# ---------------------------------------------------------------------------

def _step(cfg: DSEKLConfig, state: DSEKLState, x: Array, y: Array,
          idx_i: Array, idx_j: Array, pc: PrecondBlock = None,
          j_union: Optional[int] = None) -> DSEKLState:
    """Gather, train pass, update for ONE I-batch against a flat J index
    (Alg. 1's expansion block or Alg. 2's worker union) — the in-memory
    wrapper over the block-parametrized core.  x (N, D) or its
    ``PaddedRows``."""
    n = x.shape[0]
    with jax.named_scope("dsekl.gather"):
        xi, yi = x[idx_i], y[idx_i]
        xj, aj = x[idx_j], state.alpha[idx_j]
    with jax.named_scope("dsekl.train_pass"):
        g, delta = grad_block(cfg, xi, yi, xj, aj, scale_n(cfg, n), pc,
                              j_union)
    with jax.named_scope("dsekl.update"):
        return apply_update(cfg, state, idx_j, g,
                            None if pc is None else pc.indices, delta)


def step_serial(cfg: DSEKLConfig, state: DSEKLState, x: Array, y: Array,
                key: Array, pc: PrecondBlock = None) -> DSEKLState:
    """One Alg.-1 iteration: draw I and J uniformly with replacement, then
    one block step.  x (N, D) or its ``PaddedRows``, y (N,).  With
    ``pc=None`` (the default) no EigenPro term is traced; a
    ``PrecondBlock`` adds the correction after the dual pass.
    """
    n = x.shape[0]
    with jax.named_scope("dsekl.sample"):
        ki, kj = jax.random.split(key)
        idx_i = sampler.sample_uniform(ki, n, cfg.n_grad)
        idx_j = sampler.sample_uniform(kj, n, cfg.n_expand)
    return _step(cfg, state, x, y, idx_i, idx_j, pc)


def epoch_parallel(cfg: DSEKLConfig, state: DSEKLState, x: Array, y: Array,
                   key: Array, pc: PrecondBlock = None) -> DSEKLState:
    """One epoch of Alg. 2: without-replacement batches, scan over I-batches.

    The number of I-batches is floor(N / n_grad); each consumes K = n_workers
    expansion batches of size n_expand, cycled without replacement, as one
    flat J union.
    """
    n = x.shape[0]
    state = state._replace(epoch=state.epoch + 1)
    with jax.named_scope("dsekl.sample"):
        ki, kj = jax.random.split(key)
        i_batches = sampler.epoch_batches(ki, n, cfg.n_grad)      # (Bi, i)
        j_batches = sampler.epoch_batches(kj, n, cfg.n_expand)    # (Bj, j)
        n_i = i_batches.shape[0]
        n_j = j_batches.shape[0]
        k = min(cfg.n_workers, n_j)
        # Assign K expansion batches to each I-batch, cycling through the
        # epoch's J-partition without replacement.
        assign = (jnp.arange(n_i)[:, None] * k
                  + jnp.arange(k)[None, :]) % n_j

    def body(st, ib_and_assign):
        idx_i, a = ib_and_assign
        with jax.named_scope("dsekl.sample"):
            idx_j = j_batches[a].reshape(-1)                      # (K*j,)
        return _step(cfg, st, x, y, idx_i, idx_j, pc,
                     cfg.n_workers * cfg.n_expand), ()

    state, _ = jax.lax.scan(body, state, (i_batches, assign))
    return state


# ---------------------------------------------------------------------------
# Prediction — empirical kernel map over any expansion set.
# ---------------------------------------------------------------------------

def decision_function(cfg: DSEKLConfig, alpha: Array, x_train: Array,
                      x_test: Array, chunk: int = 4096,
                      method: str = "stream") -> Array:
    """f(x_test) = K(x_test, x_train) @ alpha, chunked over the train set.

    ``method="stream"`` (default): one jitted ``lax.scan`` over fixed
    ``chunk``-row tiles of the train set (``kops.kernel_matvec_tiled``) —
    compiles once per shape, peak kernel-block memory O(|test| * chunk).
    ``method="ref"``: the original untraced Python chunk loop
    (``decision_function_ref``), kept as the oracle the engine and the
    streaming path are tested against.
    """
    if method == "ref":
        return decision_function_ref(cfg, alpha, x_train, x_test, chunk)
    if method != "stream":
        raise ValueError(f"unknown method {method!r}; use 'stream' or 'ref'")
    return kops.kernel_matvec_tiled(
        x_test, x_train, alpha, kernel_name=cfg.kernel,
        kernel_params=cfg.kernel_params, z_block=chunk, impl=cfg.impl)


def _pad_chunk(xs: Array, al: Array, chunk: int) -> Tuple[Array, Array]:
    """Zero-pad a ragged final chunk up to the full chunk shape.

    Exact: the padded alpha entries are zero, so the padded rows
    contribute 0.0 * k(x, 0) == +0.0 to every decision value.  Keeps the
    per-chunk matvec at ONE compiled shape instead of retracing once per
    distinct tail size.
    """
    pad = chunk - xs.shape[0]
    xs = jnp.concatenate([xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)])
    al = jnp.concatenate([al, jnp.zeros((pad,), al.dtype)])
    return xs, al


def decision_function_ref(cfg: DSEKLConfig, alpha: Array, x_train: Array,
                          x_test: Array, chunk: int = 4096) -> Array:
    """The pre-engine chunk loop, bit-identical to the original
    ``decision_function``: a Python loop of per-chunk jitted matvecs (one
    dispatch per chunk).  A ragged final chunk is zero-padded to the full
    chunk shape (exact — zero alpha nullifies the padded rows) so the
    loop compiles ONE matvec shape, not one per distinct tail size."""
    n = x_train.shape[0]
    out = jnp.zeros((x_test.shape[0],), jnp.float32)
    for start in range(0, n, chunk):
        xs = x_train[start:start + chunk]
        al = alpha[start:start + chunk]
        if xs.shape[0] < chunk and n > chunk:
            xs, al = _pad_chunk(xs, al, chunk)
        out = out + kops.kernel_matvec(
            x_test, xs, al, kernel_name=cfg.kernel,
            kernel_params=cfg.kernel_params, impl=cfg.impl)
    return out


def decision_function_source(cfg: DSEKLConfig, alpha: Array, source,
                             x_test: Array, chunk: int = 4096) -> Array:
    """f(x_test) streamed from a host-resident ``DataSource`` — the
    out-of-core sibling of ``decision_function``: the train set never
    becomes device-resident; each ``chunk``-row slice is gathered from the
    source (numpy / np.memmap) and consumed by one tiled matvec.  Peak
    device memory is O(|test| * chunk) plus one chunk of rows."""
    n = source.n
    out = jnp.zeros((x_test.shape[0],), jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32)
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        xs = jnp.asarray(source.gather_x(slice(start, stop)))
        al = alpha[start:stop]
        if xs.shape[0] < chunk and n > chunk:
            # Pad the ragged tail to the full chunk shape (exact — zero
            # alpha nullifies the padded rows) so the streamed eval
            # compiles ONE matvec shape per dataset, not one per tail.
            xs, al = _pad_chunk(xs, al, chunk)
        out = out + kops.kernel_matvec(
            x_test, xs, al, kernel_name=cfg.kernel,
            kernel_params=cfg.kernel_params, impl=cfg.impl)
    return out


def predict_labels(f: Array) -> Array:
    """±1 class decision: ``f >= 0`` maps to +1, else −1.

    The one decision rule shared by the solver's error metric and the
    prediction-engine examples.  ``jnp.sign`` is NOT it — sign(0) == 0
    would count f == 0 as wrong for both classes."""
    return jnp.where(f >= 0.0, 1.0, -1.0)


def support_vectors(alpha: Array, tol: float = 1e-8) -> Array:
    """Indices with non-negligible dual weight (truncation as in §5).

    Found on the host from one O(N) copy of alpha: the count is
    data-dependent, so a device ``nonzero`` cannot be compiled ahead and
    runs eagerly at every build."""
    keep = np.abs(np.asarray(alpha)) > tol
    return jnp.asarray(np.flatnonzero(keep), jnp.int32)


def truncate(alpha: Array, x_train: Array, tol: float = 1e-8
             ) -> Tuple[Array, Array]:
    """Compact the model to its support vectors for fast prediction."""
    sv = support_vectors(alpha, tol)
    return alpha[sv], x_train[sv]
