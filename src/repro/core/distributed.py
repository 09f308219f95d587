"""Distributed DSEKL on a 2-D (data x model) mesh — the paper's §5 ask.

Redundant data distribution scheme (DESIGN.md §2): device (d, m) holds

  * gradient rows  X^(d): the data sharded over the ``data`` axis, and
  * expansion rows X^(m): the SAME data sharded over the ``model`` axis,
  * the alpha/accum shard for its expansion rows (replicated over ``data``).

Each step, device (d, m) evaluates the kernel block K_{I_d, J_m}; the mesh
jointly covers an (|data|*I) x (|model|*J) block of the full kernel matrix —
off-block-diagonal coverage by construction, unlike per-worker block-diagonal
schemes.  Communication per step is exactly two reductions, independent of
N and D:

  * psum over ``model`` of the partial decision values  (I * 4 bytes),
  * psum over ``data``  of the expansion-shard gradient  (J * 4 bytes).

This is the low-communication distributed variant the paper's conclusion
calls for.  Semantics match Algorithm 2 (jointly-evaluated kernel map +
AdaGrad dampening); ``simulate_step`` reproduces the math on one device so
tests can assert exact agreement.  The rate is the serial plan's: the
state carries the epoch the step runs in, so ``inv_epoch`` steps at
lr0 / epoch and ``inv_t`` at lr0 / t.

With ``cfg.stream_row_block > 0`` the fused ref-path step streams: K_{I,J}
is consumed in (row_block, |J|) tiles with the model-axis psum completed per
row block (DESIGN.md §6), so peak kernel-block memory is O(row_block * |J|)
and |I| can grow without materializing the local block.  Same math, same
two-reduction communication volume (the psum is split into |I|/row_block
smaller ones).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dsekl, losses as losses_lib, sampler
from repro.core.dsekl import DSEKLConfig
from repro.distributed import compression
from repro.kernels.dsekl import ops as kops

Array = jax.Array


class ShardedDSEKLState(NamedTuple):
    alpha: Array    # (N,) sharded over 'model'
    accum: Array    # (N,) sharded over 'model'
    step: Array     # () replicated
    # () replicated: the epoch the step runs in, which ``inv_epoch``'s rate
    # reads (as ``DSEKLState.epoch`` does for the serial plan).  The step
    # passes it through; the driver of the epochs sets it (``MeshPlan``).
    epoch: Array


def _shard_block_grad_v(cfg: DSEKLConfig, n_global: int, xi: Array,
                        yi: Array, xj: Array, aj: Array, key: Array,
                        *, data_axis: str, model_axis: str
                        ) -> Tuple[Array, Array]:
    """``_shard_block_grad``'s body, also returning this data shard's loss
    gradient v (every branch computes it on the way to g — callers that
    discard it trace to the identical program).  The preconditioned mesh
    step needs v for the EigenPro correction term."""
    loss = losses_lib.get_loss(cfg.loss)
    # The model-axis psum must complete before v exists, so the closed-form
    # dual-pass op cannot span it; the fused form here evaluates the local
    # K_{I_d,J_m} block ONCE and holds it across the reduction (vs. the
    # two-pass path, which re-evaluates it for the gradient).  Materializing
    # is sound for sampled |I| x |J| training blocks; once |I|*|J| outgrows
    # that, ``stream_row_block`` switches to the streaming dual pass: the
    # same one-evaluation contract, but K is consumed in (row_block, |J|)
    # tiles with the model-axis psum completed PER ROW BLOCK — peak
    # kernel-block memory O(row_block * |J|), never O(|I| * |J|).  The
    # pallas backends keep the never-materialize two-pass structure instead.
    ref_impl = kops.resolve_impl(cfg.impl, cfg.kernel) == "ref"
    fused = cfg.fuse_dual_pass and ref_impl
    if fused and cfg.stream_row_block > 0:
        n_model = jax.lax.psum(1, model_axis)

        def f_reduce(f_part):
            f_full = jax.lax.psum(f_part, model_axis)
            if cfg.unbiased_scaling:
                f_full = f_full / n_model
            return f_full

        f, g = dsekl.streaming_train_pass(
            cfg, xi, yi, xj, aj, n_global,
            row_block=cfg.stream_row_block, f_reduce=f_reduce)
        v = loss.grad_f(f, yi)
    elif fused:
        kb = kops.kernel_block(xi, xj, kernel_name=cfg.kernel,
                               kernel_params=cfg.kernel_params)
        f_part = kb @ aj
        if cfg.unbiased_scaling:
            f_part = f_part * (n_global / xj.shape[0])
        f = jax.lax.psum(f_part, model_axis)
        if cfg.unbiased_scaling:
            f = f / jax.lax.psum(1, model_axis)
        v = loss.grad_f(f, yi)
        g = kb.T @ v
    else:
        with jax.named_scope("dsekl.mesh.f_pass"):
            f = jax.lax.psum(dsekl._block_f(cfg, xi, xj, aj, n_global),
                             model_axis)
            if cfg.unbiased_scaling:
                f = f / jax.lax.psum(1, model_axis)
            v = loss.grad_f(f, yi)
        with jax.named_scope("dsekl.mesh.g_pass"):
            # Data-dependent part only; aggregate over every data shard's
            # I-batch, then add the regularizer ONCE (not once per data
            # shard).
            g = dsekl._block_grad(cfg.replace(lam=0.0), xi, xj, aj, v)
    with jax.named_scope("dsekl.mesh.psum"):
        if cfg.compress_bits:
            g = compression.compressed_psum(
                g, data_axis, jax.random.fold_in(key, 2),
                bits=cfg.compress_bits)
        else:
            g = jax.lax.psum(g, data_axis)
    return g + cfg.lam * aj, v


def _shard_block_grad(cfg: DSEKLConfig, n_global: int, xi: Array, yi: Array,
                      xj: Array, aj: Array, key: Array,
                      *, data_axis: str, model_axis: str) -> Array:
    """The per-device dual gradient for ONE gathered (xi, yi, xj, aj) block
    — the mesh analogue of ``dsekl.grad_block``, shared by the sampling
    step (``_local_step``) and the block-parametrized step fed by host
    sources (``make_distributed_block_step``).  Completes both reductions:
    the model-axis psum of the partial decision values and the data-axis
    psum of the gradient, then adds the regularizer ONCE."""
    g, _ = _shard_block_grad_v(cfg, n_global, xi, yi, xj, aj, key,
                               data_axis=data_axis, model_axis=model_axis)
    return g


def _apply_shard_update(cfg: DSEKLConfig, alpha: Array, accum: Array,
                        step: Array, epoch: Array, idx_j: Array, g: Array
                        ) -> Tuple[Array, Array, Array]:
    """Scatter one shard gradient into the local alpha/accum shard:
    ``dsekl.apply_update`` on the shard, so the rate is ``dsekl._lr`` of
    the step count t and the ``epoch`` the step runs in, as in the serial
    plan (lr0 / t under ``inv_t``, lr0 / epoch under ``inv_epoch``), and
    the AdaGrad accumulator is touched ONLY under ``schedule="adagrad"``.
    """
    st = dsekl.apply_update(cfg, dsekl.DSEKLState(alpha, accum, step, epoch),
                            idx_j, g)
    return st.alpha, st.accum, st.step


def _local_step(cfg: DSEKLConfig, n_global: int,
                x_grad: Array, y_grad: Array, x_exp: Array,
                alpha: Array, accum: Array, step: Array, epoch: Array,
                key: Array, *, data_axis: str, model_axis: str
                ) -> Tuple[Array, Array, Array]:
    """Per-device body (runs under shard_map): sample and gather, then
    ``_local_block_step``."""
    d_id = jax.lax.axis_index(data_axis)
    m_id = jax.lax.axis_index(model_axis)
    # I decorrelated per data-shard; J per model-shard (same across the
    # data axis so every replica of an alpha shard applies the same update).
    k_i = jax.random.fold_in(jax.random.fold_in(key, 0), d_id)
    k_j = jax.random.fold_in(jax.random.fold_in(key, 1), m_id)
    idx_i = sampler.sample_uniform(k_i, x_grad.shape[0], cfg.n_grad)
    idx_j = sampler.sample_uniform(k_j, x_exp.shape[0], cfg.n_expand)
    return _local_block_step(cfg, n_global, x_grad[idx_i], y_grad[idx_i],
                             x_exp[idx_j], idx_j, alpha, accum, step, epoch,
                             key, data_axis=data_axis, model_axis=model_axis)


def _local_block_step(cfg: DSEKLConfig, n_global: int,
                      xi: Array, yi: Array, xj: Array, idx_j: Array,
                      alpha: Array, accum: Array, step: Array, epoch: Array,
                      key: Array, pc: dsekl.PrecondBlock = None, *,
                      data_axis: str, model_axis: str
                      ) -> Tuple[Array, Array, Array]:
    """Per-device body for PRE-GATHERED blocks (the out-of-core mesh step):
    the data plane supplies this shard's sampled gradient rows (xi, yi),
    this model shard's expansion rows (xj) and their LOCAL indices (idx_j);
    only alpha/accum and the block math live on device.

    ``pc`` (replicated, GLOBAL indices) adds the EigenPro correction
    (DESIGN.md §10): ``dsekl.precond_correction`` with the data-axis psum
    completing c = K_{P, I_all} @ v_all = psum_data K_{P, I_d} @ v_d and
    the step's J-union, every model shard's n_expand block.  delta is
    identical on every device (v is built from the model-axis-psummed f),
    so each model shard scatters the slice of it that it owns: global ids
    are mapped to shard-local ones, with non-owned entries pushed out of
    bounds — JAX drops out-of-bounds scatter updates, so no masking pass
    is needed.  It is applied after the main update at the step's scalar
    rate, as ``dsekl.apply_update`` applies it."""
    aj = alpha[idx_j]
    if pc is None:
        g = _shard_block_grad(cfg, n_global, xi, yi, xj, aj, key,
                              data_axis=data_axis, model_axis=model_axis)
    else:
        g, v = _shard_block_grad_v(cfg, n_global, xi, yi, xj, aj, key,
                                   data_axis=data_axis, model_axis=model_axis)
        delta = dsekl.precond_correction(
            cfg, xi, v, pc, xj.shape[0] * jax.lax.axis_size(model_axis),
            c_reduce=functools.partial(jax.lax.psum, axis_name=data_axis))
    with jax.named_scope("dsekl.mesh.update"):
        alpha, accum, t = _apply_shard_update(cfg, alpha, accum, step, epoch,
                                              idx_j, g)
        if pc is not None:
            rows_m = alpha.shape[0]
            local = pc.indices - jax.lax.axis_index(model_axis) * rows_m
            safe = jnp.where((local >= 0) & (local < rows_m), local, rows_m)
            lr = dsekl._lr(cfg, dsekl.DSEKLState(alpha, accum, t, epoch))
            alpha = alpha.at[safe].add(lr * delta)  # OOB updates are dropped
    return alpha, accum, t


def make_distributed_step(cfg: DSEKLConfig, mesh: Mesh, n_global: int,
                          data_axis: str = "data", model_axis: str = "model"):
    """Build the jitted shard_map step.

    Arguments of the returned fn (already device-put with these shardings):
      x_grad (N, D) P(data), y_grad (N,) P(data),
      x_exp (N, D) P(model), state.alpha/accum (N,) P(model), key replicated.
    """
    body = functools.partial(_local_step, cfg, n_global,
                             data_axis=data_axis, model_axis=model_axis)
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axis, None), P(data_axis), P(model_axis, None),
                  P(model_axis), P(model_axis), P(), P(), P()),
        out_specs=(P(model_axis), P(model_axis), P()),
        check_vma=False,
    )

    @jax.jit
    def step(x_grad, y_grad, x_exp, state: ShardedDSEKLState, key):
        alpha, accum, t = mapped(x_grad, y_grad, x_exp, state.alpha,
                                 state.accum, state.step, state.epoch, key)
        return ShardedDSEKLState(alpha, accum, t, state.epoch)

    return step


@functools.lru_cache(maxsize=8)
def make_distributed_block_step(cfg: DSEKLConfig, mesh: Mesh, n_global: int,
                                data_axis: str = "data",
                                model_axis: str = "model",
                                precondition: bool = False):
    """The block-parametrized mesh step: the jitted shard_map over
    PRE-GATHERED blocks (the out-of-core data plane, DESIGN.md §8).

    The full dataset never reaches the device — each data-axis shard owns a
    host-resident ``HostSource`` over its local row range only (see
    ``repro.data.HostSource.split``), the per-step sampled rows are gathered
    host-side (``gather_mesh_blocks``) and arrive as:

      xi (n_data * n_grad, D)   P(data)  — per-data-shard gradient rows
      yi (n_data * n_grad,)     P(data)
      xj (n_model * n_expand, D) P(model) — per-model-shard expansion rows
      idx_j (n_model * n_expand,) P(model) — LOCAL indices into the shard's
                                             alpha/accum slice

    Device arrays and compiled shapes depend on (n_grad, n_expand, D) and
    the O(N) alpha/accum shards only.  Same math, same two-reduction
    communication as ``make_distributed_step``.

    With ``precondition=True`` the returned step takes a trailing
    ``dsekl.PrecondBlock`` (replicated; GLOBAL indices) and applies the
    EigenPro correction — one extra (m,)-float data-axis psum per step.

    Built once per argument set: every ``MeshPlan`` (one per fit) on the
    same mesh, config and N gets the same jitted step, so a fit after the
    first traces and compiles nothing.
    """
    xi_sh = NamedSharding(mesh, P(data_axis, None))
    yi_sh = NamedSharding(mesh, P(data_axis))
    xj_sh = NamedSharding(mesh, P(model_axis, None))
    ij_sh = NamedSharding(mesh, P(model_axis))
    rep_sh = NamedSharding(mesh, P())
    shardings = (xi_sh, yi_sh, xj_sh, ij_sh)

    def _put(a, sh):
        # Accept PRE-PLACED blocks: the mesh prefetcher device_puts the
        # gathered blocks straight to these shardings from its worker
        # thread, so the consumer-side put must be a no-op — re-putting
        # an already-placed array would serialize the transfer back onto
        # the critical path the overlap just took it off.
        if getattr(a, "sharding", None) == sh:
            return a
        return jax.device_put(a, sh)

    body = functools.partial(_local_block_step, cfg, n_global,
                             data_axis=data_axis, model_axis=model_axis)
    in_specs = (P(data_axis, None), P(data_axis), P(model_axis, None),
                P(model_axis), P(model_axis), P(model_axis), P(), P(), P())
    if precondition:
        in_specs += (P(),)                      # the replicated PrecondBlock
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(P(model_axis), P(model_axis), P()),
        check_vma=False,
    )

    @jax.jit
    def step(xi, yi, xj, idx_j, state: ShardedDSEKLState, key,
             pc: dsekl.PrecondBlock = None):
        alpha, accum, t = mapped(xi, yi, xj, idx_j, state.alpha,
                                 state.accum, state.step, state.epoch, key,
                                 *(() if pc is None else (pc,)))
        return ShardedDSEKLState(alpha, accum, t, state.epoch)

    def step_host(xi, yi, xj, idx_j, state: ShardedDSEKLState, key,
                  pc: dsekl.PrecondBlock = None):
        """Host-array front door: device_put the gathered blocks (and the
        preconditioner, replicated) straight to their shardings (one
        host-to-shards transfer each) — or pass already-placed device
        blocks through untouched — then run the compiled step."""
        if pc is not None:
            pc = jax.tree.map(lambda a: _put(a, rep_sh), pc)
        return step(_put(xi, xi_sh), _put(yi, yi_sh), _put(xj, xj_sh),
                    _put(idx_j, ij_sh), state, key, pc)

    step_host.jitted = step
    step_host.shardings = shardings
    return step_host


def gather_mesh_blocks_from(idx_i_np, idx_j_np, data_sources, model_sources):
    """Pure per-shard gather of ONE step's PRECOMPUTED index plan.

    ``idx_i_np (n_data, n_grad)`` / ``idx_j_np (n_model, n_expand)`` are
    one step's rows of a host-side ``sampler.mesh_epoch_plan`` (numpy,
    local indices).  Splitting the gather from the plan is what lets the
    mesh prefetcher run it on a worker thread — no jax dispatch, no
    host/device sync, just row copies out of the per-shard sources.
    Returns host arrays ``(xi, yi, xj, idx_j_local)`` shaped for
    ``make_distributed_block_step``.
    """
    import numpy as np

    gi = [src.gather(idx_i_np[d]) for d, src in enumerate(data_sources)]
    xi = np.concatenate([g[0] for g in gi])
    yi = np.concatenate([g[1] for g in gi])
    xj = np.concatenate([src.gather_x(idx_j_np[m])
                         for m, src in enumerate(model_sources)])
    return xi, yi, xj, idx_j_np.reshape(-1)


def gather_mesh_blocks(cfg: DSEKLConfig, key: Array, data_sources,
                       model_sources):
    """Host-side gather for ONE distributed block step (plan + gather).

    ``data_sources[d]`` / ``model_sources[m]`` are the per-shard local-range
    ``HostSource`` views (``source.split(n_shards)``).  Index plans use the
    identical per-shard ``fold_in`` scheme as the device-sampling step
    (``sampler.mesh_step_plan``), so the block step consumes the very same
    rows ``make_distributed_step`` would sample on device.

    Note the per-step host sync this pays (``np.asarray`` blocks on the
    jitted plan): the trainer's ``MeshPlan`` instead plans a whole epoch
    up front (``sampler.mesh_epoch_plan``) and gathers through
    ``gather_mesh_blocks_from`` — this convenience wrapper remains for
    single-step callers and as the reference the epoch path must match.
    """
    import numpy as np

    idx_i, idx_j = sampler.mesh_step_plan(
        key, cfg.n_grad, cfg.n_expand,
        tuple(s.n for s in data_sources), tuple(s.n for s in model_sources))
    return gather_mesh_blocks_from(np.asarray(idx_i), np.asarray(idx_j),
                                   data_sources, model_sources)


def make_mesh_eval(cfg: DSEKLConfig, mesh: Mesh, model_axis: str = "model",
                   chunk: int = 2048):
    """Model-axis-psum'd validation decision function for a mesh fit.

    Returns ``eval_fn(alpha, model_sources, x_test) -> f (|test|,)``:
    ``alpha`` stays sharded P(model); each model shard contributes the
    partial decision values of its LOCAL expansion rows, streamed
    ``chunk`` rows at a time from its host-resident ``HostSource`` view
    (the dataset never becomes device-resident), and the shards'
    partials are combined by ONE |test|-float psum per chunk — the same
    reduction shape as the training step's f psum.  The alpha chunks are
    sliced host-side from one O(N) device-to-host gather per eval (the
    state is O(N) by design; it is the (N, D) rows that must stream).
    """
    import numpy as np

    def body(xq, xs, al):
        f_part = kops.kernel_matvec(xq, xs, al, kernel_name=cfg.kernel,
                                    kernel_params=cfg.kernel_params,
                                    impl=cfg.impl)
        return jax.lax.psum(f_part, model_axis)

    mapped = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(model_axis, None), P(model_axis)),
        out_specs=P(), check_vma=False))
    xs_sh = NamedSharding(mesh, P(model_axis, None))
    al_sh = NamedSharding(mesh, P(model_axis))

    def eval_fn(alpha: Array, model_sources, x_test: Array) -> Array:
        rows = model_sources[0].n               # equal by the split contract
        alpha_host = np.asarray(alpha)
        out = jnp.zeros((x_test.shape[0],), jnp.float32)
        for start in range(0, rows, chunk):
            stop = min(start + chunk, rows)
            xs = np.concatenate([s.gather_x(slice(start, stop))
                                 for s in model_sources])
            al = np.concatenate([alpha_host[m * rows + start:
                                            m * rows + stop]
                                 for m in range(len(model_sources))])
            out = out + mapped(x_test, jax.device_put(xs, xs_sh),
                               jax.device_put(al, al_sh))
        return out

    return eval_fn


def shard_inputs(mesh: Mesh, x: Array, y: Array,
                 data_axis: str = "data", model_axis: str = "model"):
    """Place the redundant distribution: X over data AND over model."""
    x_grad = jax.device_put(x, NamedSharding(mesh, P(data_axis, None)))
    y_grad = jax.device_put(y, NamedSharding(mesh, P(data_axis)))
    x_exp = jax.device_put(x, NamedSharding(mesh, P(model_axis, None)))
    return x_grad, y_grad, x_exp


def init_sharded_state(mesh: Mesh, n: int, model_axis: str = "model"
                       ) -> ShardedDSEKLState:
    sh = NamedSharding(mesh, P(model_axis))
    return ShardedDSEKLState(
        alpha=jax.device_put(jnp.zeros((n,), jnp.float32), sh),
        accum=jax.device_put(jnp.ones((n,), jnp.float32), sh),
        step=jnp.zeros((), jnp.int32),
        epoch=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Single-device simulation (test oracle for the mesh step).
# ---------------------------------------------------------------------------

def simulate_step(cfg: DSEKLConfig, n_data_shards: int, n_model_shards: int,
                  x: Array, y: Array, alpha: Array, accum: Array,
                  step: Array, key: Array,
                  pc=None, epoch=0) -> Tuple[Array, Array, Array]:
    """Exactly reproduce the mesh step's math on one device (loops over
    shards).  Used by tests to validate the shard_map implementation.
    ``pc`` (a ``dsekl.PrecondBlock``) reproduces the preconditioned step:
    the per-model-shard out-of-bounds-dropped scatters of the replicated
    correction compose to ONE global scatter at ``pc.indices``.
    ``epoch`` is the epoch the step runs in (``ShardedDSEKLState.epoch``):
    ``inv_epoch`` steps at lr0 / epoch; 0, a fresh state's, reads lr0."""
    n = x.shape[0]
    loss = losses_lib.get_loss(cfg.loss)
    rows_d = n // n_data_shards
    rows_m = n // n_model_shards

    # Sample every shard's indices with the same fold_in scheme.
    idx_i = []
    for d in range(n_data_shards):
        k_i = jax.random.fold_in(jax.random.fold_in(key, 0), d)
        idx_i.append(sampler.sample_uniform(k_i, rows_d, cfg.n_grad) + d * rows_d)
    idx_j = []
    for m in range(n_model_shards):
        k_j = jax.random.fold_in(jax.random.fold_in(key, 1), m)
        idx_j.append(sampler.sample_uniform(k_j, rows_m, cfg.n_expand) + m * rows_m)

    # f per data shard: psum over model == sum over all J shards.
    vs = []
    for d in range(n_data_shards):
        f = jnp.zeros((cfg.n_grad,), jnp.float32)
        for m in range(n_model_shards):
            f = f + dsekl._block_f(cfg, x[idx_i[d]], x[idx_j[m]],
                                   alpha[idx_j[m]], n)
        if cfg.unbiased_scaling:
            f = f / n_model_shards
        vs.append(loss.grad_f(f, y[idx_i[d]]))

    t = step + 1
    new_alpha, new_accum = alpha, accum
    lr = dsekl._lr(cfg, dsekl.DSEKLState(alpha, accum, t,
                                         jnp.asarray(epoch, jnp.int32)))
    for m in range(n_model_shards):
        aj = alpha[idx_j[m]]
        g = jnp.zeros((cfg.n_expand,), jnp.float32)
        cfg0 = cfg.replace(lam=0.0)
        for d in range(n_data_shards):
            g = g + dsekl._block_grad(cfg0, x[idx_i[d]], x[idx_j[m]], aj, vs[d])
        g = g + cfg.lam * aj  # regularizer added once, as on the mesh
        if cfg.schedule == "adagrad":
            new_accum = new_accum.at[idx_j[m]].add(g * g)
            damp = jax.lax.rsqrt(new_accum[idx_j[m]])
            new_alpha = new_alpha.at[idx_j[m]].add(-lr * damp * g)
        else:
            # Accum untouched off-adagrad, matching _apply_shard_update.
            new_alpha = new_alpha.at[idx_j[m]].add(-lr * g)
    if pc is not None:
        c = jnp.zeros((pc.rows.shape[0],), jnp.float32)
        for d in range(n_data_shards):
            c = c + kops.kernel_vecmat(x[idx_i[d]], pc.rows, vs[d],
                                       kernel_name=cfg.kernel,
                                       kernel_params=cfg.kernel_params,
                                       impl=cfg.impl)
        j_union = n_model_shards * cfg.n_expand
        delta = pc.vectors @ ((float(j_union) * pc.damping)
                              * (pc.vectors.T @ c))
        new_alpha = new_alpha.at[pc.indices].add(lr * delta)
    return new_alpha, new_accum, t
