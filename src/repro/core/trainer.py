"""Unified execution-backend trainer (DESIGN.md §9).

The paper's pitch is that doubly stochastic EKM training is
"straightforward to implement, in particular in parallel execution
settings" — so data placement and parallelism should be a *backend
choice*, not four hand-rolled epoch drivers.  This module defines the
``ExecutionPlan`` interface one ``fit`` loop drives:

  * ``plan_epoch(key)``  — queue the host-side sampling plan for the
    epoch keyed by ``key`` (a no-op for fully-jitted backends, the
    one-epoch-AHEAD plan feed for the hosted prefetcher);
  * ``run_epoch(state, key) -> state`` — execute one epoch;
  * ``eval_error(state, x_val, y_val)`` — the backend's validation eval
    (cached engine / jitted / streamed-from-source / mesh-psum'd).

Five concrete backends:

  * ``SerialPlan``   — Algorithm 1, device-resident data, one jitted scan;
  * ``ParallelPlan`` — Algorithm 2, device-resident data, one jitted scan;
  * ``HostedPlan``   — either algorithm over a host-resident
    ``DataSource``: host-side epoch plans replayed through the
    N-independent block cores, with ONE cross-epoch ``BlockPrefetcher``
    whose worker thread and staging buffers survive epoch boundaries
    (plans are generated one epoch ahead, so the worker streams straight
    across the edge instead of draining);
  * ``MeshPlan``     — the 2-D (data x model) mesh driven end to end:
    per-shard ``HostSource`` views (``source.split``), whole-epoch mesh
    index plans (``sampler.mesh_epoch_plan`` — the ``fold_in`` sampling
    scheme, one dispatch per epoch), ONE cross-epoch ``MeshPrefetcher``
    whose worker gathers the per-shard blocks and ``device_put``s them
    straight to the block-parametrized shard_map step's shardings
    (``make_distributed_block_step``) while the device runs the previous
    step, and a model-axis-psum'd eval;
  * ``BCDPlan``      — block coordinate descent rounds (``core/bcd.py``,
    DESIGN.md §14): exact |J| x |J| block solves over the streamed
    ``K_{.,J}``, serial or mesh, square loss only.

The equivalence contract (``tests/test_trainer_matrix.py``): driven from
one PRNG key, every backend is bit-identical to its reference
trajectory — Serial/Parallel to the in-memory jitted epochs, Hosted to
the in-memory path (same plan replay), Mesh to the device-sampling
``make_distributed_step`` loop — and a checkpoint-interrupted + resumed
``fit`` is bit-identical to an uninterrupted one on ALL backends.

Checkpoint/resume: ``fit_loop`` snapshots ``(DSEKLState, sampler key,
epoch counter, history)`` through ``checkpoint.CheckpointManager``
(atomic, checksummed, async); restore re-places every leaf with the
backend's shardings, so a serial checkpoint can resume onto a mesh and
vice versa.  The per-epoch key chain is ``key, sub = split(key)`` —
exactly the legacy driver's — and the snapshot stores the pre-epoch
carry, so a resumed run replays the identical sub-key sequence.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core import dsekl, sampler
from repro.core.dsekl import DSEKLConfig, DSEKLState
from repro.data.source import (BlockPrefetcher, MeshPrefetcher, SyncGather,
                               SyncMeshGather)

Array = jax.Array

EXECUTIONS = ("auto", "serial", "parallel", "hosted", "mesh", "bcd")


@dataclasses.dataclass
class FitResult:
    state: DSEKLState
    history: List[Dict[str, Any]]
    converged: bool
    epochs_run: int
    # cache_info() of the validation prediction engine (None when no
    # validation set was given or ``eval_cache=False``).
    val_cache: Optional[Dict[str, Any]] = None
    # Loader counters of a host-source / mesh fit (gather_s / wait_s /
    # steps, accumulated across ALL epochs; None for the in-memory path).
    loader: Optional[Dict[str, float]] = None
    # Why the loop ended: "converged" (paper stopping rule), "hook"
    # (an ``on_epoch`` hook requested the stop), or "epochs" (budget).
    stop_reason: str = "epochs"
    # Uniform convergence reporting across solvers (stochastic epochs and
    # BCD rounds alike): the first epoch whose |dalpha| dropped below
    # ``tol`` (None if it never did) and the last epoch's |dalpha| —
    # comparable head-to-head without reaching into ``history``.
    epochs_to_tol: Optional[int] = None
    final_residual: float = 0.0
    # Width of the rows an in-memory fit's epochs gather: D when X was
    # used as given, 128 * ceil(D / 128) when the plan gathered from a
    # lane-padded copy (``_gather_matrix``).  None for the other plans.
    row_width: Optional[int] = None


# ---------------------------------------------------------------------------
# Shared epoch/eval machinery.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def _epoch_serial(cfg: DSEKLConfig, state: DSEKLState, x: Array, y: Array,
                  key: Array,
                  pc: Optional[dsekl.PrecondBlock] = None) -> DSEKLState:
    steps = max(x.shape[0] // cfg.n_grad, 1)
    keys = jax.random.split(key, steps)
    state = state._replace(epoch=state.epoch + 1)

    def body(st, k):
        return dsekl.step_serial(cfg, st, x, y, k, pc), ()

    state, _ = jax.lax.scan(body, state, keys)
    return state


_epoch_parallel = jax.jit(dsekl.epoch_parallel, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _apply_then_gather(cfg: DSEKLConfig, state: DSEKLState, idx_j: Array,
                       g: Array, idx_next: Array,
                       idx_p: Optional[Array] = None,
                       delta: Optional[Array] = None):
    """Fold the O(N) scatter of step t (``dsekl.apply_update``, with the
    EigenPro correction when ``delta`` is given) and the alpha gather of
    step t+1 into ONE dispatch — the only two N-shaped ops of a hosted
    step."""
    state = dsekl.apply_update(cfg, state, idx_j, g, idx_p, delta)
    return state, state.alpha[idx_next]


@jax.jit
def _truncate_smallest(alpha: Array, frac: float) -> Array:
    """Zero the smallest ``frac`` of non-zero |alpha| mass (budget step).

    Rank-based: drop exactly the k lowest-|alpha| non-zero entries (ties
    broken by position — argsort is stable).  A threshold comparison
    (``mag <= thresh``) zeroes EVERY tied entry, so a uniform-|alpha|
    model would be truncated wholesale instead of by ``frac``.
    """
    mag = jnp.abs(alpha)
    nz = mag > 0
    k = (nz.sum() * frac).astype(jnp.int32)
    order = jnp.argsort(jnp.where(nz, mag, jnp.inf))   # non-zeros first
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    drop = nz & (ranks < k)
    return jnp.where(drop, 0.0, alpha)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _error(cfg: DSEKLConfig, alpha: Array, x_train: Array, x: Array,
           y: Array) -> Array:
    f = dsekl.decision_function(cfg, alpha, x_train, x)
    # Decide via f >= 0 mapped to ±1 (dsekl.predict_labels), consistently
    # with the prediction-engine examples — sign(f) counts f == 0 as wrong
    # for BOTH classes.
    return jnp.mean((dsekl.predict_labels(f) != y).astype(jnp.float32))


def _error_source(cfg: DSEKLConfig, alpha: Array, source, x: Array,
                  y: Array) -> float:
    """Validation error with the train set streamed from a host source."""
    f = dsekl.decision_function_source(cfg, alpha, source, x)
    return float(jnp.mean((dsekl.predict_labels(f) != y).astype(jnp.float32)))


# "auto" eval_cache budget: the cached validation eval materializes the
# n_val x N kernel map (4 bytes/entry).  Above this it falls back to the
# streamed jitted ``_error`` path so large fits keep their old memory
# profile.
_EVAL_CACHE_BUDGET_BYTES = 1 << 30


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _gather_matrix(x: Array):
    """The matrix an in-memory plan's steps gather rows from: x as given,
    or its lane-padded copy (``dsekl.PaddedRows``) where a row gather from
    x would be strided, namely where x's layout puts the rows on the lanes
    (features major; where the layout cannot be read, D not a multiple of
    128 on a TPU), x sits on one device, and the copy fits in half of that
    device's free memory.  A CPU lays x out row-major: x as given."""
    if not isinstance(x, jax.Array) or len(x.devices()) != 1:
        return x
    n, d = x.shape
    width = _round_up(d, dsekl.LANES)
    if width == d:
        return x
    (device,) = x.devices()
    layout = x.format.layout                # None where it cannot be read
    strided = (layout.major_to_minor == (1, 0) if layout is not None
               else device.platform == "tpu")
    if not strided:
        return x
    stats = device.memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    if 2 * n * width * x.dtype.itemsize > free:
        return x
    return dsekl.pad_lanes(x)


def _make_val_engine(cfg: DSEKLConfig, x: Array, n_val: int):
    """Keep-all prediction engine for the validation eval path.

    ``truncate_tol=-1`` keeps every training row (so ``update_alpha`` is
    legal each epoch) and ``cache_blocks`` is sized to hold exactly the
    validation set's kernel-map tiles: epoch 1 pays the kernel evaluation,
    every later epoch's eval is cache hits — one cheap matvec per tile
    against the fresh alpha (K is alpha-independent; DESIGN.md §7).
    """
    # Lazy import: repro.serving imports repro.core at module load.
    from repro.serving.dsekl_engine import DSEKLPredictionEngine, EngineConfig

    qb = min(1024, max(64, _round_up(n_val, 64)))
    return DSEKLPredictionEngine(
        cfg, jnp.zeros((x.shape[0],), jnp.float32), x,
        engine_cfg=EngineConfig(query_block=qb, truncate_tol=-1.0,
                                cache_blocks=-(-n_val // qb)))


# ---------------------------------------------------------------------------
# The ExecutionPlan interface.
# ---------------------------------------------------------------------------

class ExecutionPlan:
    """One training backend: how epochs execute and where data lives.

    The unified ``fit_loop`` is backend-agnostic — it splits the epoch
    key chain, calls ``plan_epoch`` one epoch AHEAD (so plan-driven
    backends can prefetch across the boundary), runs ``run_epoch``,
    truncates/evaluates/snapshots, and checks convergence.  Everything
    placement-specific lives behind this interface.
    """

    name = "base"

    def __init__(self, cfg: DSEKLConfig, n: int):
        self.cfg = cfg
        self.n = int(n)

    # -- state ----------------------------------------------------------
    def init_state(self) -> DSEKLState:
        return dsekl.init_state(self.n)

    def place_state(self, flat: Dict[str, np.ndarray]) -> DSEKLState:
        """Re-place a restored flat checkpoint with this backend's
        shardings (default: single device)."""
        return DSEKLState(
            alpha=jax.device_put(jnp.asarray(flat["alpha"], jnp.float32)),
            accum=jax.device_put(jnp.asarray(flat["accum"], jnp.float32)),
            step=jnp.asarray(flat["step"], jnp.int32),
            epoch=jnp.asarray(flat["epoch"], jnp.int32))

    def snapshot_leaves(self, state: DSEKLState) -> Dict[str, np.ndarray]:
        """Extra backend-owned checkpoint leaves merged into every
        snapshot's tree (and handed back to ``place_state`` on restore).
        Default: none.  ``BCDPlan`` stores its incremental residual
        vector here so a resumed fit replays bit-for-bit."""
        return {}

    # -- epochs ---------------------------------------------------------
    def plan_epoch(self, key: Optional[Array]) -> None:
        """Queue the host-side sampling plan for the epoch keyed by
        ``key`` (idempotent; no-op for fully-jitted backends)."""

    def run_epoch(self, state: DSEKLState, key: Array) -> DSEKLState:
        raise NotImplementedError

    # -- eval / reporting -----------------------------------------------
    # ``FitResult.row_width`` (set by the in-memory plans).
    row_width: Optional[int] = None

    def eval_error(self, state: DSEKLState, x_val: Array,
                   y_val: Array) -> float:
        raise NotImplementedError

    def val_cache_info(self) -> Optional[Dict[str, Any]]:
        return None

    def loader_stats(self) -> Optional[Dict[str, float]]:
        return None

    def close(self) -> None:
        pass

    def __enter__(self) -> "ExecutionPlan":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _InMemoryPlan(ExecutionPlan):
    """Shared base of the device-resident backends: data on device,
    eval through the cached prediction engine or the jitted error.

    The epochs gather rows from ``_gather_matrix(x)``, made once per plan
    (in ``init_state``, so inside ``fit``'s set-up span) and dropped on
    ``close``; eval and the caller keep the unpadded ``x``."""

    def __init__(self, cfg: DSEKLConfig, x: Array, y: Array, *,
                 eval_cache: bool = False,
                 precond: Optional[dsekl.PrecondBlock] = None):
        super().__init__(cfg, int(x.shape[0]))
        self.x, self.y = x, y
        self.precond = precond
        self._eval_cache = bool(eval_cache)
        self._val_engine = None
        self._x_rows = None

    def _rows(self):
        if self._x_rows is None:
            self._x_rows = _gather_matrix(self.x)
            self.row_width = (self._x_rows.rows.shape[1]
                              if isinstance(self._x_rows, dsekl.PaddedRows)
                              else int(self.x.shape[1]))
        return self._x_rows

    def init_state(self) -> DSEKLState:
        self._rows()
        return super().init_state()

    def close(self) -> None:
        self._x_rows = None

    def eval_error(self, state: DSEKLState, x_val: Array,
                   y_val: Array) -> float:
        if self._eval_cache:
            if self._val_engine is None:
                self._val_engine = _make_val_engine(self.cfg, self.x,
                                                    int(x_val.shape[0]))
            self._val_engine.update_alpha(state.alpha)
            f_val = self._val_engine.predict(x_val)
            return float(jnp.mean(
                (dsekl.predict_labels(f_val) != y_val).astype(jnp.float32)))
        return float(_error(self.cfg, state.alpha, self.x, x_val, y_val))

    def val_cache_info(self) -> Optional[Dict[str, Any]]:
        return (self._val_engine.cache_info()
                if self._val_engine is not None else None)


class SerialPlan(_InMemoryPlan):
    """Algorithm 1 on device-resident data: one jitted scan per epoch."""

    name = "serial"

    def run_epoch(self, state: DSEKLState, key: Array) -> DSEKLState:
        return _epoch_serial(self.cfg, state, self._rows(), self.y, key,
                             self.precond)


class ParallelPlan(_InMemoryPlan):
    """Algorithm 2 on device-resident data: one jitted scan per epoch."""

    name = "parallel"

    def run_epoch(self, state: DSEKLState, key: Array) -> DSEKLState:
        return _epoch_parallel(self.cfg, state, self._rows(), self.y, key,
                               self.precond)


class HostedPlan(ExecutionPlan):
    """Either algorithm over a host-resident ``DataSource``.

    Epoch index plans (``sampler.epoch_plan`` / ``parallel_epoch_plan``
    — index-for-index what the jitted in-memory epochs sample) are
    queued onto ONE ``BlockPrefetcher`` that lives for the whole fit:
    ``plan_epoch`` extends the worker's plan, so when the driver plans
    epoch e+1 before running epoch e, the worker thread and its staging
    buffers stream straight across the epoch boundary (no re-spawn, no
    drain).  Each step is two dispatches: the N-independent block
    gradient core plus the fused scatter-and-next-gather.
    """

    name = "hosted"

    def __init__(self, cfg: DSEKLConfig, source, *,
                 algorithm: str = "serial", prefetch: bool = True,
                 precond: Optional[dsekl.PrecondBlock] = None):
        super().__init__(cfg, source.n)
        if algorithm not in ("serial", "parallel"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        self.source = source
        self.algorithm = algorithm
        self.prefetch = prefetch
        self.precond = precond
        self._loader = None
        # Queued epoch plans, FIFO: (key bytes, plan arrays...).
        self._queued: collections.deque = collections.deque()
        self._consumed_steps = 0

    # -- planning -------------------------------------------------------
    def _build_plan(self, key: Array):
        cfg, n = self.cfg, self.n
        if self.algorithm == "serial":
            steps = max(n // cfg.n_grad, 1)
            plan_i, plan_j = sampler.epoch_plan(key, n, cfg.n_grad,
                                                cfg.n_expand, steps)
            return np.asarray(plan_i), np.asarray(plan_j)
        i_batches, idx_jk = sampler.parallel_epoch_plan(
            key, n, cfg.n_grad, cfg.n_expand, cfg.n_workers)
        return np.asarray(i_batches), np.asarray(idx_jk)   # (Bi,K,j)

    def plan_epoch(self, key: Optional[Array]) -> None:
        if key is None:
            return
        kb = np.asarray(key).tobytes()
        if any(q[0] == kb for q in self._queued):
            return                              # already planned ahead
        plan_i, plan_j = self._build_plan(key)
        # One flat J per step (Alg. 2: the K-worker union).  Explicit flat
        # width: reshape(0, -1) is ambiguous for the empty epoch plan
        # (N < n_grad on the parallel path).
        flat_j = plan_j.reshape(plan_i.shape[0],
                                int(np.prod(plan_j.shape[1:], dtype=int)))
        if self._loader is None:
            cls = BlockPrefetcher if self.prefetch else SyncGather
            self._loader = cls(self.source, plan_i, flat_j)
        else:
            self._loader.extend(plan_i, flat_j)
        self._queued.append((kb, plan_i, flat_j))

    def _pop_plan(self, key: Array):
        kb = np.asarray(key).tobytes()
        if not self._queued:
            self.plan_epoch(key)
        elif self._queued[0][0] != kb:
            raise RuntimeError(
                "hosted epochs must be consumed in the order they were "
                "planned (the prefetcher streams one plan)")
        return self._queued.popleft()

    # -- epochs ---------------------------------------------------------
    def run_epoch(self, state: DSEKLState, key: Array) -> DSEKLState:
        _, plan_i, plan_j = self._pop_plan(key)
        state = state._replace(epoch=state.epoch + 1)
        steps = plan_i.shape[0]
        if steps == 0:
            # N < n_grad on the parallel path: the in-memory epoch scans
            # over zero batches and returns the state unchanged.
            return state
        cfg = self.cfg
        n_eff = dsekl.scale_n(cfg, self.n)
        loader = self._loader
        pc = self.precond
        idx_p = None if pc is None else pc.indices
        # The EigenPro scale of the in-memory epochs (dsekl.epoch_parallel).
        j_union = (cfg.n_workers * cfg.n_expand
                   if self.algorithm == "parallel" else None)
        aj = state.alpha[jnp.asarray(plan_j[0])]
        for t in range(steps):
            # xj: one expansion block (Alg. 1) or the K-worker union
            # (Alg. 2), in plan_j's flat order.
            xi, yi, xj = loader.get()
            g, delta = dsekl.grad_block_jit(cfg, xi, yi, xj, aj, n_eff, pc,
                                            j_union)
            state, aj = _apply_then_gather(
                cfg, state, plan_j[t], g, plan_j[min(t + 1, steps - 1)],
                idx_p, delta)
        state.alpha.block_until_ready()         # epoch-boundary sync
        self._consumed_steps += steps
        return state

    # -- eval / reporting -----------------------------------------------
    def eval_error(self, state: DSEKLState, x_val: Array,
                   y_val: Array) -> float:
        # Host-source fits stream the eval too — the dataset must not
        # become device-resident.
        return _error_source(self.cfg, state.alpha, self.source, x_val,
                             y_val)

    def loader_stats(self) -> Optional[Dict[str, float]]:
        if self._loader is None:
            return None
        st = dict(self._loader.stats())
        # Report steps CONSUMED, not planned: the driver plans one epoch
        # ahead, so on early convergence the loader holds a queued epoch
        # that never ran.
        st["steps"] = self._consumed_steps
        return st

    def close(self) -> None:
        if self._loader is not None:
            self._loader.close()
            self._loader = None
        self._queued.clear()


class MeshPlan(ExecutionPlan):
    """The 2-D (data x model) mesh, driven end to end.

    Each data-axis shard owns a ``HostSource`` view over its LOCAL row
    range only (``source.split``); ``plan_epoch`` samples the WHOLE
    epoch's per-shard index plan up front with the mesh ``fold_in``
    scheme (``sampler.mesh_epoch_plan`` — index for index what the
    device-sampling step draws, one dispatch + one host sync per epoch
    instead of per step) and queues it onto ONE cross-epoch
    ``MeshPrefetcher``: its worker gathers step t+1's per-shard blocks
    and ``device_put``s them straight to the step's shardings while the
    device runs step t, so the block-parametrized shard_map step
    (``make_distributed_block_step``) consumes pre-placed arrays and the
    gather + H2D leave the critical path (``prefetch=False`` gathers
    inline through ``SyncMeshGather`` — the A/B baseline and the
    pre-overlap shipping path).  On device live only the O(N)
    alpha/accum shards (P(model)) and the sampled blocks; validation
    evaluates through a model-axis psum of per-shard partial decision
    values, streamed chunk by chunk from the per-shard sources.

    An epoch is ``max(N // (n_grad * n_data_shards), 1)`` steps — every
    step consumes ``n_data * n_grad`` gradient samples, so one epoch
    touches ~N gradient rows, matching the serial epoch's sampling
    budget.  Bit-identical to the inline path and to a
    ``make_distributed_step`` loop driven from the same keys (the PR-4
    contract, now through ``fit`` with the overlap on).
    """

    name = "mesh"

    def __init__(self, cfg: DSEKLConfig, source, mesh, *,
                 data_axis: str = "data", model_axis: str = "model",
                 prefetch: bool = True,
                 precond: Optional[dsekl.PrecondBlock] = None):
        from repro.core import distributed as dist

        super().__init__(cfg, source.n)
        self.mesh = mesh
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.n_data, self.n_model = shape[data_axis], shape[model_axis]
        self.data_sources = source.split(self.n_data)
        self.model_sources = source.split(self.n_model)
        self.prefetch = bool(prefetch)
        self.precond = precond
        self.step_host = dist.make_distributed_block_step(
            cfg, mesh, self.n, data_axis, model_axis,
            precondition=precond is not None)
        self.steps_per_epoch = max(self.n // (cfg.n_grad * self.n_data), 1)
        self._model_axis = model_axis
        self._state_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(model_axis))
        self._eval = None
        self._loader = None
        # Queued epoch plans, FIFO: (key bytes, per-step keys).
        self._queued: collections.deque = collections.deque()
        self._consumed_steps = 0

    def init_state(self) -> DSEKLState:
        from repro.core import distributed as dist

        sh = dist.init_sharded_state(self.mesh, self.n, self._model_axis)
        return DSEKLState(alpha=sh.alpha, accum=sh.accum, step=sh.step,
                          epoch=jnp.zeros((), jnp.int32))

    def place_state(self, flat: Dict[str, np.ndarray]) -> DSEKLState:
        n_ckpt = int(np.asarray(flat["alpha"]).shape[0])
        if n_ckpt != self.n:
            # The elastic-rescale contract re-places the SAME N onto a
            # different mesh shape; a different N means the data (or its
            # divisibility trim) changed between runs — resuming would
            # silently train a different problem.
            raise ValueError(
                f"checkpoint carries alpha of {n_ckpt} rows but this mesh "
                f"fit trains {self.n}; an elastic rescale must keep the "
                "(trimmed) row count identical across mesh shapes — pick "
                "N divisible by every data/model axis size you resume on")
        sh = self._state_sharding
        return DSEKLState(
            alpha=jax.device_put(np.asarray(flat["alpha"], np.float32), sh),
            accum=jax.device_put(np.asarray(flat["accum"], np.float32), sh),
            step=jnp.asarray(flat["step"], jnp.int32),
            epoch=jnp.asarray(flat["epoch"], jnp.int32))

    # -- planning -------------------------------------------------------
    def plan_epoch(self, key: Optional[Array]) -> None:
        if key is None:
            return
        kb = np.asarray(key).tobytes()
        if any(q[0] == kb for q in self._queued):
            return                              # already planned ahead
        plan_i, plan_j = sampler.mesh_epoch_plan(
            key, self.cfg.n_grad, self.cfg.n_expand,
            tuple(s.n for s in self.data_sources),
            tuple(s.n for s in self.model_sources), self.steps_per_epoch)
        if self._loader is None:
            cls = MeshPrefetcher if self.prefetch else SyncMeshGather
            self._loader = cls(self.data_sources, self.model_sources,
                               self.step_host.shardings, plan_i, plan_j)
        else:
            self._loader.extend(plan_i, plan_j)
        # Replay the per-step key chain exactly as the inline path's
        # ``jax.random.split(key, steps)`` — stored host-side with the
        # plan so run_epoch never re-dispatches the split.
        step_keys = np.asarray(jax.random.split(key, self.steps_per_epoch))
        self._queued.append((kb, step_keys))

    def _pop_plan(self, key: Array):
        kb = np.asarray(key).tobytes()
        if not self._queued:
            self.plan_epoch(key)
        elif self._queued[0][0] != kb:
            raise RuntimeError(
                "mesh epochs must be consumed in the order they were "
                "planned (the prefetcher streams one plan)")
        return self._queued.popleft()

    def run_epoch(self, state: DSEKLState, key: Array) -> DSEKLState:
        """One epoch of mesh steps.  The steps run in epoch
        ``state.epoch + 1``, the serial plan's numbering, which the
        ``inv_epoch`` rate reads.  Under ``jax.profiler.trace`` each step
        writes ``dsekl.mesh.wait`` (the wait for the prefetcher's blocks)
        and ``dsekl.mesh.step`` (the step's dispatch)."""
        from repro.core import distributed as dist

        _, step_keys = self._pop_plan(key)
        sh = dist.ShardedDSEKLState(state.alpha, state.accum, state.step,
                                    state.epoch + 1)
        pc = self.precond
        loader = self._loader
        for t in range(self.steps_per_epoch):
            with TraceAnnotation("dsekl.mesh.wait"):
                xi, yi, xj, idx_j = loader.get()
            with TraceAnnotation("dsekl.mesh.step"):
                k = jnp.asarray(step_keys[t])
                sh = self.step_host(xi, yi, xj, idx_j, sh, k, pc)
        sh.alpha.block_until_ready()            # epoch-boundary sync
        self._consumed_steps += self.steps_per_epoch
        return DSEKLState(alpha=sh.alpha, accum=sh.accum, step=sh.step,
                          epoch=sh.epoch)

    def eval_error(self, state: DSEKLState, x_val: Array,
                   y_val: Array) -> float:
        from repro.core import distributed as dist

        if self._eval is None:
            self._eval = dist.make_mesh_eval(self.cfg, self.mesh,
                                             model_axis=self._model_axis)
        f = self._eval(state.alpha, self.model_sources, x_val)
        return float(jnp.mean(
            (dsekl.predict_labels(f) != y_val).astype(jnp.float32)))

    def loader_stats(self) -> Optional[Dict[str, float]]:
        if self._loader is None:
            return None
        st = dict(self._loader.stats())
        # Steps CONSUMED, not planned (the driver plans one epoch ahead).
        st["steps"] = float(self._consumed_steps)
        return st

    def close(self) -> None:
        if self._loader is not None:
            self._loader.close()
            self._loader = None
        self._queued.clear()


class BCDPlan(ExecutionPlan):
    """Block coordinate descent rounds (core/bcd.py; DESIGN.md §14).

    One "epoch" of the fit loop is one BCD round: sample a
    without-replacement coordinate block J, stream K_{.,J} row-block by
    row-block through the SAME data plane as the stochastic backends
    (``BlockPrefetcher`` serially, ``MeshPrefetcher`` on the mesh — the
    round plans feed one epoch ahead so gathers and H2D overlap device
    compute), accumulate the Gram system and residual right-hand side,
    solve the |J| x |J| regularized system exactly (Cholesky, jittered
    fallback), scatter alpha_J += d and replay the streamed pass once
    more to update the incremental residual ``f = K alpha`` by
    ``K_{.,J} d`` only.  Square loss only — BCD solves the regularized
    least-squares dual, there is no hinge variant of the exact block
    solve.

    Placement contract: row groups accumulate private Gram partials
    (sequential groups serially, one per data-axis device on the mesh)
    combined ON HOST in fixed order, and the solve is one single-device
    jitted call in both placements — a serial fit with
    ``cfg.bcd_shards = n_data`` is bit-identical to the mesh fit
    (tests/test_bcd.py).  The residual vector rides in every checkpoint
    (``snapshot_leaves``), so resumed == uninterrupted, bit for bit.
    """

    name = "bcd"

    def __init__(self, cfg: DSEKLConfig, source, *, mesh=None,
                 data_axis: str = "data", model_axis: str = "model",
                 prefetch: bool = True):
        from repro.core import bcd as bcd_lib

        super().__init__(cfg, source.n)
        if cfg.loss != "square":
            raise ValueError(
                "execution='bcd' solves the regularized square-loss "
                f"system; cfg.loss={cfg.loss!r} has no exact block solve "
                "(set loss='square')")
        self._bcd = bcd_lib
        self.source = source
        self.prefetch = bool(prefetch)
        self.mesh = mesh
        self.j_size = bcd_lib.block_size(cfg, self.n)
        self.rb = bcd_lib.row_block_size(cfg)
        self._lam_n = float(cfg.lam * self.n)
        if mesh is not None:
            shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            self.n_data = shape[data_axis]
            self.n_model = shape[model_axis]
            if cfg.bcd_shards and cfg.bcd_shards != self.n_data:
                raise ValueError(
                    f"cfg.bcd_shards={cfg.bcd_shards} conflicts with the "
                    f"mesh's data axis of {self.n_data} shards (on a mesh "
                    "the Gram partials are one-per-data-device; leave "
                    "bcd_shards=0 or match it)")
            self.shards = self.n_data
            self.data_sources = source.split(self.n_data)
            self.model_sources = source.split(self.n_model)
            self._model_axis = model_axis
            self._state_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(model_axis))
            self._ops = bcd_lib.make_mesh_bcd_ops(
                cfg, mesh, data_axis=data_axis, model_axis=model_axis)
            self._eval = None
        else:
            self.shards = int(cfg.bcd_shards or 1)
        idx_np, mask_np = bcd_lib.row_plan(self.n, self.shards, self.rb)
        self._idx_np, self._mask_np = idx_np, mask_np
        self.blocks_per_group = idx_np.shape[1]
        if mesh is not None:
            rep = self._ops.rep_sharding
            # Local tile indices/masks are identical across data shards
            # (row_plan's contract) — replicated per-step operands, like
            # the stochastic mesh step's key.
            self._idx_dev = [
                jax.device_put(np.asarray(idx_np[0, t], np.int32), rep)
                for t in range(self.blocks_per_group)]
            self._mask_dev = [jax.device_put(mask_np[t], rep)
                              for t in range(self.blocks_per_group)]
        else:
            self._idx_dev = [
                [jnp.asarray(idx_np[d, t], jnp.int32)
                 for t in range(self.blocks_per_group)]
                for d in range(self.shards)]
            self._mask_dev = [jnp.asarray(mask_np[t])
                              for t in range(self.blocks_per_group)]
        self._f = None
        self._loader = None
        # Queued round plans, FIFO: (key bytes, J).
        self._queued: collections.deque = collections.deque()
        self._consumed_steps = 0

    # -- state ----------------------------------------------------------
    def _zero_f(self):
        if self.mesh is not None:
            return jax.device_put(np.zeros((self.n,), np.float32),
                                  self._ops.f_sharding)
        return jnp.zeros((self.n,), jnp.float32)

    def _place_f(self, f_host: np.ndarray):
        f_host = np.asarray(f_host, np.float32)
        if self.mesh is not None:
            return jax.device_put(f_host, self._ops.f_sharding)
        return jax.device_put(jnp.asarray(f_host))

    def init_state(self) -> DSEKLState:
        self._f = self._zero_f()
        if self.mesh is None:
            return dsekl.init_state(self.n)
        from repro.core import distributed as dist

        sh = dist.init_sharded_state(self.mesh, self.n, self._model_axis)
        return DSEKLState(alpha=sh.alpha, accum=sh.accum, step=sh.step,
                          epoch=jnp.zeros((), jnp.int32))

    def place_state(self, flat: Dict[str, np.ndarray]) -> DSEKLState:
        if "bcd_f" not in flat:
            raise ValueError(
                "checkpoint carries no 'bcd_f' residual leaf — it was "
                "written by a non-BCD fit; a BCD resume needs the "
                "incremental f = K alpha to continue bit-identically")
        n_ckpt = int(np.asarray(flat["alpha"]).shape[0])
        if n_ckpt != self.n:
            raise ValueError(
                f"checkpoint carries alpha of {n_ckpt} rows but this BCD "
                f"fit trains {self.n}; the (trimmed) row count must stay "
                "identical across resumes")
        self._f = self._place_f(flat["bcd_f"])
        if self.mesh is None:
            return super().place_state(flat)
        sh = self._state_sharding
        return DSEKLState(
            alpha=jax.device_put(np.asarray(flat["alpha"], np.float32), sh),
            accum=jax.device_put(np.asarray(flat["accum"], np.float32), sh),
            step=jnp.asarray(flat["step"], jnp.int32),
            epoch=jnp.asarray(flat["epoch"], jnp.int32))

    def snapshot_leaves(self, state: DSEKLState) -> Dict[str, np.ndarray]:
        return {"bcd_f": np.asarray(self._f)}

    # -- planning -------------------------------------------------------
    def plan_epoch(self, key: Optional[Array]) -> None:
        if key is None:
            return
        kb = np.asarray(key).tobytes()
        if any(q[0] == kb for q in self._queued):
            return                              # already planned ahead
        j_idx = self._bcd.sample_block(key, self.n, self.j_size)
        blocks = self.blocks_per_group
        if self.mesh is not None:
            local = self._idx_np[0]             # (blocks, rb), shard-local
            plan_i = np.ascontiguousarray(np.broadcast_to(
                local[:, None, :], (blocks, self.n_data, self.rb)))
            plan_i = np.concatenate([plan_i, plan_i])     # two passes
            plan_j = np.ascontiguousarray(np.broadcast_to(
                j_idx, (2 * blocks, 1, self.j_size)))
            if self._loader is None:
                cls = MeshPrefetcher if self.prefetch else SyncMeshGather
                self._loader = cls(self.data_sources, [self.source],
                                   self._ops.shardings, plan_i, plan_j)
            else:
                self._loader.extend(plan_i, plan_j)
        else:
            pass1 = self._idx_np.reshape(self.shards * blocks, self.rb)
            plan_i = np.concatenate([pass1, pass1])       # two passes
            plan_j = np.ascontiguousarray(np.broadcast_to(
                j_idx, (plan_i.shape[0], self.j_size)))
            if self._loader is None:
                cls = BlockPrefetcher if self.prefetch else SyncGather
                self._loader = cls(self.source, plan_i, plan_j)
            else:
                self._loader.extend(plan_i, plan_j)
        self._queued.append((kb, j_idx))

    def _pop_plan(self, key: Array):
        kb = np.asarray(key).tobytes()
        if not self._queued:
            self.plan_epoch(key)
        elif self._queued[0][0] != kb:
            raise RuntimeError(
                "bcd rounds must be consumed in the order they were "
                "planned (the prefetcher streams one plan)")
        return self._queued.popleft()

    # -- rounds ---------------------------------------------------------
    def run_epoch(self, state: DSEKLState, key: Array) -> DSEKLState:
        _, j_idx = self._pop_plan(key)
        if self.mesh is not None:
            return self._run_round_mesh(state, j_idx)
        return self._run_round_serial(state, j_idx)

    def _run_round_serial(self, state: DSEKLState, j_idx) -> DSEKLState:
        bcd, cfg = self._bcd, self.cfg
        j, blocks, loader = self.j_size, self.blocks_per_group, self._loader
        f = self._f
        parts = np.empty((self.shards, j, j + 1), np.float32)
        xj_dev = None
        for d in range(self.shards):
            gb = jnp.zeros((j, j + 1), jnp.float32)
            for t in range(blocks):
                xi, yi, xj = loader.get()
                if xj_dev is None:
                    xj_dev = xj
                gb = bcd.acc_serial(cfg, xi, yi, xj, f,
                                    self._idx_dev[d][t],
                                    self._mask_dev[t], gb)
            parts[d] = np.asarray(gb)
        g_h, b_h = bcd.split_gram(bcd.combine_partials(parts))
        rhs = b_h - np.float32(self._lam_n) * np.asarray(f)[j_idx]
        delta, _ = bcd.solve_block(cfg, np.asarray(xj_dev), g_h, rhs,
                                   self._lam_n)
        alpha = bcd.scatter_alpha(state.alpha,
                                  jnp.asarray(j_idx, jnp.int32), delta)
        for d in range(self.shards):
            for t in range(blocks):
                xi, _, _ = loader.get()
                f = bcd.fupd_serial(cfg, xi, xj_dev, delta, f,
                                    self._idx_dev[d][t], self._mask_dev[t])
        f.block_until_ready()
        self._f = f
        self._consumed_steps += 2 * self.shards * blocks
        return state._replace(alpha=alpha, step=state.step + 1,
                              epoch=state.epoch + 1)

    def _run_round_mesh(self, state: DSEKLState, j_idx) -> DSEKLState:
        bcd, cfg, ops = self._bcd, self.cfg, self._ops
        j, blocks, loader = self.j_size, self.blocks_per_group, self._loader
        f = self._f
        gb = jax.device_put(np.zeros((self.n_data, j, j + 1), np.float32),
                            ops.gram_sharding)
        xj_dev = idxj_dev = None
        for t in range(blocks):
            xi, yi, xj, idx_j = loader.get()
            xj_dev, idxj_dev = xj, idx_j
            gb = ops.acc(xi, yi, xj, f, self._idx_dev[t],
                         self._mask_dev[t], gb)
        g_h, b_h = bcd.split_gram(bcd.combine_partials(np.asarray(gb)))
        rhs = b_h - np.float32(self._lam_n) * np.asarray(f)[j_idx]
        delta, _ = bcd.solve_block(cfg, np.asarray(xj_dev), g_h, rhs,
                                   self._lam_n)
        delta_rep = jax.device_put(delta, ops.rep_sharding)
        alpha = ops.scatter(state.alpha, idxj_dev, delta_rep)
        for t in range(blocks):
            xi, _, _, _ = loader.get()
            f = ops.fupd(xi, xj_dev, delta_rep, f, self._idx_dev[t],
                         self._mask_dev[t])
        f.block_until_ready()
        self._f = f
        self._consumed_steps += 2 * blocks
        return DSEKLState(alpha=alpha, accum=state.accum,
                          step=state.step + 1, epoch=state.epoch + 1)

    # -- eval / reporting -----------------------------------------------
    def eval_error(self, state: DSEKLState, x_val: Array,
                   y_val: Array) -> float:
        if self.mesh is None:
            return _error_source(self.cfg, state.alpha, self.source, x_val,
                                 y_val)
        from repro.core import distributed as dist

        if self._eval is None:
            self._eval = dist.make_mesh_eval(self.cfg, self.mesh,
                                             model_axis=self._model_axis)
        f = self._eval(state.alpha, self.model_sources, x_val)
        return float(jnp.mean(
            (dsekl.predict_labels(f) != y_val).astype(jnp.float32)))

    def loader_stats(self) -> Optional[Dict[str, float]]:
        if self._loader is None:
            return None
        st = dict(self._loader.stats())
        st["steps"] = float(self._consumed_steps)
        return st

    def close(self) -> None:
        if self._loader is not None:
            self._loader.close()
            self._loader = None
        self._queued.clear()


# ---------------------------------------------------------------------------
# The one backend-agnostic fit loop.
# ---------------------------------------------------------------------------

def _snapshot(manager, state: DSEKLState, key: Array, epoch: int,
              history: List[Dict[str, Any]], converged: bool,
              extra_fields: Optional[Dict[str, Any]] = None,
              leaves: Optional[Dict[str, np.ndarray]] = None) -> None:
    """Checkpoint the full resume closure: state + the PRE-epoch sampler
    carry key + epoch counter + history + the converged flag (a resumed
    fit must STOP where the uninterrupted one stopped, not train past
    convergence).  Sharded leaves are gathered to host by
    ``flatten_tree``; timing fields ride along in history but never
    influence the trajectory.  ``extra_fields`` merges caller payload
    into ``extra`` (the solver stores the serialized preconditioner here
    so a resumed preconditioned fit replays the identical correction)."""
    tree = {"alpha": state.alpha, "accum": state.accum,
            "step": state.step, "epoch": state.epoch,
            "key": np.asarray(key)}
    if leaves:
        # Backend-owned leaves (ExecutionPlan.snapshot_leaves): the BCD
        # residual vector rides here so a resumed round replays exactly.
        tree.update(leaves)
    extra = {"epoch": epoch, "history": history, "converged": converged}
    if extra_fields:
        # A callable is evaluated at snapshot time — the online service
        # injects its live publish log / snapshot identity this way.
        if callable(extra_fields):
            extra_fields = extra_fields()
        extra.update(extra_fields)
    manager.save(epoch, tree, extra=extra)


def _restore(manager, plan: ExecutionPlan):
    step = manager.latest_valid_step()
    if step is None:
        return None
    _, flat, extra = manager.restore(step)
    state = plan.place_state(flat)
    key = jnp.asarray(flat["key"])
    return (state, key, int(extra["epoch"]), list(extra["history"]),
            bool(extra.get("converged", False)))


def fit_loop(plan: ExecutionPlan, key: Array, *, n_epochs: int = 50,
             tol: float = 1e-3, x_val: Optional[Array] = None,
             y_val: Optional[Array] = None, eval_every: int = 1,
             verbose: bool = False, truncate_every: int = 0,
             truncate_frac: float = 0.1,
             callback: Optional[Callable[[int, DSEKLState], None]] = None,
             manager=None, checkpoint_every: int = 1,
             resume: bool = False,
             snapshot_extra=None,
             on_epoch: Optional[
                 Callable[[int, DSEKLState, Dict[str, Any]], Any]] = None
             ) -> FitResult:
    """Drive any ``ExecutionPlan`` to convergence (paper §4.2 stopping
    rule) or ``n_epochs``: epoch -> truncate -> eval -> snapshot.

    The epoch key chain is ``key, sub = split(key)`` per epoch (the
    legacy chain, so all backends remain bit-compatible with pre-refactor
    fits), with ``plan_epoch`` called one epoch AHEAD of ``run_epoch`` —
    plan-driven backends keep their prefetch pipeline streaming across
    epoch boundaries.  With a ``CheckpointManager`` the loop snapshots
    every ``checkpoint_every`` epochs (and at the end); ``resume=True``
    restores the newest valid snapshot and continues — bit-identically
    to a run that was never interrupted (the snapshot carries the
    pre-epoch sampler key, so the sub-key sequence replays exactly).

    ``on_epoch(epoch, state, record)`` is the epoch-*boundary* hook
    (DESIGN.md §11): called after truncate/eval with the completed
    epoch's history record, it is where an online service publishes the
    fresh alpha into its serving engine.  Unlike ``callback`` (purely
    observational, pre-PR-7 behavior) a truthy return value stops the
    fit after the boundary's snapshot — ``FitResult.stop_reason`` then
    reads ``"hook"``.  ``snapshot_extra`` may be a dict or a zero-arg
    callable evaluated at each snapshot (live caller state rides along
    in the checkpoint).

    Under ``jax.profiler.trace`` the loop writes host spans: its set-up
    (``init_state``, resume, the first epoch's plan) is
    ``dsekl.fit.setup``; each epoch is the step span ``dsekl.epoch``
    (``step_num`` = epoch number) holding one span per phase:
    ``dsekl.epoch.plan``, ``.dispatch``, ``.wait``, ``.host_delta``,
    ``.eval``, ``.hooks`` and ``.snapshot``.  The history ``seconds`` of
    an epoch is the host clock over its dispatch and wait."""
    with TraceAnnotation("dsekl.fit.setup"):
        state = plan.init_state()
        history: List[Dict[str, Any]] = []
        start = 0
        converged = False
        if manager is not None and resume:
            restored = _restore(manager, plan)
            if restored is not None:
                state, key, start, history, converged = restored
                if converged:
                    # The interrupted run had already met the stopping
                    # rule: an uninterrupted run would have stopped here
                    # too.
                    start = n_epochs
                if verbose:
                    print(f"[dsekl] resumed at epoch {start} "
                          f"({plan.name} backend)"
                          + (" — already converged" if converged else ""))
        sub = None
        hook_stop = False
        prev_alpha = np.asarray(state.alpha, np.float64)
        if start < n_epochs:
            key, sub = jax.random.split(key)
            plan.plan_epoch(sub)
    for e in range(start, n_epochs):
        with StepTraceAnnotation("dsekl.epoch", step_num=e + 1):
            with TraceAnnotation("dsekl.epoch.plan"):
                ckpt_key = key                  # pre-epoch carry (resume)
                if e + 1 < n_epochs:
                    key, sub_next = jax.random.split(key)
                    plan.plan_epoch(sub_next)   # one epoch ahead
                else:
                    sub_next = None
            t0 = time.perf_counter()
            with TraceAnnotation("dsekl.epoch.dispatch"):
                state = plan.run_epoch(state, sub)
                if truncate_every and (e + 1) % truncate_every == 0:
                    state = state._replace(
                        alpha=_truncate_smallest(state.alpha, truncate_frac))
            with TraceAnnotation("dsekl.epoch.wait"):
                state.alpha.block_until_ready()
            dt = time.perf_counter() - t0
            with TraceAnnotation("dsekl.epoch.host_delta"):
                # |dalpha| on a host copy: a fixed-order float64
                # reduction, so the history does not depend on how alpha
                # is sharded.
                alpha_host = np.asarray(state.alpha, np.float64)
                delta = float(np.sqrt(np.sum(np.square(alpha_host
                                                       - prev_alpha))))
                prev_alpha = alpha_host
            converged = delta < tol             # paper §4.2 stopping rule
            rec: Dict[str, Any] = {"epoch": e + 1, "delta_alpha": delta,
                                   "seconds": dt}
            with TraceAnnotation("dsekl.epoch.eval"):
                # Evaluate on eval_every epochs AND on the last record of
                # the fit — the final epoch or the convergence epoch (a
                # fit stopping early off the eval cadence must not leave
                # its last history record without a val_error).
                if x_val is not None and (e % eval_every == 0 or converged
                                          or e == n_epochs - 1):
                    rec["val_error"] = plan.eval_error(state, x_val, y_val)
            history.append(rec)
            with TraceAnnotation("dsekl.epoch.hooks"):
                if callback is not None:
                    callback(e, state)
                hook_stop = bool(on_epoch(e + 1, state, rec)) \
                    if on_epoch is not None else False
                if verbose:
                    print(f"[dsekl] epoch {e + 1}: |dalpha|={delta:.4f} "
                          + (f"val_err={rec.get('val_error', float('nan')):.4f}"
                             if "val_error" in rec else ""))
            with TraceAnnotation("dsekl.epoch.snapshot"):
                if manager is not None and (
                        (e + 1) % checkpoint_every == 0 or converged
                        or hook_stop or e == n_epochs - 1):
                    _snapshot(manager, state, ckpt_key, e + 1, history,
                              converged, snapshot_extra,
                              leaves=plan.snapshot_leaves(state))
            sub = sub_next
            if converged or hook_stop:
                break
    if manager is not None:
        manager.wait()
    return FitResult(state=state, history=history, converged=converged,
                     epochs_run=len(history),
                     val_cache=plan.val_cache_info(),
                     loader=plan.loader_stats(),
                     stop_reason=("converged" if converged
                                  else "hook" if hook_stop else "epochs"),
                     # Uniform convergence summary (history-derived only —
                     # the trajectory and history semantics are untouched).
                     epochs_to_tol=next(
                         (h["epoch"] for h in history
                          if h["delta_alpha"] < tol), None),
                     final_residual=(history[-1]["delta_alpha"]
                                     if history else 0.0),
                     row_width=plan.row_width)


def resolve_execution(execution: Optional[str], cfg: DSEKLConfig, *,
                      algorithm: str, hosted_data: bool,
                      mesh=None) -> str:
    """``execution=None`` defers to ``cfg.execution``; ``"auto"`` picks
    mesh when a mesh is given, hosted for host-resident sources, else the
    in-memory backend matching ``algorithm``."""
    execution = execution if execution is not None else cfg.execution
    if execution not in EXECUTIONS:
        raise ValueError(f"unknown execution {execution!r}; "
                         f"one of {EXECUTIONS}")
    if execution == "auto":
        if mesh is not None:
            return "mesh"
        if hosted_data:
            return "hosted"
        return algorithm
    return execution


def make_plan(execution: str, cfg: DSEKLConfig, *, x=None, y=None,
              source=None, algorithm: str = "serial",
              prefetch: bool = True, eval_cache: bool = False,
              mesh=None, precond=None) -> ExecutionPlan:
    """Build the concrete backend for a resolved ``execution`` string.

    ``precond`` is an ``EigenProPreconditioner`` (staged to its device
    ``PrecondBlock`` here) or an already-staged ``PrecondBlock``; None
    trains unpreconditioned — bit-identical to the pre-precond trainer.
    """
    if precond is not None and hasattr(precond, "block"):
        precond = precond.block()
    if execution in ("serial", "parallel"):
        if x is None:
            raise ValueError(
                f"execution={execution!r} needs device-resident arrays; "
                "a host-resident DataSource trains via 'hosted' or 'mesh'")
        plan_cls = SerialPlan if execution == "serial" else ParallelPlan
        return plan_cls(cfg, x, y, eval_cache=eval_cache, precond=precond)
    if execution == "hosted":
        if source is None:
            raise ValueError("execution='hosted' needs a DataSource")
        return HostedPlan(cfg, source, algorithm=algorithm,
                          prefetch=prefetch, precond=precond)
    if execution == "mesh":
        if source is None:
            raise ValueError("execution='mesh' needs a DataSource "
                             "(wrap arrays in InMemorySource)")
        if mesh is None:
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh(jax.device_count(), 1)
        return MeshPlan(cfg, source, mesh, prefetch=prefetch,
                        precond=precond)
    if execution == "bcd":
        if source is None:
            raise ValueError("execution='bcd' needs a DataSource "
                             "(wrap arrays in InMemorySource)")
        if precond is not None:
            raise ValueError(
                "execution='bcd' solves each block exactly — EigenPro "
                "preconditioning applies to the stochastic step only")
        return BCDPlan(cfg, source, mesh=mesh, prefetch=prefetch)
    raise ValueError(f"unknown execution {execution!r}")
