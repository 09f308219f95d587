"""Training front door for DSEKL: ``fit`` over any execution backend.

The paper's stopping rule (§4.2): stop when the L2 norm of the weight
(dual-coefficient) change over one epoch is below a tolerance (they use 1.0
on covertype).  ``fit`` implements that for both Algorithm 1 ("serial") and
Algorithm 2 ("parallel") — over ANY execution backend.

Since PR 5 the epoch drivers live behind the ``ExecutionPlan`` interface
(``core/trainer.py``, DESIGN.md §9): ``fit`` resolves the data placement
and the requested ``execution`` to one of

  * ``SerialPlan`` / ``ParallelPlan`` — device-resident arrays, the
    fully-jitted in-memory epochs (exactly the pre-refactor paths);
  * ``HostedPlan`` — a host-resident ``DataSource`` (numpy / np.memmap):
    host-side epoch plans, ONE cross-epoch ``BlockPrefetcher``, the
    N-independent block gradient cores — bit-identical to in-memory;
  * ``MeshPlan`` — the 2-D (data x model) mesh: per-shard ``HostSource``
    views, host-gathered mesh blocks, the shard_map block step, psum'd
    eval;

then drives the single backend-agnostic loop (``trainer.fit_loop``:
epoch -> truncate -> eval -> snapshot), including checkpoint/resume
through ``checkpoint.CheckpointManager``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from repro.core import trainer
from repro.core.dsekl import DSEKLConfig, DSEKLState
from repro.core.trainer import (  # noqa: F401  (re-exported API)
    BCDPlan, ExecutionPlan, FitResult, HostedPlan, MeshPlan, ParallelPlan,
    SerialPlan, _error, _EVAL_CACHE_BUDGET_BYTES,
)
from repro.data.source import InMemorySource

Array = jax.Array


def train_epoch_hosted(cfg: DSEKLConfig, state: DSEKLState, source,
                       key: Array, *, algorithm: str = "serial",
                       prefetch: bool = True,
                       stats: Optional[dict] = None) -> DSEKLState:
    """One out-of-core epoch over a host-resident source — the public
    single-epoch entry point (the per-epoch building block ``fit`` drives
    through ``HostedPlan``; examples and the ``train_outofcore`` bench
    cell use it to A/B the prefetch pipeline against the
    synchronous-gather baseline).  Bit-identical to one epoch of a
    hosted ``fit`` from the same key."""
    with trainer.HostedPlan(cfg, source, algorithm=algorithm,
                            prefetch=prefetch) as plan:
        state = plan.run_epoch(state, key)
        if stats is not None:
            for k, v in (plan.loader_stats() or {}).items():
                stats[k] = stats.get(k, 0.0) + v
    return state


# fold_in tag deriving the one-time preconditioner-estimation key from the
# fit key: the per-epoch ``key, sub = split(key)`` chain never sees it, so
# preconditioned and unpreconditioned fits sample identical epochs.
_PRECOND_KEY_TAG = 1337


def _resolve_preconditioner(cfg: DSEKLConfig, precondition, data,
                            key: Array, *, manager, resume: bool):
    """``fit``'s ``precondition=`` semantics: pass-through / rank / config
    default, with checkpoint-extra restore on resume."""
    if hasattr(precondition, "block"):      # an EigenProPreconditioner
        return precondition
    k = cfg.precondition_k if precondition is None else int(precondition)
    if k <= 0:
        return None
    from repro.core import precond as precond_lib
    if manager is not None and resume:
        step = manager.latest_valid_step()
        if step is not None:
            _, _, extra = manager.restore(step)
            if "precond" in extra:
                # Bit-exact restore: the resumed correction replays the
                # interrupted fit's, even if the data files moved.
                return precond_lib.EigenProPreconditioner.from_extra(
                    extra["precond"])
    return precond_lib.estimate_preconditioner(
        cfg, data, jax.random.fold_in(key, _PRECOND_KEY_TAG), k=k)


@functools.partial(jax.profiler.annotate_function, name="dsekl.fit")
def fit(cfg: DSEKLConfig, x, y=None, key: Array = None, *,
        execution: Optional[str] = None, algorithm: str = "serial",
        n_epochs: int = 50, tol: float = 1e-3,
        x_val: Optional[Array] = None, y_val: Optional[Array] = None,
        eval_every: int = 1, verbose: bool = False,
        truncate_every: int = 0, truncate_frac: float = 0.1,
        eval_cache="auto", prefetch: bool = True, mesh=None,
        checkpoint_dir: Optional[str] = None, checkpoint_every: int = 1,
        checkpoint_keep: int = 3, resume: bool = False,
        callback: Optional[Callable[[int, DSEKLState], None]] = None,
        precondition=None, on_epoch=None) -> FitResult:
    """Run DSEKL until convergence (paper stopping rule) or ``n_epochs``.

    ``x`` is either the device-resident ``(N, D)`` array (with ``y``) or a
    ``DataSource``.  ``execution`` picks the backend (default
    ``cfg.execution``, normally ``"auto"``): an ``InMemorySource`` / raw
    arrays resolve onto the fully-jitted in-memory epochs
    (``SerialPlan``/``ParallelPlan`` per ``algorithm``), a ``HostSource``
    (numpy / np.memmap, ``y`` inside the source) onto ``HostedPlan`` —
    host-side epoch plans generated ONE EPOCH AHEAD so the double-buffered
    block prefetcher streams across epoch boundaries (``prefetch=False``
    gathers inline, the A/B baseline) — and ``execution="mesh"`` (or a
    ``mesh=`` argument) onto ``MeshPlan``, driving the distributed block
    step end to end from per-shard source views.  ``execution="bcd"``
    runs block coordinate descent rounds instead of stochastic steps
    (``BCDPlan``; square loss only, no truncation/preconditioning, see
    DESIGN.md §14) — serially, or on the mesh when ``mesh=`` is given.
    All backends consume the same per-epoch PRNG chain; each is
    bit-identical to its reference trajectory
    (``tests/test_trainer_matrix.py``).

    ``truncate_every``: paper §5's NORMA/Forgetron-style truncation made
    doubly-stochastic-simple — every k epochs the smallest
    ``truncate_frac`` of non-zero |alpha| mass is zeroed (budgeted model;
    zeroed points can re-enter via later J samples, unlike the Forgetron).

    ``eval_cache``: evaluate ``x_val`` through a cached prediction engine
    (serving/dsekl_engine.py): the validation kernel map K(x_val, X) is
    materialized once and reused every epoch — later epochs' eval skips
    the kernel evaluation entirely.  Costs O(n_val * N) floats of resident
    cache, so the default ``"auto"`` enables it only when that footprint
    fits 1 GiB; ``True`` forces it, ``False`` forces the memory-lean
    jitted error path.  Host-source and mesh fits always use the streamed
    source eval (the dataset must not become device-resident).

    ``checkpoint_dir``: snapshot ``(state, sampler key, epoch, history)``
    every ``checkpoint_every`` epochs (atomic + async + checksummed,
    ``checkpoint.CheckpointManager``).  ``resume=True`` restores the
    newest valid snapshot from the directory (fresh start when empty) and
    continues — bit-identical to a run that was never interrupted.

    ``precondition``: EigenPro preconditioning (DESIGN.md §10).  ``None``
    defers to ``cfg.precondition_k`` (0 — the default — trains
    unpreconditioned, tracing to the exact pre-precond program); an int
    is the rank k (0 forces off); an ``EigenProPreconditioner`` is used
    as given.  When a rank is requested the eigensystem is estimated
    once from a Nystrom subsample of the training data
    (``precond.estimate_preconditioner``, host-side, out-of-core) with a
    key derived from ``key`` by ``fold_in`` — the per-epoch sampling
    chain is untouched, and a resumed fit restores the preconditioner
    bit-exactly from the checkpoint instead of re-estimating.  Under
    ``schedule="const"`` with ``cfg.precondition_auto_lr`` the fit also
    swaps ``lr0`` for the recipe's auto step size.

    ``on_epoch(epoch, state, record)``: the epoch-boundary hook
    (``trainer.fit_loop``; DESIGN.md §11) — return truthy to stop the
    fit after that boundary's snapshot.  A live appendable source
    (``data.RingSource``) is snapshotted once at entry: the fit trains
    a frozen, versioned window while the writer keeps appending.

    Under ``jax.profiler.trace`` the call is the host span ``dsekl.fit``;
    its argument checks, preconditioner and plan building are
    ``dsekl.fit.setup``, and ``fit_loop`` adds its own spans (epochs and
    their phases; docs/OPERATIONS.md, "Tracing a fit").
    """
    with jax.profiler.TraceAnnotation("dsekl.fit.setup"):
        if key is None:
            raise TypeError("fit() requires a PRNG key (jax.random.PRNGKey)")
        if x_val is not None and y_val is None:
            raise TypeError(
                "fit() got x_val without y_val: validation labels are required "
                "to evaluate (pass y_val, or drop x_val to skip eval)")
        source = None
        if hasattr(x, "gather") and hasattr(x, "n"):        # any DataSource
            if y is not None:
                raise TypeError(
                    "fit() over a DataSource takes labels from the source; "
                    "pass y=None (a separate y would be silently wrong)")
            if hasattr(x, "snapshot") and hasattr(x, "append"):
                # A live appendable source (RingSource): fit trains over a
                # frozen, versioned snapshot of the current window — the
                # writer keeps appending, this fit's indices never move.
                # (The online service owns the grow-across-epochs loop;
                # a plain fit is one frozen window.)
                x = x.snapshot()
            source = x
            x = y = None
        hosted_data = source is not None and not isinstance(source,
                                                            InMemorySource)
        execution = trainer.resolve_execution(execution, cfg,
                                              algorithm=algorithm,
                                              hosted_data=hosted_data,
                                              mesh=mesh)
        if execution in ("serial", "parallel"):
            algorithm = execution                   # the backend IS the algorithm
            if isinstance(source, InMemorySource):
                x, y = source.x, source.y
            elif source is not None:
                raise ValueError(
                    f"execution={execution!r} needs device-resident data; a "
                    "HostSource trains out of core via 'hosted' or 'mesh'")
            n = int(x.shape[0])
        else:
            if source is None:                      # raw arrays -> host mirror
                source = InMemorySource(x, y)
            n = source.n
        if eval_cache == "auto":
            eval_cache = (execution in ("serial", "parallel")
                          and x_val is not None
                          and 4 * int(x_val.shape[0]) * n
                          <= _EVAL_CACHE_BUDGET_BYTES)
        manager = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointManager
            manager = CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
        if execution == "bcd" and truncate_every:
            raise ValueError(
                "execution='bcd' cannot truncate: zeroing alpha entries "
                "outside a round would desync the incremental residual "
                "f = K alpha that the block solves maintain")
        pre = _resolve_preconditioner(cfg, precondition,
                                      source if source is not None else x, key,
                                      manager=manager, resume=resume)
        if execution == "bcd" and pre is not None:
            raise ValueError(
                "execution='bcd' solves each block exactly — EigenPro "
                "preconditioning applies to the stochastic step only (drop "
                "precondition/cfg.precondition_k)")
        snapshot_extra = {"precond": pre.to_extra()} if pre is not None else None
        if (pre is not None and cfg.precondition_auto_lr
                and cfg.schedule == "const"):
            # The step-size rule wants the per-step J-union size: how many
            # expansion coordinates one step scatters.
            if execution == "mesh" and mesh is not None:
                n_model = dict(zip(mesh.axis_names,
                                   mesh.devices.shape)).get("model", 1)
                j_union = n_model * cfg.n_expand
            elif algorithm == "parallel":
                j_union = cfg.n_workers * cfg.n_expand
            else:
                j_union = cfg.n_expand
            cfg = cfg.replace(lr0=pre.step_size(j_union))
        plan = trainer.make_plan(execution, cfg, x=x, y=y, source=source,
                                 algorithm=algorithm, prefetch=prefetch,
                                 eval_cache=eval_cache, mesh=mesh,
                                 precond=pre)
    with plan:
        return trainer.fit_loop(
            plan, key, n_epochs=n_epochs, tol=tol, x_val=x_val, y_val=y_val,
            eval_every=eval_every, verbose=verbose,
            truncate_every=truncate_every, truncate_frac=truncate_frac,
            callback=callback, manager=manager,
            checkpoint_every=checkpoint_every, resume=resume,
            snapshot_extra=snapshot_extra, on_epoch=on_epoch)

def error_rate(cfg: DSEKLConfig, alpha: Array, x_train: Array, x: Array,
               y: Array) -> float:
    return float(_error(cfg, alpha, x_train, x, y))
