"""Production training launcher.

On a real pod this process runs per host (jax.distributed.initialize picks
up the cluster env); on this CPU container it runs the same code end to
end with a local mesh and a reduced config, exercising every production
path: sharded params/opt-state, fault-tolerant loop with atomic
checkpoints, exact resume, straggler watchdog.

    PYTHONPATH=src python -m repro.launch.train --arch granite-20b \
        --steps 100 [--full] [--data-par 2 --model-par 1]

DSEKL kernel training (the empirical-kernel-map model).  ``--data memory``
is the device-resident path; ``--data mmap`` writes the dataset to disk as
float32 memmaps and trains OUT OF CORE through the host-resident data
plane (DESIGN.md §8): host-side epoch plans, a prefetch thread
double-buffering the sampled row blocks while the device runs the previous
step, and the N-independent block gradient core — only O(n_grad + n_expand)
rows plus the O(N) dual vector ever live on the device:

    PYTHONPATH=src python -m repro.launch.train --dsekl --data mmap \
        --n 200000 --dim 64 --epochs 3 [--no-prefetch] [--algorithm parallel]
"""
import os

if __name__ == "__main__" and os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_FORCE_DEVICES"])

import argparse          # noqa: E402

import jax               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint import CheckpointManager                 # noqa: E402
from repro.configs import get_config                           # noqa: E402
from repro.data.pipeline import BigramPipeline                 # noqa: E402
from repro.distributed.sharding import MeshCtx, make_rules     # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_production_mesh  # noqa: E402
from repro.models.model import LanguageModel                   # noqa: E402
from repro.nn.module import param_pspecs                       # noqa: E402
from repro.optim import make_optimizer, make_schedule          # noqa: E402
from repro.train import make_train_step, train_loop, TrainLoopConfig  # noqa: E402


def train_dsekl(args):
    """Train the kernel machine through the unified execution-backend
    trainer: in-memory, out-of-core from a memmap, or mesh-distributed —
    with optional checkpoint/resume."""
    import time

    import numpy as np

    from repro.core import DSEKLConfig, fit
    from repro.data import HostSource, make_memmap_dataset, split_holdout
    from repro.data.synthetic import make_covertype_like

    cfg = DSEKLConfig(n_grad=args.n_grad, n_expand=args.n_expand,
                      kernel=args.kernel,
                      kernel_params=(("gamma", args.gamma),),
                      lam=1e-4, schedule="adagrad",
                      n_workers=args.workers, impl="auto",
                      precondition_k=args.precondition_k,
                      bcd_block=args.bcd_block,
                      bcd_row_block=args.bcd_row_block)
    if args.execution == "bcd":
        # BCD solves the regularized least-squares system exactly — it
        # has no hinge variant (core/bcd.py; DESIGN.md §14).
        cfg = cfg.replace(loss="square")
        print(f"[train-dsekl] block coordinate descent: |J|="
              f"{args.bcd_block or args.n_expand} per round")
    key = jax.random.PRNGKey(args.seed)
    mesh = None
    if args.execution == "mesh" or (
            args.execution == "bcd"
            and args.data_par * args.model_par > 1):
        mesh = make_local_mesh(args.data_par, args.model_par)
    if args.precondition_k:
        print(f"[train-dsekl] EigenPro preconditioning: "
              f"top-{args.precondition_k} Nystrom eigensystem")
    ckpt_kw = dict(checkpoint_dir=args.checkpoint_dir, resume=args.resume,
                   checkpoint_every=args.ckpt_every_epochs)
    if args.checkpoint_dir:
        print(f"[train-dsekl] checkpoints -> {args.checkpoint_dir} "
              f"(every {args.ckpt_every_epochs} epoch(s)"
              + (", resuming from newest valid" if args.resume else "")
              + ")")

    if args.data == "mmap":
        src = make_memmap_dataset(args.mmap_dir, args.n, args.dim,
                                  seed=args.seed)
        train_src, x_val, y_val = split_holdout(src)
        if mesh is not None:
            # The mesh split contract needs the train rows divisible by
            # both axes: trim the tail of the train VIEW (the holdout
            # already came off the end of the backing set).
            import math
            shards = math.lcm(args.data_par, args.model_par)
            train_src = train_src.local(0, train_src.n - train_src.n % shards)
        x_val, y_val = jax.numpy.asarray(x_val), jax.numpy.asarray(y_val)
        print(f"[train-dsekl] mmap dataset: {args.n} x {args.dim} = "
              f"{src.nbytes / 2**20:.1f} MiB on disk at {args.mmap_dir}; "
              f"device sees {4 * (cfg.n_grad + cfg.n_expand) * args.dim / 2**10:.0f}"
              f" KiB of rows per step + {8 * args.n / 2**20:.1f} MiB of state")
        t0 = time.perf_counter()
        res = fit(cfg, train_src, None, key, execution=args.execution,
                  algorithm=args.algorithm, mesh=mesh,
                  n_epochs=args.epochs, tol=0.0, x_val=x_val, y_val=y_val,
                  prefetch=not args.no_prefetch, verbose=True, **ckpt_kw)
        dt = time.perf_counter() - t0
        ld = res.loader or {}
        print(f"[train-dsekl] {res.epochs_run} epochs in {dt:.2f}s "
              f"(mode={'sync' if args.no_prefetch else 'prefetch'}; "
              f"host gather {ld.get('gather_s', 0.0):.2f}s, consumer wait "
              f"{ld.get('wait_s', 0.0):.2f}s)")
    else:
        x, y = make_covertype_like(key, n=args.n, d=args.dim)
        n_val = max(min(2048, args.n // 8), 1)  # never 0: x[:-0] is empty
        x_val, y_val = x[-n_val:], y[-n_val:]
        x, y = x[:-n_val], y[:-n_val]
        if mesh is not None:
            # The mesh split contract needs N divisible by both axes:
            # trim the tail rows (they re-enter nothing — the holdout
            # already came off the end).
            import math
            shards = math.lcm(args.data_par, args.model_par)
            n_tr = x.shape[0] - x.shape[0] % shards
            x, y = x[:n_tr], y[:n_tr]
            data = HostSource(np.asarray(x), np.asarray(y))
            fit_args, fit_y = data, None
        else:
            fit_args, fit_y = x, y
        t0 = time.perf_counter()
        res = fit(cfg, fit_args, fit_y, key, execution=args.execution,
                  algorithm=args.algorithm, mesh=mesh,
                  n_epochs=args.epochs, tol=0.0, x_val=x_val, y_val=y_val,
                  prefetch=not args.no_prefetch, verbose=True, **ckpt_kw)
        dt = time.perf_counter() - t0
        ld = res.loader or {}
        overlap = (f"; host gather {ld.get('gather_s', 0.0):.2f}s, consumer "
                   f"wait {ld.get('wait_s', 0.0):.2f}s" if ld else "")
        print(f"[train-dsekl] {res.epochs_run} epochs in {dt:.2f}s "
              f"({'mesh ' + str(dict(zip(mesh.axis_names, mesh.devices.shape))) if mesh is not None else 'device-resident'}"
              f"{overlap})")
    errs = [h["val_error"] for h in res.history if "val_error" in h]
    nsv = int((np.asarray(res.state.alpha) != 0).sum())
    print(f"[train-dsekl] val error {errs[0]:.4f} -> {errs[-1]:.4f}; "
          f"{nsv} support vectors")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full-size config + production mesh (needs a pod)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-3)
    # DSEKL kernel training (in-memory or out-of-core)
    ap.add_argument("--dsekl", action="store_true",
                    help="train the DSEKL kernel machine instead of an LM")
    ap.add_argument("--data", choices=("memory", "mmap"), default="memory",
                    help="device-resident arrays, or out-of-core from "
                         "float32 memmaps via the HostSource data plane")
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=54)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--n-grad", type=int, default=256)
    ap.add_argument("--n-expand", type=int, default=256)
    ap.add_argument("--kernel", default="rbf")
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--algorithm", choices=("serial", "parallel"),
                    default="serial")
    ap.add_argument("--precondition-k", type=int, default=0,
                    help="EigenPro preconditioning rank: damp the top-k "
                         "kernel eigendirections estimated from a Nystrom "
                         "subsample (core/precond.py; 0 = off)")
    ap.add_argument("--execution",
                    choices=("auto", "serial", "parallel", "hosted", "mesh",
                             "bcd"),
                    default="auto",
                    help="training execution backend (core/trainer.py): "
                         "auto resolves from the data placement; mesh uses "
                         "a --data-par x --model-par local mesh; bcd runs "
                         "exact block coordinate descent rounds (square "
                         "loss; mesh-distributed when --data-par x "
                         "--model-par > 1)")
    ap.add_argument("--bcd-block", type=int, default=0,
                    help="BCD coordinate-block size |J| per round "
                         "(0 = n_expand)")
    ap.add_argument("--bcd-row-block", type=int, default=0,
                    help="BCD streamed row-tile size (0 = n_grad)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot (state, sampler key, epoch, history) "
                         "here every --ckpt-every-epochs epochs (atomic + "
                         "async, checkpoint.CheckpointManager)")
    ap.add_argument("--ckpt-every-epochs", type=int, default=1)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint from "
                         "--checkpoint-dir and continue (bit-identical to "
                         "an uninterrupted run; fresh start if empty). A "
                         "mesh fit may resume on a DIFFERENT --data-par x "
                         "--model-par shape (elastic rescale) as long as "
                         "the trimmed row count is unchanged")
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mmap-dir", default="/tmp/repro_dsekl_mmap")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="gather sampled blocks inline (the synchronous "
                         "baseline) instead of the double-buffered prefetch; "
                         "applies to the hosted data plane and to --execution "
                         "mesh (where prefetch also hides the per-shard H2D "
                         "transfers)")
    args = ap.parse_args()
    setup_compile_cache()

    if args.dsekl:
        train_dsekl(args)
        return

    if args.full:
        # Multi-host entry: initialize the cluster BEFORE building meshes.
        if "COORDINATOR_ADDRESS" in os.environ:
            jax.distributed.initialize()
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg = get_config(args.arch)
    else:
        mesh = make_local_mesh(args.data_par, args.model_par)
        cfg = get_config(args.arch, reduced=True)

    rules = make_rules("train", multi_pod=("pod" in mesh.axis_names))
    ctx = MeshCtx.for_mesh(mesh, "train")
    model = LanguageModel(cfg)
    print(f"[launch] arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"params~{cfg.param_count_estimate()/1e6:.1f}M")

    opt = make_optimizer("adamw", make_schedule(
        "cosine", args.lr, warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps))

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        pspecs = model.pspecs(rules, ctx.axis_sizes)
        shard = lambda t, ps: jax.tree.map(
            lambda x, p: jax.device_put(x, NamedSharding(mesh, p)), t, ps,
            is_leaf=lambda x: hasattr(x, "shape"))
        params = shard(params, pspecs)
        opt_state = opt.init(params)
        step_fn = jax.jit(make_train_step(model, ctx, opt, loss_chunks=4),
                          donate_argnums=(0, 1))

        pipe = BigramPipeline(cfg.vocab_size, args.batch, args.seq, seed=1)
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        batch_sh = {
            "tokens": NamedSharding(mesh, ctx.pspec(
                "batch", "seq", shape=(args.batch, args.seq))),
            "labels": NamedSharding(mesh, ctx.pspec(
                "batch", "seq", shape=(args.batch, args.seq)))}
        out = train_loop(step_fn, params, opt_state, pipe, ckpt,
                         TrainLoopConfig(n_steps=args.steps,
                                         ckpt_every=args.ckpt_every,
                                         log_every=10),
                         batch_shardings=batch_sh, verbose=True)
    losses = [h["loss"] for h in out["history"]]
    if losses:
        print(f"[launch] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
