"""Traffic kind ``mesh_fit``: data-parallel DSEKL training on a mesh of the
cell's chips through ``repro.core.fit(execution="mesh")``.

Set-up makes the data on the device from the seed (``chipbench.higgs``)
and brings it to host memory once: the fit reads it through a
``HostSource``, as a deployment with X in host memory does.  The mesh is
the cell's chips as (data = ``mesh_data``, model = ``mesh_model``).  One
fit (the fit key of index 0) runs its first ``check_epochs`` epochs with
the same call, source, mesh and compiled step the window uses; the alpha
after each of those epochs is what ``check`` compares with the float32
mesh reference (``chipbench.refs_mesh``), computed on the first chip from
a device copy of the same rows.  That fit also compiles every program
the window runs.

The window runs ``fit(execution="mesh")`` back to back, each for the
traffic's ``epochs_per_fit``, with fit keys 1, 2, ...; every epoch end is
timestamped through fit's ``on_epoch`` hook, which also ends the fit in
progress once the window's length has passed.  An epoch is
N // (n_grad n_data) steps, each of n_data x n_grad gradient rows, so
``train_rows_per_s`` is n_data x n_grad x steps of every epoch completed,
over the time from the window's start to the last epoch's end.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import fitcheck, higgs, program, refs_mesh
from chipbench.harness import log


def _mesh(ctx):
    from jax.sharding import Mesh

    conf = ctx.config
    devs = np.array(ctx.devices).reshape(conf["mesh_data"], conf["mesh_model"])
    return Mesh(devs, ("data", "model"))


def _steps(conf):
    return max(conf["n_train"] // (conf["n_grad"] * conf["mesh_data"]), 1)


def setup(ctx):
    from repro.core import fit
    from repro.data import HostSource

    conf, tr = ctx.config, ctx.traffic
    if conf["mesh_model"] != 1:
        raise ValueError("the mesh reference covers one model shard")
    if conf["mesh_data"] * conf["mesh_model"] != ctx.chips:
        raise ValueError(f"mesh {conf['mesh_data']} x {conf['mesh_model']} "
                         f"is not the cell's {ctx.chips} chips")
    data_key = program.seed_key(ctx.seed, 1)
    x, y = higgs.higgs_like(data_key, n=conf["n_train"],
                            d=conf["n_features"], stream=0)
    xv, yv = higgs.higgs_like(data_key, n=tr["loss_eval_rows"],
                              d=conf["n_features"], stream=1)
    src = HostSource(np.asarray(x), np.asarray(y))
    ctx.mark("data made and in host memory")
    cfg = program.dsekl_config(conf, conf["n_train"])
    mesh = _mesh(ctx)
    kfit = program.seed_key(ctx.seed, 2)
    alphas = []
    fit(cfg, src, None, jax.random.fold_in(kfit, 0), execution="mesh",
        mesh=mesh, prefetch=conf["prefetch"], n_epochs=tr["check_epochs"],
        tol=0.0, callback=lambda e, st: alphas.append(np.asarray(st.alpha)))
    ctx.mark("check fit done")
    ctx.stash.update(x=x, y=y, xv=xv, yv=yv, src=src, cfg=cfg, mesh=mesh,
                     kfit=kfit, prog_alphas=alphas)


def window(ctx):
    from repro.core import fit

    conf, tr, st = ctx.config, ctx.traffic, ctx.stash
    steps = _steps(conf)
    marks = []
    t0 = time.perf_counter()
    deadline = t0 + ctx.length

    def on_epoch(_epoch, _state, _rec):
        marks.append(time.perf_counter())
        return marks[-1] >= deadline

    hist_s, wait_s, not_ready, i = 0.0, 0.0, 0, 1
    while not marks or marks[-1] < deadline:
        with jax.profiler.TraceAnnotation("chipbench.fit"):
            res = fit(st["cfg"], st["src"], None,
                      jax.random.fold_in(st["kfit"], i), execution="mesh",
                      mesh=st["mesh"], prefetch=conf["prefetch"],
                      n_epochs=tr["epochs_per_fit"], tol=0.0,
                      on_epoch=on_epoch)
        hist_s += sum(h["seconds"] for h in res.history)
        wait_s += res.loader["wait_s"]
        not_ready += int(res.loader.get("not_ready", 0))
        i += 1
    ctx.window_s = marks[-1] - t0
    epochs = len(marks)
    st.update(attempted=epochs, failed=0, fits=i - 1, steps=epochs * steps,
              n_data=conf["mesh_data"], history_s=hist_s,
              block=(conf["n_grad"], conf["n_expand"], conf["n_features"]))
    log(f"window: {epochs} epochs of {steps} steps in {i - 1} fits; "
        f"prefetcher wait {wait_s:.3f}s, {not_ready} steps not ready")
    return {"train_rows_per_s": epochs * steps * conf["mesh_data"]
            * conf["n_grad"] / ctx.window_s}


def release(ctx):
    for k in ("cfg", "src", "mesh"):
        ctx.stash.pop(k, None)


def reference_alphas(ctx, precision="highest", **fault):
    """The reference's alphas after each checked epoch; ``fault`` plants
    one of ``refs_mesh``'s faults (calibration only)."""
    conf, st = ctx.config, ctx.stash
    return refs_mesh.ref_mesh_fit_epochs(
        st["x"], st["y"], jax.random.fold_in(st["kfit"], 0),
        ctx.traffic["check_epochs"], n_data=conf["mesh_data"],
        n_grad=conf["n_grad"], n_expand=conf["n_expand"], gamma=conf["gamma"],
        lam=conf["lam_times_n"] / conf["n_train"], lr0=conf["lr0"],
        schedule=conf["schedule"], loss=conf["loss"], kernel=conf["kernel"],
        precision=precision, **fault)


def fault_alphas(ctx):
    """Faults planted in the reference put in the program's place, for
    calibration: the per-step rate lr0 / t, no data-axis exchange of the
    gradient, and half of each shard's batch."""
    return {"fault_per_step_rate": reference_alphas(ctx, rate="per_step"),
            "fault_no_exchange": reference_alphas(ctx, exchange=False),
            "fault_half_batch": reference_alphas(
                ctx, grad_rows=ctx.config["n_grad"] // 2)}


# Rows of the support ``compare`` keeps are counted up to a multiple of
# this, so that the reference's decision function compiles for a few row
# counts and not for each comparison's own.
SUPPORT_ROUND = 1 << 19


def compare(ctx, got, want):
    """The numbers ``check`` holds to limits (``chipbench.fitcheck``), over
    the rows where some alpha compared is nonzero (and a few more, with
    alpha 0): the others add nothing to a norm or to a decision value,
    and an early epoch touches well under half of N."""
    st, m = ctx.stash, ctx.traffic["loss_eval_rows"]
    alphas = jnp.stack([jnp.asarray(a) for a in list(got) + list(want)])
    x = st["x"]
    used = jnp.nonzero(jnp.any(alphas != 0, axis=0))[0]
    rows = -(-max(int(used.shape[0]), 1) // SUPPORT_ROUND) * SUPPORT_ROUND
    if rows < x.shape[0]:
        idx = jnp.zeros((rows,), used.dtype).at[:used.shape[0]].set(used)
        alphas = jnp.where(jnp.arange(rows) < used.shape[0],
                           alphas[:, idx], 0.0)
        x = x[idx]
    e = len(got)
    return fitcheck.compare(list(alphas[:e]), list(alphas[e:]), x,
                            st["xv"][:m], st["yv"][:m], ctx.config["gamma"])


def check(ctx):
    numbers = compare(ctx, ctx.stash["prog_alphas"], reference_alphas(ctx))
    log(f"compared: {numbers}")
    return fitcheck.judge(numbers, ctx.traffic["limits"])
