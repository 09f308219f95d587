"""Traffic kind ``fit``: DSEKL training through ``repro.core.fit``.

Set-up makes the data on the device from the seed and drives one fit
(the fit key of index 0) through its first ``check_epochs`` epochs with
the same call, data and compiled epoch program the window uses; the
alpha after each of those epochs is what ``check`` compares with the
float32 reference.  That fit also compiles every program the window runs.

The window runs ``fit(execution="serial")`` back to back, each for the
traffic's ``epochs_per_fit``, with fit keys 1, 2, ...; every epoch end is
timestamped through fit's ``on_epoch`` hook, which also ends the fit in
progress once the window's length has passed.  ``train_rows_per_s`` is
n_grad x steps of every epoch completed, over the time from the window's
start to the last epoch's end.
"""
from __future__ import annotations

import time

import jax

from chipbench import fitcheck, program, refs
from chipbench.harness import log


def setup(ctx):
    from repro.core import fit

    conf, tr = ctx.config, ctx.traffic
    n, n_val = conf["n_train"], conf["n_val"]
    x, y = program.rows(conf, ctx.seed, n, stream=0)
    xv, yv = program.rows(conf, ctx.seed, n_val, stream=1)
    jax.block_until_ready((x, y, xv, yv))
    ctx.mark("data made")
    cfg = program.dsekl_config(conf, n)
    kfit = program.seed_key(ctx.seed, 2)
    alphas = []
    fit(cfg, x, y, jax.random.fold_in(kfit, 0), execution="serial",
        n_epochs=tr["check_epochs"], tol=0.0,
        callback=lambda e, st: alphas.append(st.alpha))
    jax.block_until_ready(alphas)
    ctx.mark("check fit done")
    ctx.stash.update(x=x, y=y, xv=xv, yv=yv, cfg=cfg, kfit=kfit,
                     prog_alphas=alphas)


def window(ctx):
    from repro.core import fit

    conf, tr, st = ctx.config, ctx.traffic, ctx.stash
    steps = max(conf["n_train"] // conf["n_grad"], 1)
    marks = []
    t0 = time.perf_counter()
    deadline = t0 + ctx.length

    def on_epoch(_epoch, _state, _rec):
        marks.append(time.perf_counter())
        return marks[-1] >= deadline

    hist_s, i = 0.0, 1
    while not marks or marks[-1] < deadline:
        with jax.profiler.TraceAnnotation("chipbench.fit"):
            res = fit(st["cfg"], st["x"], st["y"],
                      jax.random.fold_in(st["kfit"], i), execution="serial",
                      n_epochs=tr["epochs_per_fit"], tol=0.0,
                      on_epoch=on_epoch)
        hist_s += sum(h["seconds"] for h in res.history)
        i += 1
    ctx.window_s = marks[-1] - t0
    epochs = len(marks)
    st.update(attempted=epochs, failed=0, fits=i - 1, steps=epochs * steps,
              history_s=hist_s,
              block=(conf["n_grad"], conf["n_expand"], conf["n_features"]))
    return {"train_rows_per_s": epochs * steps * conf["n_grad"]
            / ctx.window_s}


def release(ctx):
    ctx.stash.pop("cfg", None)


def reference_alphas(ctx, precision="highest", grad_rows=None):
    """The reference's alphas after each checked epoch; ``grad_rows`` below
    n_grad plants the half-batch fault (calibration only)."""
    conf, st = ctx.config, ctx.stash
    return refs.ref_fit_epochs(
        st["x"], st["y"], jax.random.fold_in(st["kfit"], 0),
        ctx.traffic["check_epochs"], n_grad=conf["n_grad"],
        n_expand=conf["n_expand"], gamma=conf["gamma"],
        lam=conf["lam_times_n"] / conf["n_train"], lr0=conf["lr0"],
        schedule=conf["schedule"], loss=conf["loss"], kernel=conf["kernel"],
        precision=precision, grad_rows=grad_rows)


def fault_alphas(ctx):
    """Faults planted in the reference put in the program's place, for
    calibration: half of each step's gradient rows left out, the sum
    scaled up over the rest."""
    half = ctx.config["n_grad"] // 2
    return {"fault_half_batch": reference_alphas(ctx, grad_rows=half)}


def compare(ctx, got, want):
    """The numbers ``check`` holds to limits (``chipbench.fitcheck``)."""
    st, m = ctx.stash, ctx.traffic["loss_eval_rows"]
    return fitcheck.compare(got, want, st["x"], st["xv"][:m], st["yv"][:m],
                            ctx.config["gamma"])


def check(ctx):
    numbers = compare(ctx, ctx.stash["prog_alphas"], reference_alphas(ctx))
    log(f"compared: {numbers}")
    return fitcheck.judge(numbers, ctx.traffic["limits"])
