"""The four-chip mesh cell (``higgs-rbf-mesh4.train``) run end to end on four
forced host devices at a size a test run holds, in a subprocess (jax fixes
the device count at its first use): the harness's look for a chip is
skipped, the rest of a run is not.  A sound run reads correct and compiles
nothing inside its window; each fault planted in the timed path reads not
correct.  The limits are the cell's own, from its traffic file.

The control (the float32 reference at three bf16 passes in the program's
place) moves the compared numbers through the hinge decisions its
rounding flips.  On the chip, at 10.5 M rows and 2 x 2,563 steps, that
puts it above the limits (PERF.md, section 2); at the sizes a test run
holds it flips none, so here it is held to read far above the sound
program, not to the limits."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "higgs-rbf-mesh4.train"
FAULTS = ("per_step_rate", "no_exchange", "half_batch")

_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, %(bench)r)
    import jax, jax.numpy as jnp
    from chipbench import fitcheck, harness
    from repro.core import distributed as dist

    CELL, SEED = %(cell)r, 2147483659
    SMALL = {"config": {"n_train": 8192, "n_grad": 64, "n_expand": 64,
                        "impl": "ref"},
             "traffic": {"epochs_per_fit": 2, "loss_eval_rows": 512}}

    def run():
        jax.clear_caches()          # retrace: a planted fault takes effect
        out = harness.run_cell(CELL, SEED, 0.5, False,
                               t_start=time.perf_counter(),
                               require_tpu=False, overrides=SMALL)
        return {k: out[k] for k in ("correct", "checks", "metrics",
                                    "device", "attempted", "failed")}

    res = {"sound": run()}
    apply, grad_v = dist._apply_shard_update, dist._shard_block_grad_v
    grad = dist._shard_block_grad

    # Fault: the step count t read as the epoch (lr0 / t under inv_epoch).
    dist._apply_shard_update = (
        lambda c, a, acc, step, epoch, idx, g: apply(c, a, acc, step,
                                                     step + 1, idx, g))
    res["per_step_rate"] = run()
    dist._apply_shard_update = apply

    # No data-axis exchange: the gradient psum runs over the model axis,
    # of size 1, so each data shard applies its own gradient alone.
    dist._shard_block_grad = (
        lambda *a, data_axis, model_axis: grad(
            *a, data_axis=model_axis, model_axis=model_axis))
    res["no_exchange"] = run()
    dist._shard_block_grad = grad

    # Half of each shard's batch, the data part of the sum scaled up.
    def half(cfg, n, xi, yi, xj, aj, key, **axes):
        h = xi.shape[0] // 2
        g, v = grad_v(cfg, n, xi[:h], yi[:h], xj, aj, key, **axes)
        return 2.0 * g - cfg.lam * aj, jnp.concatenate([v, v])
    dist._shard_block_grad_v = half
    res["half_batch"] = run()
    dist._shard_block_grad_v = grad_v
    jax.clear_caches()

    ctx, driver, _, _ = harness.prepare(CELL, SEED, 0.5, False,
                                        require_tpu=False, overrides=SMALL)
    driver.setup(ctx)
    want = driver.reference_alphas(ctx, "highest")
    numbers = driver.compare(ctx, driver.reference_alphas(ctx, "high"), want)
    res["control"] = {"checks": {c["name"]: c for c in fitcheck.judge(
        numbers, ctx.traffic["limits"])}}
    print("MESH_CELL " + json.dumps(res))
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"bench": BENCH, "cell": CELL}],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith("MESH_CELL "))
    res = json.loads(line[len("MESH_CELL "):])
    # The sound run is the first; its window logs its compilations.
    windows = [ln for ln in p.stderr.splitlines()
               if "compilations inside the window" in ln]
    res["sound"]["window_log"] = windows[0]
    return res


def test_sound_mesh_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                             "memory_peak_bytes": 0}
    assert set(out["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert out["metrics"]["train_rows_per_s"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0


def test_sound_mesh_window_compiles_nothing(runs):
    assert "; 0 compilations inside the window" in runs["sound"]["window_log"]


@pytest.mark.parametrize("fault", FAULTS)
def test_mesh_fault_planted_in_the_timed_path_is_not_correct(runs, fault):
    out = runs[fault]
    assert not out["correct"], out["checks"]


def test_mesh_control_reads_far_above_the_program(runs):
    control, sound = runs["control"]["checks"], runs["sound"]["checks"]
    for name in ("alpha_norm_gap_e1", "alpha_norm_gap_e2"):
        assert control[name]["value"] > 100 * sound[name]["value"], \
            (name, control[name], sound[name])
