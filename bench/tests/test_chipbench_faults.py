"""The check that decides ``correct``, driven end to end on the CPU at a
size a test run holds: the harness's look for a chip is skipped, the rest
of a run is not.  A sound run reads correct; the control (the float32
reference at three bf16 passes in the program's place) and each fault the
cell can have, planted in the timed path, read not correct.  The limits
are the cells' own, from their traffic files."""
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from chipbench import fitcheck, harness, refs  # noqa: E402

SEED = 2147483659
TRAIN = {"config": {"n_train": 4096, "n_val": 512, "n_grad": 128,
                    "n_expand": 128, "impl": "ref"},
         "traffic": {"epochs_per_fit": 2, "loss_eval_rows": 512}}
SERVE = {"config": {"n_train": 4096, "serve_support_rows": 3000,
                    "query_block": 256, "impl": "ref"},
         "traffic": {"rate_rps": 200, "query_pool_rows": 4096,
                     "warm_tiles": 2, "size_max": 256, "check_requests": 32}}
# The serving cell waits in bench/pending (PERF.md, Open questions).
SERVE_CELL = "covertype-rbf.serve-poisson"
BENCH_SPEC = harness.with_pending(harness.load_benchmark(), SERVE_CELL)


def _run(cell, overrides):
    import jax

    jax.clear_caches()              # retrace: a planted fault takes effect
    return harness.run_cell(cell, SEED, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            overrides=overrides, bench=BENCH_SPEC)


def test_sound_training_run_is_correct():
    out = _run("covertype-rbf.train", TRAIN)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_training_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.core import trainer

    def frozen(cfg, state, x, y, key, pc=None):
        return state._replace(epoch=state.epoch + 1)

    monkeypatch.setattr(trainer, "_epoch_serial", frozen)
    out = _run("covertype-rbf.train", TRAIN)
    assert not out["correct"]
    assert out["checks"]["alpha_norm_gap_e1"]["value"] == pytest.approx(1.0)


def test_training_step_that_leaves_out_half_the_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.kernels.dsekl import ops

    whole = ops.kernel_dual_pass

    def half(xi, xj, aj, yi, **kw):
        h = xi.shape[0] // 2            # the mean over the half that is left
        f, g = whole(xi[:h], xj, aj, yi[:h], **kw)
        return jnp.concatenate([f, f]), 2.0 * g

    monkeypatch.setattr(ops, "kernel_dual_pass", half)
    out = _run("covertype-rbf.train", TRAIN)
    assert not out["correct"], out["checks"]


def test_training_control_is_not_correct():
    ctx, driver, res, _ = harness.prepare(
        "covertype-rbf.train", SEED, 0.5, False, require_tpu=False,
        overrides=TRAIN)
    driver.setup(ctx)
    want = driver.reference_alphas(ctx, "highest")
    numbers = driver.compare(ctx, driver.reference_alphas(ctx, "high"), want)
    checks = fitcheck.judge(numbers, ctx.traffic["limits"])
    assert checks and not all(c["ok"] for c in checks), checks


def test_sound_serving_run_is_correct():
    out = _run(SERVE_CELL, SERVE)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_p95_ms", "serve_queries_per_s",
                                   "setup_s"}


def test_served_answer_altered_where_it_is_produced(monkeypatch):
    from repro.serving import DSEKLPredictionEngine

    served = DSEKLPredictionEngine._predict_pipelined

    def altered(self, merged, a_sv):
        f = served(self, merged, a_sv)
        return f.at[0].add(0.01)        # the first query of every flush

    monkeypatch.setattr(DSEKLPredictionEngine, "_predict_pipelined", altered)
    out = _run(SERVE_CELL, SERVE)
    assert not out["correct"], out["checks"]


def test_serving_control_is_not_correct():
    import jax.numpy as jnp
    import numpy as np

    ctx, driver, res, _ = harness.prepare(
        SERVE_CELL, SEED, 0.5, False, require_tpu=False, overrides=SERVE,
        bench=BENCH_SPEC)
    driver.setup(ctx)
    st = ctx.stash
    q = jnp.asarray(st["pool"][:1024])
    f_ref, f_abs = refs.ref_decision(q, st["x"], st["alpha"],
                                     gamma=ctx.config["gamma"])
    f_ctl, _ = refs.ref_decision(q, st["x"], st["alpha"],
                                 gamma=ctx.config["gamma"], precision="high")
    numbers = driver.compare(np.asarray(f_ctl), f_ref, f_abs)
    limit = ctx.traffic["limits"]["serve_rel_err"]
    assert numbers["serve_rel_err"] > limit, (numbers, limit)
