"""Driver-equivalence matrix for the unified execution-backend trainer.

The PR-5 acceptance contract (DESIGN.md §9): one backend-agnostic ``fit``
loop drives every ExecutionPlan, and from one PRNG key

  * ``SerialPlan`` / ``ParallelPlan`` (in-memory) and ``HostedPlan``
    (host-resident source, prefetched or sync) produce bit-identical
    ``DSEKLState`` for the same algorithm;
  * ``MeshPlan`` (4 simulated devices) driven through ``fit`` is
    bit-identical to the device-sampling ``make_distributed_step``
    reference loop from the same keys (subprocess test);
  * a checkpoint-interrupted + resumed fit is bit-identical to an
    uninterrupted one, on every backend;
  * the cross-epoch prefetch regression: ONE ``BlockPrefetcher`` (one
    worker thread, one staging-buffer set) serves the whole fit, and its
    gather/wait stats accumulate across epochs.
"""
import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DSEKLConfig, dsekl, fit, trainer
from repro.data import HostSource, make_xor

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _assert_states_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
    np.testing.assert_array_equal(np.asarray(a.accum), np.asarray(b.accum))
    assert int(a.step) == int(b.step)
    assert int(a.epoch) == int(b.epoch)


@pytest.fixture(scope="module")
def xy():
    x, y = make_xor(jax.random.PRNGKey(0), 240)
    return x, y


@pytest.fixture(scope="module")
def src(xy):
    x, y = xy
    return HostSource(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# In-memory vs hosted: same algorithm, bit-identical across placements.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["serial", "parallel"])
def test_matrix_inmemory_hosted_bitidentical(xy, src, algorithm):
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, schedule="adagrad",
                      n_workers=3 if algorithm == "parallel" else 1,
                      impl="ref")
    key = jax.random.PRNGKey(7)
    r_mem = fit(cfg, x, y, key, execution=algorithm, n_epochs=3, tol=0.0)
    r_host = fit(cfg, src, None, key, execution="hosted",
                 algorithm=algorithm, n_epochs=3, tol=0.0)
    r_sync = fit(cfg, src, None, key, execution="hosted",
                 algorithm=algorithm, n_epochs=3, tol=0.0, prefetch=False)
    _assert_states_identical(r_mem.state, r_host.state)
    _assert_states_identical(r_mem.state, r_sync.state)
    # cfg.execution is the config-side selector for the same backends.
    r_cfg = fit(cfg.replace(execution=algorithm), x, y, key, n_epochs=3,
                tol=0.0)
    _assert_states_identical(r_mem.state, r_cfg.state)


@pytest.mark.parametrize("algorithm,kernel,precondition,d", [
    ("serial", "rbf", 0, 54),
    ("serial", "laplacian", 0, 54),
    ("serial", "rbf", 4, 54),
    ("serial", "rbf", 0, 130),
    ("parallel", "rbf", 0, 54),
    ("parallel", "laplacian", 0, 54),
])
def test_lane_padded_rows_bitidentical(monkeypatch, algorithm, kernel,
                                       precondition, d):
    """Gathering from the lane-padded copy of X (what an in-memory plan
    does on a TPU, where X of D % 128 != 0 sits column-major) gives the
    alpha of the unpadded gather bit for bit.  A CPU lays X out row-major,
    so a plain fit uses X as given; the padding is forced here."""
    kx, ky = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (240, d))
    y = jnp.sign(jax.random.normal(ky, (240,)))
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, schedule="adagrad",
                      n_workers=3 if algorithm == "parallel" else 1,
                      kernel=kernel, kernel_params=(("gamma", 0.1),),
                      precondition_k=precondition, impl="ref")
    key = jax.random.PRNGKey(7)
    kw = dict(execution=algorithm, n_epochs=3, tol=0.0)
    r_given = fit(cfg, x, y, key, **kw)
    assert r_given.row_width == d
    monkeypatch.setattr(trainer, "_gather_matrix", dsekl.pad_lanes)
    r_padded = fit(cfg, x, y, key, **kw)
    assert r_padded.row_width == -(-d // 128) * 128
    assert np.any(np.asarray(r_padded.state.alpha) != 0)
    _assert_states_identical(r_given.state, r_padded.state)


def test_execution_resolution_and_errors(xy, src):
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, impl="ref")
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="out of core"):
        fit(cfg, src, None, key, execution="serial", n_epochs=1)
    with pytest.raises(ValueError, match="unknown execution"):
        fit(cfg, x, y, key, execution="banana", n_epochs=1)
    # auto: host source -> hosted (loader stats exist), arrays -> in-memory.
    r = fit(cfg, src, None, key, n_epochs=1, tol=0.0)
    assert r.loader is not None and r.loader["steps"] > 0
    r = fit(cfg, x, y, key, n_epochs=1, tol=0.0)
    assert r.loader is None


# ---------------------------------------------------------------------------
# Checkpoint-resume: interrupted + resumed == uninterrupted, bit for bit.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("execution", ["serial", "parallel", "hosted"])
def test_resume_matches_uninterrupted(xy, src, tmp_path, execution):
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, schedule="adagrad",
                      impl="ref")
    key = jax.random.PRNGKey(3)
    data = (x, y) if execution in ("serial", "parallel") else (src, None)
    kw = dict(execution=execution, n_epochs=4, tol=0.0,
              x_val=x[:40], y_val=y[:40], truncate_every=2)
    r_full = fit(cfg, data[0], data[1], key, **kw)
    d = str(tmp_path / execution)
    fit(cfg, data[0], data[1], key, **{**kw, "n_epochs": 2},
        checkpoint_dir=d)
    r_res = fit(cfg, data[0], data[1], key, **kw, checkpoint_dir=d,
                resume=True)
    _assert_states_identical(r_full.state, r_res.state)
    assert [h["delta_alpha"] for h in r_full.history] == \
           [h["delta_alpha"] for h in r_res.history]
    assert [h.get("val_error") for h in r_full.history] == \
           [h.get("val_error") for h in r_res.history]
    assert r_full.epochs_run == r_res.epochs_run == 4


def test_resume_after_midrun_crash(xy, tmp_path):
    """An actual interruption: the run dies mid-fit (after epoch 2's
    snapshot), and the resumed fit is bit-identical to one that never
    crashed — including the restored history prefix."""
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, schedule="adagrad",
                      impl="ref")
    key = jax.random.PRNGKey(5)
    d = str(tmp_path / "crash")
    r_full = fit(cfg, x, y, key, n_epochs=5, tol=0.0)

    class Boom(RuntimeError):
        pass

    def die_after_two(e, state):
        if e == 2:                      # third epoch: snapshots 1-2 exist
            raise Boom()

    with pytest.raises(Boom):
        fit(cfg, x, y, key, n_epochs=5, tol=0.0, checkpoint_dir=d,
            callback=die_after_two)
    r_res = fit(cfg, x, y, key, n_epochs=5, tol=0.0, checkpoint_dir=d,
                resume=True)
    _assert_states_identical(r_full.state, r_res.state)
    assert len(r_res.history) == 5


def test_resume_after_converged_run_stays_converged(xy, tmp_path):
    """A run that met the stopping rule must not train PAST convergence
    when resumed with the same command — the uninterrupted run stopped
    there, so the resumed one must too (the snapshot carries the
    converged flag)."""
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    key = jax.random.PRNGKey(6)
    d = str(tmp_path / "conv")
    r1 = fit(cfg, x, y, key, n_epochs=8, tol=1e9, checkpoint_dir=d)
    assert r1.converged and r1.epochs_run == 1
    r2 = fit(cfg, x, y, key, n_epochs=8, tol=1e9, checkpoint_dir=d,
             resume=True)
    assert r2.converged and r2.epochs_run == 1
    _assert_states_identical(r1.state, r2.state)


def test_resume_on_empty_dir_is_fresh_start(xy, tmp_path):
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, impl="ref")
    key = jax.random.PRNGKey(1)
    r_plain = fit(cfg, x, y, key, n_epochs=2, tol=0.0)
    r_res = fit(cfg, x, y, key, n_epochs=2, tol=0.0,
                checkpoint_dir=str(tmp_path / "empty"), resume=True)
    _assert_states_identical(r_plain.state, r_res.state)


# ---------------------------------------------------------------------------
# Cross-epoch prefetch: one worker, one buffer set, stats accumulate.
# ---------------------------------------------------------------------------

def test_prefetcher_survives_epoch_boundary(src, monkeypatch):
    """The regression PR 5 fixes: the old drivers spawned (and drained) a
    fresh BlockPrefetcher per epoch.  Now ONE prefetcher — one worker
    thread — serves the whole fit, fed one epoch ahead."""
    made = []
    real = trainer.BlockPrefetcher

    class Counting(real):
        def __init__(self, *a, **kw):
            made.append(self)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trainer, "BlockPrefetcher", Counting)
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    res = fit(cfg, src, None, jax.random.PRNGKey(2), n_epochs=3, tol=0.0)
    assert len(made) == 1, "one prefetcher must serve all epochs"
    steps_per_epoch = max(src.n // cfg.n_grad, 1)
    assert res.loader["steps"] == 3 * steps_per_epoch
    assert res.loader["gather_s"] > 0.0


@pytest.mark.parametrize("prefetch", [True, False])
def test_loader_steps_count_consumed_not_planned(src, prefetch):
    """The driver plans one epoch ahead; on early convergence the queued
    epoch never runs and must NOT inflate FitResult.loader['steps']."""
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    res = fit(cfg, src, None, jax.random.PRNGKey(8), n_epochs=5, tol=1e9,
              prefetch=prefetch)
    assert res.converged and res.epochs_run == 1
    assert res.loader["steps"] == max(src.n // cfg.n_grad, 1)


def test_hosted_plan_thread_identity_across_epochs(src):
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    key = jax.random.PRNGKey(4)
    k1, k2 = jax.random.split(key)
    with trainer.HostedPlan(cfg, src) as plan:
        state = plan.init_state()
        plan.plan_epoch(k1)
        worker = plan._loader._thread
        plan.plan_epoch(k2)                 # planned ahead, same loader
        state = plan.run_epoch(state, k1)
        assert plan._loader._thread is worker and worker.is_alive(), \
            "worker thread must survive the epoch boundary"
        state = plan.run_epoch(state, k2)
        assert plan._loader._thread is worker
        st = plan.loader_stats()
        assert st["steps"] == 2 * max(src.n // cfg.n_grad, 1)
    assert not worker.is_alive()            # close() joins it

    # Consuming epochs out of plan order would desync the stream: refuse.
    with trainer.HostedPlan(cfg, src) as plan2:
        plan2.plan_epoch(k1)
        plan2.plan_epoch(k2)
        with pytest.raises(RuntimeError, match="order"):
            plan2.run_epoch(plan2.init_state(), k2)


# ---------------------------------------------------------------------------
# Mesh overlap on the (1, 1) mesh: the full MeshPrefetcher machinery runs
# in the fast lane without forced devices.
# ---------------------------------------------------------------------------

def test_mesh_overlap_singledevice_bitidentical(src):
    """prefetch=True (MeshPrefetcher, pre-placed blocks) and
    prefetch=False (SyncMeshGather, inline H2D) must produce the same
    bits; only the overlapped loader hides gather time."""
    from repro.launch.mesh import make_local_mesh

    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    mesh = make_local_mesh(1, 1)
    key = jax.random.PRNGKey(6)
    r_pre = fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                n_epochs=3, tol=0.0)
    r_inl = fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                n_epochs=3, tol=0.0, prefetch=False)
    _assert_states_identical(r_pre.state, r_inl.state)
    steps = 3 * max(src.n // cfg.n_grad, 1)
    for r in (r_pre, r_inl):
        assert r.loader is not None and r.loader["steps"] == steps
    # the inline arm hides nothing, by construction
    assert r_inl.loader["wait_s"] == r_inl.loader["gather_s"]
    assert r_pre.loader["gather_s"] > 0.0


def test_mesh_plan_order_and_thread_identity(src):
    """MeshPlan mirrors HostedPlan's cross-epoch loader contract: ONE
    worker across planned-ahead epochs, refusal to consume out of
    order."""
    from repro.launch.mesh import make_local_mesh

    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    mesh = make_local_mesh(1, 1)
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    with trainer.MeshPlan(cfg, src, mesh) as plan:
        state = plan.init_state()
        plan.plan_epoch(k1)
        worker = plan._loader._thread
        plan.plan_epoch(k2)
        state = plan.run_epoch(state, k1)
        assert plan._loader._thread is worker and worker.is_alive()
        state = plan.run_epoch(state, k2)
        st = plan.loader_stats()
        assert st["steps"] == 2 * plan.steps_per_epoch
    assert not worker.is_alive()

    with trainer.MeshPlan(cfg, src, mesh) as plan2:
        plan2.plan_epoch(k1)
        plan2.plan_epoch(k2)
        with pytest.raises(RuntimeError, match="order"):
            plan2.run_epoch(plan2.init_state(), k2)


def test_mesh_place_state_rejects_different_n(src):
    """The elastic-rescale guard: resuming a checkpoint whose alpha row
    count differs from this fit's (trimmed) N is a different problem —
    refuse loudly instead of silently training garbage."""
    from repro.launch.mesh import make_local_mesh

    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, impl="ref")
    with trainer.MeshPlan(cfg, src, make_local_mesh(1, 1)) as plan:
        flat = {"alpha": np.zeros(src.n - 2, np.float32),
                "accum": np.zeros(src.n - 2, np.float32),
                "step": np.int32(0), "epoch": np.int32(0)}
        with pytest.raises(ValueError, match="row count identical"):
            plan.place_state(flat)


def test_mesh_fit_from_manifest_source_matches_hostsource(tmp_path):
    """Multi-host resume plumbing: a fit fed from range-mapping
    ManifestSource views is bit-identical to the same fit over a plain
    HostSource — and the root manifest view never maps the full file."""
    from repro.data import ManifestSource, make_memmap_dataset
    from repro.launch.mesh import make_local_mesh

    make_memmap_dataset(str(tmp_path), 256, 8, seed=2)
    cfg = DSEKLConfig(n_grad=32, n_expand=16, lam=1e-4, impl="ref")
    mesh = make_local_mesh(1, 1)
    key = jax.random.PRNGKey(5)
    ms = ManifestSource(str(tmp_path))
    r_ms = fit(cfg, ms, None, key, execution="mesh", mesh=mesh,
               n_epochs=2, tol=0.0)
    assert not ms.mapped, "mesh fit must read through per-shard views only"
    from repro.data import open_memmap_dataset
    hs = open_memmap_dataset(str(tmp_path))
    r_hs = fit(cfg, hs, None, key, execution="mesh", mesh=mesh,
               n_epochs=2, tol=0.0)
    _assert_states_identical(r_ms.state, r_hs.state)


# ---------------------------------------------------------------------------
# MeshPlan: 4 simulated devices, driven end to end through fit.
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
def test_mesh_plan_matrix_subprocess():
    """fit(execution='mesh') on a (2, 2) mesh must be bit-identical to the
    device-sampling ``make_distributed_step`` reference loop from the
    same keys; mesh resume must be bit-identical to uninterrupted; the
    psum'd eval must match the single-device decision function."""
    script = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import DSEKLConfig, fit, dsekl
        from repro.core import distributed as dist
        from repro.data import make_xor, HostSource
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(2, 2)
        x, y = make_xor(jax.random.PRNGKey(0), 256)
        src = HostSource(np.asarray(x), np.asarray(y))
        cfg = DSEKLConfig(n_grad=16, n_expand=16, lam=1e-4,
                          schedule="adagrad", impl="ref")
        key = jax.random.PRNGKey(7)

        # 1) fit-driven MeshPlan == device-sampling reference loop.
        r = fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                n_epochs=2, tol=0.0, x_val=x[:48], y_val=y[:48])
        step = dist.make_distributed_step(cfg, mesh, 256)
        xg, yg, xe = dist.shard_inputs(mesh, x, y)
        st = dist.init_sharded_state(mesh, 256)
        steps_per_epoch = max(256 // (cfg.n_grad * 2), 1)
        k = key
        for e in range(2):
            k, sub = jax.random.split(k)
            for kk in jax.random.split(sub, steps_per_epoch):
                st = step(xg, yg, xe, st, kk)
        np.testing.assert_array_equal(np.asarray(r.state.alpha),
                                      np.asarray(st.alpha))
        np.testing.assert_array_equal(np.asarray(r.state.accum),
                                      np.asarray(st.accum))
        assert int(r.state.step) == int(st.step) == 2 * steps_per_epoch

        # 2) mesh checkpoint-resume == uninterrupted, bit for bit.
        with tempfile.TemporaryDirectory() as d:
            fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                n_epochs=1, tol=0.0, checkpoint_dir=d)
            r_res = fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                        n_epochs=2, tol=0.0, checkpoint_dir=d, resume=True)
        np.testing.assert_array_equal(np.asarray(r.state.alpha),
                                      np.asarray(r_res.state.alpha))
        np.testing.assert_array_equal(np.asarray(r.state.accum),
                                      np.asarray(r_res.state.accum))

        # 3) psum'd eval == single-device decision function.
        ev = dist.make_mesh_eval(cfg, mesh, chunk=48)
        f_mesh = ev(r.state.alpha, src.split(2), x[:48])
        f_ref = dsekl.decision_function(
            cfg, jnp.asarray(np.asarray(r.state.alpha)), x, x[:48])
        np.testing.assert_allclose(np.asarray(f_mesh), np.asarray(f_ref),
                                   rtol=1e-5, atol=1e-6)
        print("MESH_MATRIX_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "MESH_MATRIX_OK" in out.stdout

@pytest.mark.slow
@pytest.mark.distributed
def test_mesh_overlap_matrix_subprocess():
    """The overlapped mesh data plane on 4 devices: prefetch == inline ==
    the device-sampling reference, bit for bit, with a REAL hidden-gather
    fraction (not the inline arm's wait==gather); the pre-placed blocks
    keep precond fits identical too."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import DSEKLConfig, fit
        from repro.core import distributed as dist
        from repro.data import make_xor, HostSource
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(2, 2)
        x, y = make_xor(jax.random.PRNGKey(0), 256)
        src = HostSource(np.asarray(x), np.asarray(y))
        cfg = DSEKLConfig(n_grad=16, n_expand=16, lam=1e-4,
                          schedule="adagrad", impl="ref")
        key = jax.random.PRNGKey(7)

        r_pre = fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                    n_epochs=2, tol=0.0)
        r_inl = fit(cfg, src, None, key, execution="mesh", mesh=mesh,
                    n_epochs=2, tol=0.0, prefetch=False)
        np.testing.assert_array_equal(np.asarray(r_pre.state.alpha),
                                      np.asarray(r_inl.state.alpha))
        np.testing.assert_array_equal(np.asarray(r_pre.state.accum),
                                      np.asarray(r_inl.state.accum))

        step = dist.make_distributed_step(cfg, mesh, 256)
        xg, yg, xe = dist.shard_inputs(mesh, x, y)
        st = dist.init_sharded_state(mesh, 256)
        spe = max(256 // (cfg.n_grad * 2), 1)
        k = key
        for e in range(2):
            k, sub = jax.random.split(k)
            for kk in jax.random.split(sub, spe):
                st = step(xg, yg, xe, st, kk)
        np.testing.assert_array_equal(np.asarray(r_pre.state.alpha),
                                      np.asarray(st.alpha))

        ld = r_pre.loader
        hidden = max(0.0, 1.0 - ld["wait_s"] / max(ld["gather_s"], 1e-12))
        assert ld["steps"] == 2 * spe, ld
        assert ld["gather_s"] > 0.0 and hidden > 0.0, ld
        ld_i = r_inl.loader
        assert ld_i["wait_s"] == ld_i["gather_s"], ld_i

        cfg_pc = cfg.replace(precondition_k=4)
        r_pc = fit(cfg_pc, src, None, key, execution="mesh", mesh=mesh,
                   n_epochs=2, tol=0.0)
        r_pc_i = fit(cfg_pc, src, None, key, execution="mesh", mesh=mesh,
                     n_epochs=2, tol=0.0, prefetch=False)
        np.testing.assert_array_equal(np.asarray(r_pc.state.alpha),
                                      np.asarray(r_pc_i.state.alpha))
        print("MESH_OVERLAP_OK hidden=%.3f" % hidden)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "MESH_OVERLAP_OK" in out.stdout


@pytest.mark.slow
@pytest.mark.distributed
def test_mesh_elastic_rescale_subprocess():
    """Elastic rescale: checkpoint on a (4, 1) mesh, resume on (2, 1).
    Mesh sampling is mesh-shape-dependent, so the contract is: every
    continuation FROM THE SAME CHECKPOINT on mesh B lands on the same
    bits — a twice-interrupted resume equals a once-interrupted one, and
    the post-resume epochs equal a device-sampling loop on mesh B from
    the restored state and key."""
    script = textwrap.dedent("""
        import os, shutil, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.checkpoint import CheckpointManager
        from repro.core import DSEKLConfig, fit
        from repro.core import distributed as dist
        from repro.data import make_xor, HostSource
        from repro.launch.mesh import make_local_mesh

        mesh_a, mesh_b = make_local_mesh(4, 1), make_local_mesh(2, 1)
        x, y = make_xor(jax.random.PRNGKey(0), 256)
        src = HostSource(np.asarray(x), np.asarray(y))
        cfg = DSEKLConfig(n_grad=16, n_expand=16, lam=1e-4,
                          schedule="adagrad", impl="ref")
        key = jax.random.PRNGKey(7)

        with tempfile.TemporaryDirectory() as d:
            fit(cfg, src, None, key, execution="mesh", mesh=mesh_a,
                n_epochs=2, tol=0.0, checkpoint_dir=d)
            d2 = d + "_b"; shutil.copytree(d, d2)
            # snapshot the mesh-A checkpoint BEFORE the resumes below
            # add (and retention prunes) checkpoints
            man = CheckpointManager(d)
            assert man.latest_valid_step() == 2
            _, flat, _ = man.restore(2)
            # resume the mesh-A checkpoint on mesh B, straight to the end
            r1 = fit(cfg, src, None, key, execution="mesh", mesh=mesh_b,
                     n_epochs=5, tol=0.0, checkpoint_dir=d, resume=True)
            assert len(r1.state.alpha.sharding.device_set) == 2
            # interrupt AGAIN mid-way on mesh B, then resume
            fit(cfg, src, None, key, execution="mesh", mesh=mesh_b,
                n_epochs=4, tol=0.0, checkpoint_dir=d2, resume=True)
            r2 = fit(cfg, src, None, key, execution="mesh", mesh=mesh_b,
                     n_epochs=5, tol=0.0, checkpoint_dir=d2, resume=True)
            np.testing.assert_array_equal(np.asarray(r1.state.alpha),
                                          np.asarray(r2.state.alpha))
            np.testing.assert_array_equal(np.asarray(r1.state.accum),
                                          np.asarray(r2.state.accum))

            # oracle: device-sampling steps on mesh B from the restored
            # checkpoint reproduce the resumed epochs bit for bit
            step = dist.make_distributed_step(cfg, mesh_b, 256)
            xg, yg, xe = dist.shard_inputs(mesh_b, x, y)
            st = dist.init_sharded_state(mesh_b, 256)
            sh = st.alpha.sharding
            st = dist.ShardedDSEKLState(
                alpha=jax.device_put(np.asarray(flat["alpha"]), sh),
                accum=jax.device_put(np.asarray(flat["accum"]), sh),
                step=jnp.asarray(flat["step"], jnp.int32),
                epoch=jnp.asarray(flat["epoch"], jnp.int32))
            k = jnp.asarray(flat["key"])
            spe = max(256 // (cfg.n_grad * 2), 1)
            for e in range(3):
                k, sub = jax.random.split(k)
                for kk in jax.random.split(sub, spe):
                    st = step(xg, yg, xe, st, kk)
            np.testing.assert_array_equal(np.asarray(r1.state.alpha),
                                          np.asarray(st.alpha))
        print("ELASTIC_RESCALE_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "ELASTIC_RESCALE_OK" in out.stdout


# ---------------------------------------------------------------------------
# Launcher kill-and-resume: SIGKILL mid-run, resume, bit-identical final
# checkpoint.
# ---------------------------------------------------------------------------

def _launcher_cmd(ckpt_dir, epochs, resume=False, mesh=None):
    cmd = [sys.executable, "-m", "repro.launch.train", "--dsekl",
           "--n", "4000", "--dim", "16", "--epochs", str(epochs),
           "--n-grad", "64", "--n-expand", "64",
           "--checkpoint-dir", ckpt_dir]
    if mesh is not None:
        cmd += ["--execution", "mesh",
                "--data-par", str(mesh[0]), "--model-par", str(mesh[1])]
    if resume:
        cmd.append("--resume")
    return cmd


def _final_checkpoint(ckpt_dir):
    from repro.checkpoint import CheckpointManager

    man = CheckpointManager(ckpt_dir)
    step = man.latest_valid_step()
    assert step is not None, f"no valid checkpoint in {ckpt_dir}"
    return man.restore(step)


@pytest.mark.slow
def test_launcher_kill_and_resume(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    d_full = str(tmp_path / "full")
    d_kill = str(tmp_path / "kill")
    epochs = 6

    out = subprocess.run(_launcher_cmd(d_full, epochs), env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"

    # Start the same run, SIGKILL it once the first valid checkpoint
    # lands, then resume to completion.
    proc = subprocess.Popen(_launcher_cmd(d_kill, epochs), env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    from repro.checkpoint import CheckpointManager
    man = CheckpointManager(d_kill)
    deadline = time.time() + 300
    killed = False
    while time.time() < deadline:
        if proc.poll() is not None:
            break                       # finished before we could kill it
        if man.latest_valid_step() is not None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=60)
            killed = True
            break
        time.sleep(0.05)
    assert killed, "launcher finished before any checkpoint appeared"
    assert proc.returncode not in (0, None)

    out = subprocess.run(_launcher_cmd(d_kill, epochs, resume=True),
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "resumed at epoch" in out.stdout

    step_f, flat_f, extra_f = _final_checkpoint(d_full)
    step_k, flat_k, extra_k = _final_checkpoint(d_kill)
    assert step_f == step_k == epochs
    for name in ("alpha", "accum", "step", "epoch", "key"):
        np.testing.assert_array_equal(flat_f[name], flat_k[name],
                                      err_msg=f"checkpoint leaf {name!r}")
    assert [h["delta_alpha"] for h in extra_f["history"]] == \
           [h["delta_alpha"] for h in extra_k["history"]]


@pytest.mark.slow
@pytest.mark.distributed
def test_launcher_mesh_kill_and_resume(tmp_path):
    """SIGKILL a mesh launcher mid-run WITH THE OVERLAP ON (prefetch is
    the default) and resume on the same (2, 2) shape: the final
    checkpoint must match an uninterrupted run leaf for leaf.  The
    prefetcher's in-flight plan dies with the process; resume replans
    from the checkpointed key, which is the whole crash contract."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    env["REPRO_FORCE_DEVICES"] = "4"
    d_full = str(tmp_path / "full")
    d_kill = str(tmp_path / "kill")
    epochs, mesh = 6, (2, 2)

    out = subprocess.run(_launcher_cmd(d_full, epochs, mesh=mesh), env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"

    proc = subprocess.Popen(_launcher_cmd(d_kill, epochs, mesh=mesh),
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    from repro.checkpoint import CheckpointManager
    man = CheckpointManager(d_kill)
    deadline = time.time() + 300
    killed = False
    while time.time() < deadline:
        if proc.poll() is not None:
            break
        if man.latest_valid_step() is not None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=60)
            killed = True
            break
        time.sleep(0.05)
    assert killed, "launcher finished before any checkpoint appeared"
    assert proc.returncode not in (0, None)

    out = subprocess.run(_launcher_cmd(d_kill, epochs, resume=True,
                                       mesh=mesh),
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "resumed at epoch" in out.stdout

    step_f, flat_f, _ = _final_checkpoint(d_full)
    step_k, flat_k, _ = _final_checkpoint(d_kill)
    assert step_f == step_k == epochs
    for name in ("alpha", "accum", "step", "epoch", "key"):
        np.testing.assert_array_equal(flat_f[name], flat_k[name],
                                      err_msg=f"checkpoint leaf {name!r}")
