"""Out-of-core training parity: HostSource == in-memory, bit for bit.

The data-plane acceptance tests (DESIGN.md §8):
  * ``fit`` over a ``HostSource`` produces the bit-identical ``DSEKLState``
    the in-memory path produces for the same PRNG key — serial and
    parallel algorithms, on both CPU-runnable kernel-op backends;
  * the block-parametrized gradient core compiles ONCE across datasets
    with different N (the compile-count / no-retrace contract);
  * the streamed source decision function matches the device-resident one;
  * the solver's error metric and the prediction engine agree on the
    decision rule, including exactly-zero decision values;
  * the mesh block step fed by per-shard host sources is bit-identical to
    the device-sampling mesh step (subprocess, 8 forced host devices).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DSEKLConfig, dsekl, fit, solver
from repro.data import HostSource, make_xor


def _assert_states_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
    np.testing.assert_array_equal(np.asarray(a.accum), np.asarray(b.accum))
    assert int(a.step) == int(b.step)
    assert int(a.epoch) == int(b.epoch)


@pytest.fixture(scope="module")
def xy():
    x, y = make_xor(jax.random.PRNGKey(0), 240)
    return x, y


@pytest.mark.parametrize("algorithm", ["serial", "parallel"])
@pytest.mark.parametrize("impl,kernel,params", [
    ("ref", "rbf", (("gamma", 1.0),)),
    ("ref", "laplacian", (("gamma", 0.5),)),
    ("pallas_interpret", "rbf", (("gamma", 1.0),)),
])
def test_hostsource_bit_identical_to_inmemory(xy, algorithm, impl, kernel,
                                              params):
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, kernel=kernel,
                      kernel_params=params, lam=1e-4, schedule="adagrad",
                      n_workers=3 if algorithm == "parallel" else 1,
                      impl=impl)
    key = jax.random.PRNGKey(7)
    r_mem = fit(cfg, x, y, key, algorithm=algorithm, n_epochs=2, tol=0.0)
    src = HostSource(np.asarray(x), np.asarray(y))
    r_host = fit(cfg, src, None, key, algorithm=algorithm, n_epochs=2,
                 tol=0.0)
    _assert_states_identical(r_mem.state, r_host.state)
    assert r_host.loader is not None and r_host.loader["steps"] > 0
    # the synchronous-gather baseline walks the identical plan
    r_sync = fit(cfg, src, None, key, algorithm=algorithm, n_epochs=2,
                 tol=0.0, prefetch=False)
    _assert_states_identical(r_mem.state, r_sync.state)


@pytest.mark.parametrize("schedule", ["inv_t", "adagrad"])
def test_hostsource_parity_streaming_path(xy, schedule):
    """stream_row_block engages the streaming dual pass inside the block
    core; the hosted plan must still match the in-memory epoch exactly."""
    x, y = xy
    cfg = DSEKLConfig(n_grad=24, n_expand=16, lam=1e-4, schedule=schedule,
                      stream_row_block=10, impl="ref")
    key = jax.random.PRNGKey(3)
    r_mem = fit(cfg, x, y, key, n_epochs=2, tol=0.0)
    r_host = fit(cfg, HostSource(np.asarray(x), np.asarray(y)), None, key,
                 n_epochs=2, tol=0.0)
    _assert_states_identical(r_mem.state, r_host.state)


def test_block_step_compiles_once_across_datasets():
    """The block-parametrized core must NOT retrace when N changes: three
    datasets with very different N, one compile-cache entry.

    Fresh lambdas isolate the compile caches — jax shares the cache
    between ``jax.jit`` objects wrapping the same callable, so wrapping
    ``dsekl.grad_block`` directly would count other tests' entries.
    """
    cfg = DSEKLConfig(n_grad=16, n_expand=16, impl="ref")
    core = jax.jit(
        lambda cfg, xi, yi, xj, aj, n: dsekl.grad_block(cfg, xi, yi, xj,
                                                        aj, n),
        static_argnames=("cfg", "n"))
    core_p = jax.jit(                   # Alg. 2: the flattened worker union
        lambda cfg, xi, yi, xjk, ajk, n: dsekl.grad_block(
            cfg, xi, yi, xjk.reshape(-1, xjk.shape[-1]), ajk.reshape(-1), n),
        static_argnames=("cfg", "n"))
    for n in (128, 4096, 262_144):
        ks = jax.random.split(jax.random.PRNGKey(n), 4)
        xi = jax.random.normal(ks[0], (16, 6))
        yi = jnp.sign(jax.random.normal(ks[1], (16,)))
        xj = jax.random.normal(ks[2], (16, 6))
        aj = jax.random.normal(ks[3], (16,))
        core(cfg, xi, yi, xj, aj, dsekl.scale_n(cfg, n))
        core_p(cfg, xi, yi, xj[None].repeat(2, 0), aj[None].repeat(2, 0),
               dsekl.scale_n(cfg, n))
    assert core._cache_size() == 1
    assert core_p._cache_size() == 1
    # unbiased_scaling is the documented exception: n becomes part of the
    # compiled step (the N/|J| scale is static), one entry per N.
    cfg_u = cfg.replace(unbiased_scaling=True)
    assert dsekl.scale_n(cfg_u, 128) != dsekl.scale_n(cfg_u, 4096)


def test_fit_does_not_retrace_block_core_across_datasets():
    """End to end: two HostSource fits with different N must not add a
    single compile-cache entry to the production block core after the
    first fit compiled it."""
    cfg = DSEKLConfig(n_grad=16, n_expand=16, lam=1e-4, impl="ref")
    key = jax.random.PRNGKey(0)
    for i, n in enumerate((160, 320)):
        x, y = make_xor(jax.random.PRNGKey(n), n)
        fit(cfg, HostSource(np.asarray(x), np.asarray(y)), None, key,
            n_epochs=1, tol=0.0)
        if i == 0:
            after_first = dsekl.grad_block_jit._cache_size()
    assert dsekl.grad_block_jit._cache_size() == after_first


def test_hosted_parallel_handles_dataset_smaller_than_batch():
    """N < n_grad: the in-memory parallel epoch scans zero I-batches and
    leaves the state untouched; the hosted path must match, not crash."""
    x, y = make_xor(jax.random.PRNGKey(1), 100)
    cfg = DSEKLConfig(n_grad=128, n_expand=32, lam=1e-4, impl="ref")
    key = jax.random.PRNGKey(2)
    r_mem = fit(cfg, x, y, key, algorithm="parallel", n_epochs=2, tol=0.0)
    r_host = fit(cfg, HostSource(np.asarray(x), np.asarray(y)), None, key,
                 algorithm="parallel", n_epochs=2, tol=0.0)
    _assert_states_identical(r_mem.state, r_host.state)


def test_decision_function_source_matches_device(xy):
    x, y = xy
    cfg = DSEKLConfig(impl="ref")
    alpha = jax.random.normal(jax.random.PRNGKey(5), (x.shape[0],))
    xq = jax.random.normal(jax.random.PRNGKey(6), (33, 2))
    f_dev = dsekl.decision_function(cfg, alpha, x, xq)
    f_src = dsekl.decision_function_source(
        cfg, alpha, HostSource(np.asarray(x), np.asarray(y)), xq, chunk=64)
    np.testing.assert_allclose(np.asarray(f_src), np.asarray(f_dev),
                               rtol=1e-5, atol=1e-6)


# --- decision rule: solver == engine, f == 0 included ---------------------

def test_predict_labels_zero_is_positive_class():
    f = jnp.asarray([-1.0, -0.0, 0.0, 1e-30, 2.0])
    np.testing.assert_array_equal(np.asarray(dsekl.predict_labels(f)),
                                  [-1.0, 1.0, 1.0, 1.0, 1.0])


def test_solver_and_engine_agree_on_decision_rule(xy):
    from repro.serving import DSEKLPredictionEngine, EngineConfig

    x, y = xy
    cfg = DSEKLConfig(impl="ref")
    xq = jax.random.normal(jax.random.PRNGKey(8), (40, 2))
    yq = jnp.sign(jax.random.normal(jax.random.PRNGKey(9), (40,)) + 0.1)
    for alpha in (jax.random.normal(jax.random.PRNGKey(10), (x.shape[0],)),
                  jnp.zeros((x.shape[0],))):   # all-zero model: f == 0
        err_solver = solver.error_rate(cfg, alpha, x, xq, yq)
        eng = DSEKLPredictionEngine(
            cfg, alpha, x, engine_cfg=EngineConfig(query_block=16,
                                                   truncate_tol=-1.0))
        f_eng = eng.predict(xq)
        err_engine = float(jnp.mean(
            (dsekl.predict_labels(f_eng) != yq).astype(jnp.float32)))
        assert err_solver == err_engine
    # the all-zero model decides +1 everywhere: error == P(y == -1), not 1
    assert err_solver == pytest.approx(
        float(jnp.mean((yq == -1).astype(jnp.float32))))


def test_fit_eval_cache_uses_same_rule(xy):
    """Cached-engine eval and streamed eval must report the same val error
    (the old sign() rule disagreed whenever f hit exactly zero)."""
    x, y = xy
    cfg = DSEKLConfig(n_grad=16, n_expand=16, lam=1e-4, impl="ref")
    key = jax.random.PRNGKey(2)
    r_cache = fit(cfg, x, y, key, n_epochs=2, tol=0.0, x_val=x[:40],
                  y_val=y[:40], eval_cache=True)
    r_plain = fit(cfg, x, y, key, n_epochs=2, tol=0.0, x_val=x[:40],
                  y_val=y[:40], eval_cache=False)
    for a, b in zip(r_cache.history, r_plain.history):
        assert a["val_error"] == pytest.approx(b["val_error"], abs=1e-7)


# --- the mesh data plane --------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
def test_mesh_block_step_matches_device_sampling_step():
    """Per-shard HostSources + host-side mesh plan + the block step must be
    bit-identical to the in-core sampling mesh step AND match the
    single-device oracle."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core.dsekl import DSEKLConfig
        from repro.core import distributed as dist
        from repro.data import make_xor, HostSource
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(4, 2)
        x, y = make_xor(jax.random.PRNGKey(0), 256)
        src = HostSource(np.asarray(x), np.asarray(y))
        data_srcs, model_srcs = src.split(4), src.split(2)
        for schedule, unbiased in (("adagrad", False), ("inv_t", True)):
            cfg = DSEKLConfig(n_grad=16, n_expand=16, lam=1e-4,
                              schedule=schedule, unbiased_scaling=unbiased)
            step_mem = dist.make_distributed_step(cfg, mesh, 256)
            step_blk = dist.make_distributed_block_step(cfg, mesh, 256)
            xg, yg, xe = dist.shard_inputs(mesh, x, y)
            st_m = dist.init_sharded_state(mesh, 256)
            st_b = dist.init_sharded_state(mesh, 256)
            a_ref = jnp.zeros(256); g_ref = jnp.ones(256)
            t_ref = jnp.zeros((), jnp.int32)
            key = jax.random.PRNGKey(7)
            for it in range(3):
                key, sub = jax.random.split(key)
                st_m = step_mem(xg, yg, xe, st_m, sub)
                xi, yi, xj, idx_j = dist.gather_mesh_blocks(
                    cfg, sub, data_srcs, model_srcs)
                st_b = step_blk(xi, yi, xj, idx_j, st_b, sub)
                a_ref, g_ref, t_ref = dist.simulate_step(
                    cfg, 4, 2, x, y, a_ref, g_ref, t_ref, sub)
            np.testing.assert_array_equal(np.asarray(st_b.alpha),
                                          np.asarray(st_m.alpha))
            np.testing.assert_array_equal(np.asarray(st_b.accum),
                                          np.asarray(st_m.accum))
            np.testing.assert_allclose(np.asarray(st_b.alpha),
                                       np.asarray(a_ref),
                                       rtol=1e-5, atol=1e-6)
            assert int(st_b.step) == 3
        print("MESH_BLOCK_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "MESH_BLOCK_OK" in out.stdout
