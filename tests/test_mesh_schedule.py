"""The mesh fit's learning rate follows the serial plan's schedule.

``fit(execution="mesh")`` on four forced host devices (data = 4 x model =
1, a subprocess: jax fixes the device count at its first use) is held to
a plain ``jax.numpy`` copy of the benchmark's mesh reference
(``bench/chipbench/refs_mesh.py``) under ``inv_epoch``: lr0 / epoch for
every step of an epoch.  The rate the mesh used to step at (lr0 / t, the
step count standing in for the epoch) and a step that never sums the
shards' gradients are planted in the program and must fail the same
tolerance.  Under ``inv_t``, ``const`` and ``adagrad`` the epoch the mesh
state carries is read by nothing: fits match the device-sampling step
run with any epoch, bit for bit.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# Relative L2 distance between the mesh fit's alpha and the reference's
# after each epoch.  Both compute in float32 and sample the same rows; they
# differ only in the order of their sums (the program's kernel block per
# shard and a psum over four shards, the reference's one stacked block),
# which moves alpha by about 1e-7 of its norm in 64 steps.  1e-5 leaves
# 100x room for that; a wrong rate or a lost shard moves alpha by 10 % or
# more.
TOL = 1e-5

_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import DSEKLConfig, fit
    from repro.core import distributed as dist
    from repro.data import HostSource

    N, D, NG, NDATA, EPOCHS, GAMMA = 8192, 28, 64, 4, 2, 0.05
    kx, ky, kfit = jax.random.split(jax.random.PRNGKey(20), 3)
    x = jax.random.normal(kx, (N, D))
    y = jnp.where(x[:, 0] * x[:, 1] + 0.5 * jax.random.normal(ky, (N,)) > 0,
                  1.0, -1.0)
    src = HostSource(np.asarray(x), np.asarray(y))
    mesh = Mesh(np.array(jax.devices()).reshape(NDATA, 1), ("data", "model"))
    cfg = DSEKLConfig(n_grad=NG, n_expand=NG, kernel_params=(("gamma", GAMMA),),
                      lam=1.0 / N, lr0=1.0, schedule="inv_epoch", impl="ref")

    def rbf(a, b):
        hp = jax.lax.Precision.HIGHEST
        sq = (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
              - 2.0 * jnp.matmul(a, b.T, precision=hp))
        return jnp.exp(-GAMMA * jnp.maximum(sq, 0.0))

    def reference(key):
        # A copy of bench/chipbench/refs_mesh.py: one stacked block per step.
        hp = jax.lax.Precision.HIGHEST
        rows, steps = N // NDATA, N // (NG * NDATA)
        alpha, out = jnp.zeros((N,), jnp.float32), []
        key, sub = jax.random.split(key)
        for e in range(EPOCHS):
            key, nxt = jax.random.split(key)
            for k in jax.random.split(sub, steps):
                ii = jnp.concatenate([jax.random.randint(
                    jax.random.fold_in(jax.random.fold_in(k, 0), d), (NG,), 0,
                    rows) + d * rows for d in range(NDATA)])
                jj = jax.random.randint(
                    jax.random.fold_in(jax.random.fold_in(k, 1), 0), (NG,), 0, N)
                kb, aj, yi = rbf(x[ii], x[jj]), alpha[jj], y[ii]
                f = jnp.matmul(kb, aj, precision=hp)
                v = jnp.where(yi * f < 1.0, -yi, 0.0)
                g = jnp.matmul(kb.T, v, precision=hp) + cfg.lam * aj
                alpha = alpha.at[jj].add(-(cfg.lr0 / (e + 1)) * g)
            out.append(np.asarray(alpha, np.float64))
            sub = nxt
        return out

    def mesh_fit(c):
        got = []
        fit(c, src, None, kfit, execution="mesh", mesh=mesh, n_epochs=EPOCHS,
            tol=0.0, callback=lambda e, st: got.append(
                np.asarray(st.alpha, np.float64)))
        return got

    def gaps(got, want):
        return [float(np.linalg.norm(g - w) / np.linalg.norm(w))
                for g, w in zip(got, want)]

    want = reference(kfit)
    out = {"sound": gaps(mesh_fit(cfg), want)}

    apply, grad = dist._apply_shard_update, dist._shard_block_grad
    # Fault: the step count t read as the epoch (lr0 / t under inv_epoch).
    dist._apply_shard_update = (
        lambda c, a, acc, step, epoch, idx, g: apply(c, a, acc, step,
                                                     step + 1, idx, g))
    jax.clear_caches()
    out["per_step_rate"] = gaps(mesh_fit(cfg), want)
    dist._apply_shard_update = apply
    # No data-axis exchange: the gradient psum runs over the model axis,
    # of size 1, so each data shard applies its own gradient alone.
    dist._shard_block_grad = (
        lambda *a, data_axis, model_axis: grad(
            *a, data_axis=model_axis, model_axis=model_axis))
    jax.clear_caches()
    out["no_exchange"] = gaps(mesh_fit(cfg), want)
    dist._shard_block_grad = grad
    jax.clear_caches()

    # Other schedules: the fit equals the device-sampling step driven with
    # an epoch of 0 in the state (the fit carries 1, 2, ...).
    xg, yg, xe = dist.shard_inputs(mesh, x, y)
    steps = N // (NG * NDATA)
    for schedule in ("inv_t", "const", "adagrad"):
        c = cfg.replace(schedule=schedule, lr0=0.5)
        r = fit(c, src, None, kfit, execution="mesh", mesh=mesh,
                n_epochs=EPOCHS, tol=0.0)
        step = dist.make_distributed_step(c, mesh, N)
        st = dist.init_sharded_state(mesh, N)
        k = kfit
        for e in range(EPOCHS):
            k, sub = jax.random.split(k)
            for kk in jax.random.split(sub, steps):
                st = step(xg, yg, xe, st, kk)
        out[schedule] = {
            "alpha_equal": bool(np.array_equal(np.asarray(r.state.alpha),
                                               np.asarray(st.alpha))),
            "accum_equal": bool(np.array_equal(np.asarray(r.state.accum),
                                               np.asarray(st.accum))),
            "epoch": int(r.state.epoch), "moved": bool(np.any(
                np.asarray(r.state.alpha) != 0))}
    print("MESH_SCHEDULE " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("MESH_SCHEDULE "))
    return json.loads(line[len("MESH_SCHEDULE "):])


@pytest.mark.distributed
def test_inv_epoch_mesh_fit_matches_the_reference(readings):
    gaps = readings["sound"]
    assert len(gaps) == 2 and max(gaps) <= TOL, gaps


@pytest.mark.distributed
@pytest.mark.parametrize("fault", ["per_step_rate", "no_exchange"])
def test_planted_mesh_fault_fails_the_tolerance(readings, fault):
    assert max(readings[fault]) > 100 * TOL, readings[fault]


@pytest.mark.distributed
@pytest.mark.parametrize("schedule", ["inv_t", "const", "adagrad"])
def test_other_schedules_do_not_read_the_epoch(readings, schedule):
    r = readings[schedule]
    assert r["alpha_equal"] and r["accum_equal"], r
    assert r["epoch"] == 2 and r["moved"], r
