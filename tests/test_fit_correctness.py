"""Fit-loop correctness regressions (PR 6 satellites).

Four independent bugs, each pinned by a regression test:

  * an early-converged fit whose convergence epoch was off the
    ``eval_every`` cadence ended with NO ``val_error`` in its final
    history record;
  * the Alg.-2 update (and the mesh ``_apply_shard_update``)
    scattered ``g*g`` into the AdaGrad accumulator on EVERY schedule —
    non-adagrad fits paid an extra O(N) scatter per step and
    checkpointed a silently mutated accumulator;
  * ``_truncate_smallest`` dropped every entry tied at the threshold
    magnitude, so a uniform-|alpha| model was truncated wholesale;
  * ``fit(x_val=...)`` without ``y_val`` crashed deep inside the
    epoch-1 eval, and the chunked decision functions retraced once per
    distinct ragged tail shape.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsekl, solver, trainer
from repro.core.dsekl import DSEKLConfig, init_state
from repro.data.source import HostSource

CFG = DSEKLConfig(n_grad=24, n_expand=16, kernel="rbf",
                  kernel_params=(("gamma", 0.5),), lam=1e-4,
                  schedule="adagrad", impl="ref")


def _data(n=256, d=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (n, d))
    y = jnp.sign(jax.random.normal(ks[1], (n,)))
    return x, y


# ---------------------------------------------------------------------------
# Satellite 1: evaluate on the convergence epoch.
# ---------------------------------------------------------------------------

def test_convergence_epoch_off_eval_cadence_still_evaluates():
    """eval_every=3, convergence at epoch 2 (e=1, off the cadence): the
    final history record must still carry val_error."""
    x, y = _data()
    xv, yv = x[:48], y[:48]
    cfg = CFG.replace(schedule="inv_t", lr0=0.5)
    # Probe the deterministic delta_alpha sequence, then pick a tol
    # strictly between epoch 1's and epoch 2's deltas so the real fit
    # converges EXACTLY at epoch 2.
    probe = solver.fit(cfg, x, y, jax.random.PRNGKey(3), n_epochs=3,
                       tol=0.0)
    d1, d2 = (h["delta_alpha"] for h in probe.history[:2])
    assert d2 < d1, "probe fit must have decreasing deltas"
    tol = (d1 + d2) / 2.0
    res = solver.fit(cfg, x, y, jax.random.PRNGKey(3), n_epochs=9,
                     tol=tol, x_val=xv, y_val=yv, eval_every=3)
    assert res.converged and res.epochs_run == 2
    assert res.history[0].get("val_error") is not None   # cadence epoch
    assert "val_error" in res.history[-1], (
        "convergence epoch off the eval_every cadence lost its val_error")


# ---------------------------------------------------------------------------
# Satellite 2: accum is touched ONLY under schedule="adagrad".
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", ["inv_t", "inv_epoch", "const"])
def test_parallel_apply_leaves_accum_untouched_off_adagrad(schedule):
    """Non-adagrad parallel updates (``apply_update`` over a two-worker
    J union) must not mutate the accumulator.

    DELIBERATE semantic change: the old Alg.-2 update scattered ``g*g``
    into accum on every schedule (alpha was unaffected — the damp factor
    was ones), so a checkpoint of a non-adagrad parallel fit pinned a
    mutated accumulator.  No shipped fixture relied on it; new
    checkpoints hold the pristine init (all ones).
    """
    cfg = CFG.replace(schedule=schedule, n_workers=2)
    st = init_state(128)
    flat_j = jnp.arange(32)
    flat_g = jnp.linspace(-1.0, 1.0, 32)
    out = dsekl.apply_update(cfg, st, flat_j, flat_g)
    assert np.array_equal(np.asarray(out.accum), np.ones(128))
    assert not np.array_equal(np.asarray(out.alpha), np.zeros(128))


def test_parallel_apply_adagrad_still_accumulates():
    cfg = CFG.replace(n_workers=2)
    st = init_state(128)
    flat_j = jnp.arange(32)
    flat_g = jnp.full((32,), 2.0)
    out = dsekl.apply_update(cfg, st, flat_j, flat_g)
    expect = np.ones(128)
    expect[:32] += 4.0
    np.testing.assert_allclose(np.asarray(out.accum), expect)


def test_serial_and_parallel_accum_contract_match():
    """The single-device update and the mesh shard update agree bit for
    bit, on every schedule: the same alpha, accum and step count."""
    from repro.core import distributed as dist

    for schedule in ("adagrad", "inv_t", "inv_epoch", "const"):
        cfg = CFG.replace(schedule=schedule, lr0=0.7)
        st = init_state(64)._replace(step=jnp.asarray(4, jnp.int32),
                                     epoch=jnp.asarray(3, jnp.int32))
        st = st._replace(accum=st.accum + jnp.linspace(0.0, 2.0, 64))
        idx = jnp.arange(0, 64, 4)
        g = jnp.linspace(-1.5, 2.0, 16)
        want = dsekl.apply_update(cfg, st, idx, g)
        alpha, accum, t = dist._apply_shard_update(
            cfg, st.alpha, st.accum, st.step, st.epoch, idx, g)
        np.testing.assert_array_equal(np.asarray(alpha),
                                      np.asarray(want.alpha))
        np.testing.assert_array_equal(np.asarray(accum),
                                      np.asarray(want.accum))
        assert int(t) == int(want.step) == 5


# ---------------------------------------------------------------------------
# Satellite 3: rank-based truncation.
# ---------------------------------------------------------------------------

def test_truncate_tied_magnitudes_drops_exactly_frac():
    """Uniform |alpha|: the threshold rule zeroed EVERYTHING; the
    rank-based mask drops exactly floor(nnz * frac)."""
    alpha = jnp.ones((100,))
    out = np.asarray(trainer._truncate_smallest(alpha, 0.1))
    assert (out == 0).sum() == 10
    assert (out == 1).sum() == 90


def test_truncate_distinct_magnitudes_matches_threshold_semantics():
    """With untied magnitudes the rank mask is the old behavior: the k
    smallest non-zero entries go."""
    rng = np.random.RandomState(0)
    alpha = rng.permutation(np.arange(1.0, 51.0)).astype(np.float32)
    alpha[10:20] = 0.0                          # pre-zeroed entries
    out = np.asarray(trainer._truncate_smallest(jnp.asarray(alpha), 0.25))
    nnz = (alpha != 0).sum()
    k = int(nnz * 0.25)
    dropped = np.setdiff1d(np.nonzero(alpha)[0], np.nonzero(out)[0])
    assert len(dropped) == k
    kept_mags = np.abs(out[out != 0])
    assert np.abs(alpha[dropped]).max() < kept_mags.min()


def test_truncate_frac_zero_is_identity():
    alpha = jnp.asarray([0.0, 1.0, 1.0, 2.0])
    out = np.asarray(trainer._truncate_smallest(alpha, 0.0))
    np.testing.assert_array_equal(out, np.asarray(alpha))


# ---------------------------------------------------------------------------
# Satellite 4: x_val-without-y_val guard + no-retrace chunked eval.
# ---------------------------------------------------------------------------

def test_fit_x_val_without_y_val_raises_up_front():
    x, y = _data()
    with pytest.raises(TypeError, match="x_val without y_val"):
        solver.fit(CFG, x, y, jax.random.PRNGKey(0), n_epochs=1,
                   x_val=x[:16])


def test_decision_function_ref_pads_ragged_tail_no_retrace():
    """Distinct ragged tails must reuse ONE compiled matvec shape."""
    from repro.kernels.dsekl import ops as kops

    xt = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    chunk = 64

    def run(n):
        x = jax.random.normal(jax.random.PRNGKey(1), (n, 4))
        a = jax.random.normal(jax.random.PRNGKey(2), (n,))
        return dsekl.decision_function_ref(CFG, a, x, xt, chunk=chunk)

    run(chunk + 17)                             # warm: full chunk + one tail
    before = kops.kernel_matvec._cache_size()
    for n in (chunk + 5, chunk + 33, 3 * chunk + 1):
        run(n)                                  # all tails pad to `chunk`
    assert kops.kernel_matvec._cache_size() == before, (
        "ragged final chunks retraced the matvec")


def test_decision_function_source_pads_ragged_tail_no_retrace():
    from repro.kernels.dsekl import ops as kops

    xt = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    chunk = 64

    def run(n):
        x, y = _data(n=n, d=4, seed=5)
        src = HostSource(np.asarray(x), np.asarray(y))
        a = jax.random.normal(jax.random.PRNGKey(2), (n,))
        return dsekl.decision_function_source(CFG, a, src, xt, chunk=chunk)

    run(chunk + 17)
    before = kops.kernel_matvec._cache_size()
    for n in (chunk + 5, chunk + 33, 3 * chunk + 1):
        run(n)
    assert kops.kernel_matvec._cache_size() == before


@pytest.mark.parametrize("n", [40, 64, 150, 200])
def test_padded_decision_functions_exact(n):
    """Padding is exact: padded rows carry zero alpha, so both chunked
    evals equal the dense product at every (n, chunk) relation."""
    x, y = _data(n=n, d=4, seed=7)
    a = jax.random.normal(jax.random.PRNGKey(3), (n,))
    xt = jax.random.normal(jax.random.PRNGKey(4), (16, 4))
    from repro.core import kernels_fn
    dense = kernels_fn.get_kernel("rbf", gamma=0.5)(xt, x) @ a
    f_ref = dsekl.decision_function_ref(CFG, a, x, xt, chunk=64)
    src = HostSource(np.asarray(x), np.asarray(y))
    f_src = dsekl.decision_function_source(CFG, a, src, xt, chunk=64)
    np.testing.assert_allclose(np.asarray(f_ref), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_src), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
