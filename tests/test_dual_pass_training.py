"""End-to-end behaviour of the fused dual-pass training path.

The fused path (cfg.fuse_dual_pass=True, the default) must:
  * reach >= 95% train accuracy on the synthetic XOR task, for both the
    serial Alg. 1 loop and the parallel Alg. 2 epoch, and
  * track the two-pass path's state trajectory over 50 steps at tolerance
    (on the ref backend the serial fused step is the *same* float program —
    K evaluated once instead of twice — so agreement is essentially exact;
    the parallel epoch evaluates the workers' union in one block, hence
    the tolerance).

Alg. 2's epoch is the block core over the flattened union of its K worker
blocks: a hand loop of ``grad_block`` + ``apply_update`` over that union
reproduces it bit for bit, and the per-worker two-pass sum (the paper's
"K workers jointly evaluate the kernel map", kept here as an oracle)
matches it at tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DSEKLConfig, dsekl, error_rate, fit, losses, sampler
from repro.data import make_xor, train_test_split
from repro.kernels.dsekl import ops as kops


@pytest.fixture(scope="module")
def xor_split():
    x, y = make_xor(jax.random.PRNGKey(0), 400)
    return train_test_split(jax.random.PRNGKey(1), x, y)


CFG = DSEKLConfig(n_grad=32, n_expand=32, kernel_params=(("gamma", 1.0),),
                  lam=1e-4, lr0=1.0, schedule="adagrad", fuse_dual_pass=True)


def _train_accuracy(cfg, alpha, xtr, ytr):
    return 1.0 - error_rate(cfg, alpha, xtr, xtr, ytr)


@pytest.mark.slow
def test_fused_serial_reaches_95pct_train_accuracy(xor_split):
    xtr, ytr, _, _ = xor_split
    res = fit(CFG, xtr, ytr, jax.random.PRNGKey(2), algorithm="serial",
              n_epochs=30)
    acc = _train_accuracy(CFG, res.state.alpha, xtr, ytr)
    assert acc >= 0.95, f"fused serial train accuracy too low: {acc}"


@pytest.mark.slow
def test_fused_parallel_reaches_95pct_train_accuracy(xor_split):
    xtr, ytr, _, _ = xor_split
    cfg = CFG.replace(n_workers=4)
    res = fit(cfg, xtr, ytr, jax.random.PRNGKey(2), algorithm="parallel",
              n_epochs=15)
    acc = _train_accuracy(cfg, res.state.alpha, xtr, ytr)
    assert acc >= 0.95, f"fused parallel train accuracy too low: {acc}"


@pytest.mark.parametrize("schedule", ["adagrad", "inv_t"])
def test_fused_serial_matches_two_pass_50_steps(xor_split, schedule):
    """Same keys, same samples: the fused step must track the two-pass step
    state (alpha AND accum) over 50 serial steps."""
    xtr, ytr, _, _ = xor_split
    cfg_f = CFG.replace(schedule=schedule)
    cfg_2 = cfg_f.replace(fuse_dual_pass=False)
    st_f = dsekl.init_state(xtr.shape[0])
    st_2 = dsekl.init_state(xtr.shape[0])
    key = jax.random.PRNGKey(3)
    for _ in range(50):
        key, sub = jax.random.split(key)
        st_f = dsekl.step_serial(cfg_f, st_f, xtr, ytr, sub)
        st_2 = dsekl.step_serial(cfg_2, st_2, xtr, ytr, sub)
    np.testing.assert_allclose(np.asarray(st_f.alpha), np.asarray(st_2.alpha),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_f.accum), np.asarray(st_2.accum),
                               rtol=1e-5, atol=1e-6)
    assert int(st_f.step) == int(st_2.step) == 50


def test_fused_parallel_epoch_matches_two_pass(xor_split):
    """Three Alg. 2 epochs: the fused union-block evaluation against the
    two-pass one (matvec, then vecmat, over the same union) at (tight)
    float tolerance."""
    xtr, ytr, _, _ = xor_split
    cfg_f = CFG.replace(n_workers=4)
    cfg_2 = cfg_f.replace(fuse_dual_pass=False)
    st_f = dsekl.init_state(xtr.shape[0])
    st_2 = dsekl.init_state(xtr.shape[0])
    key = jax.random.PRNGKey(5)
    for _ in range(3):
        key, sub = jax.random.split(key)
        st_f = dsekl.epoch_parallel(cfg_f, st_f, xtr, ytr, sub)
        st_2 = dsekl.epoch_parallel(cfg_2, st_2, xtr, ytr, sub)
    np.testing.assert_allclose(np.asarray(st_f.alpha), np.asarray(st_2.alpha),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_f.accum), np.asarray(st_2.accum),
                               rtol=1e-4, atol=1e-4)


def _per_worker_two_pass(cfg, xi, yi, xjk, ajk):
    """The Alg.-2 gradient as its K workers compute it: each worker's
    partial decision values K_{I,J^k} a_{J^k}, summed; then each worker's
    block gradient K_{I,J^k}^T v + lam a_{J^k}, in worker order."""
    kw = dict(kernel_name=cfg.kernel, kernel_params=cfg.kernel_params,
              impl=cfg.impl)
    f = sum(kops.kernel_matvec(xi, xj, aj, **kw) for xj, aj in zip(xjk, ajk))
    v = losses.get_loss(cfg.loss).grad_f(f, yi)
    return jnp.concatenate([kops.kernel_vecmat(xi, xj, v, **kw) + cfg.lam * aj
                            for xj, aj in zip(xjk, ajk)])


def _hand_parallel_epochs(cfg, x, y, key, grad_fn, epochs=3):
    """``epochs`` Alg.-2 epochs as a Python loop over the epoch plan
    ``dsekl.epoch_parallel`` samples, one ``apply_update`` per I-batch
    over the flat worker union."""
    st = dsekl.init_state(x.shape[0])
    apply = jax.jit(dsekl.apply_update, static_argnames=("cfg",))
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        st = st._replace(epoch=st.epoch + 1)
        i_batches, idx_jk = sampler.parallel_epoch_plan(
            sub, x.shape[0], cfg.n_grad, cfg.n_expand, cfg.n_workers)
        for idx_i, jk in zip(i_batches, idx_jk):
            idx_j = jk.reshape(-1)
            g = grad_fn(cfg, x[idx_i], y[idx_i], x[jk], st.alpha[jk])
            st = apply(cfg, st, idx_j, g)
    return st


def _parallel_epochs(cfg, x, y, key, epochs=3):
    st = dsekl.init_state(x.shape[0])
    epoch = jax.jit(dsekl.epoch_parallel, static_argnames=("cfg",))
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        st = epoch(cfg, st, x, y, sub)
    return st


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "two_pass"])
def test_parallel_epoch_is_the_block_core_over_the_worker_union(xor_split,
                                                                fuse):
    """``epoch_parallel`` == ``grad_block`` + ``apply_update`` over the
    flattened union of the K worker blocks, bit for bit."""
    xtr, ytr, _, _ = xor_split
    cfg = CFG.replace(n_workers=3, fuse_dual_pass=fuse, impl="ref")
    core = jax.jit(lambda cfg, xi, yi, xjk, ajk: dsekl.grad_block(
        cfg, xi, yi, xjk.reshape(-1, xjk.shape[-1]), ajk.reshape(-1))[0],
        static_argnames=("cfg",))
    want = _parallel_epochs(cfg, xtr, ytr, jax.random.PRNGKey(5))
    got = _hand_parallel_epochs(cfg, xtr, ytr, jax.random.PRNGKey(5), core)
    for name in ("alpha", "accum", "step", "epoch"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)), name)


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "two_pass"])
def test_union_core_matches_the_per_worker_two_pass_sum(xor_split, fuse):
    """Three Alg. 2 epochs of the union core against the per-worker
    two-pass oracle, at ``test_fused_parallel_epoch_matches_two_pass``'s
    tolerances."""
    xtr, ytr, _, _ = xor_split
    cfg = CFG.replace(n_workers=4, fuse_dual_pass=fuse, impl="ref")
    want = _hand_parallel_epochs(cfg, xtr, ytr, jax.random.PRNGKey(5),
                                 jax.jit(_per_worker_two_pass,
                                         static_argnames=("cfg",)))
    got = _parallel_epochs(cfg, xtr, ytr, jax.random.PRNGKey(5))
    np.testing.assert_allclose(np.asarray(got.alpha), np.asarray(want.alpha),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.accum), np.asarray(want.accum),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_fused_with_unbiased_scaling(xor_split):
    """f_scale (the N/|J| unbiased empirical-map scaling) flows through the
    fused op identically to the two-pass scaling."""
    xtr, ytr, _, _ = xor_split
    cfg_f = CFG.replace(unbiased_scaling=True, lr0=0.1)
    cfg_2 = cfg_f.replace(fuse_dual_pass=False)
    st_f = dsekl.init_state(xtr.shape[0])
    st_2 = dsekl.init_state(xtr.shape[0])
    key = jax.random.PRNGKey(7)
    for _ in range(20):
        key, sub = jax.random.split(key)
        st_f = dsekl.step_serial(cfg_f, st_f, xtr, ytr, sub)
        st_2 = dsekl.step_serial(cfg_2, st_2, xtr, ytr, sub)
    np.testing.assert_allclose(np.asarray(st_f.alpha), np.asarray(st_2.alpha),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_fused_step_interpret_backend_matches_ref_backend(xor_split):
    """The fused step through the Pallas train-pass kernel (interpret) must
    track the fused ref backend — the end-to-end wiring of the tentpole."""
    xtr, ytr, _, _ = xor_split
    cfg_r = CFG.replace(impl="ref")
    cfg_p = CFG.replace(impl="pallas_interpret")
    st_r = dsekl.init_state(xtr.shape[0])
    st_p = dsekl.init_state(xtr.shape[0])
    key = jax.random.PRNGKey(11)
    for _ in range(10):
        key, sub = jax.random.split(key)
        st_r = dsekl.step_serial(cfg_r, st_r, xtr, ytr, sub)
        st_p = dsekl.step_serial(cfg_p, st_p, xtr, ytr, sub)
    np.testing.assert_allclose(np.asarray(st_p.alpha), np.asarray(st_r.alpha),
                               rtol=1e-4, atol=1e-5)
