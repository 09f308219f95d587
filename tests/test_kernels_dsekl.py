"""Per-kernel allclose: Pallas (interpret mode) vs the pure-jnp oracle,
plus hypothesis property tests on the kernel functions themselves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import kernels_fn
from repro.kernels.dsekl import block, ref
from repro.kernels.dsekl import ops as kops


SHAPES = [
    (8, 8, 2),        # tiny, far below one block
    (100, 130, 7),    # ragged, multi-block in j
    (128, 128, 54),   # exactly one block, covertype D
    pytest.param((257, 64, 130),
                 marks=pytest.mark.slow),   # ragged i, D > 128
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_matvec_matches_ref(shape, dtype):
    i, j, d = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(i * 100 + j), 3)
    x = jax.random.normal(k1, (i, d), dtype)
    z = jax.random.normal(k2, (j, d), dtype)
    a = jax.random.normal(k3, (j,), dtype)
    kern = kernels_fn.get_kernel("rbf", gamma=0.7)
    want = ref.ref_kernel_matvec(kern, x.astype(jnp.float32),
                                 z.astype(jnp.float32), a.astype(jnp.float32))
    got = block.kernel_matvec_pallas(x, z, a, kernel_name="rbf",
                                     params={"gamma": 0.7}, interpret=True,
                                     block_i=64, block_j=64)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_vecmat_matches_ref(shape, dtype):
    i, j, d = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(i * 100 + j + 1), 3)
    x = jax.random.normal(k1, (i, d), dtype)
    z = jax.random.normal(k2, (j, d), dtype)
    v = jax.random.normal(k3, (i,), dtype)
    kern = kernels_fn.get_kernel("rbf", gamma=0.7)
    want = ref.ref_kernel_vecmat(kern, x.astype(jnp.float32),
                                 z.astype(jnp.float32), v.astype(jnp.float32))
    got = block.kernel_vecmat_pallas(x, z, v, kernel_name="rbf",
                                     params={"gamma": 0.7}, interpret=True,
                                     block_i=64, block_j=64)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_block_shape_invariance():
    """Different BlockSpec tilings must give identical results."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (200, 17))
    z = jax.random.normal(k2, (150, 17))
    a = jax.random.normal(k3, (150,))
    outs = [block.kernel_matvec_pallas(x, z, a, kernel_name="rbf",
                                       params={"gamma": 1.0}, interpret=True,
                                       block_i=bi, block_j=bj)
            for bi, bj in [(64, 64), (128, 128), (32, 128)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-6)


def test_bf16_mxu_path_accuracy():
    """The bf16 distance-matmul lever (§Perf): rel error must stay < 1%
    of the decision-value scale (SGD is robust to that noise level)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k1, (256, 54))
    z = jax.random.normal(k2, (256, 54))
    a = jax.random.normal(k3, (256,))
    gamma = 0.5 / 54          # O(1) kernel values (median sq dist ~ 2D)
    kern = kernels_fn.get_kernel("rbf", gamma=gamma)
    want = ref.ref_kernel_matvec(kern, x, z, a)
    got = block.kernel_matvec_pallas(x, z, a, kernel_name="rbf",
                                     params={"gamma": gamma}, interpret=True,
                                     mxu_dtype=jnp.bfloat16,
                                     block_i=128, block_j=128)
    rel = float(jnp.abs(want - got).max() / jnp.abs(want).max())
    assert rel < 0.01, rel


def test_choose_blocks_vmem_budget():
    from repro.kernels.dsekl.block import tile_vmem_bytes
    from repro.kernels.dsekl.block import (choose_blocks, pass_hbm_bytes,
                                           VMEM_BUDGET)
    for d in [54, 128, 512, 2048]:
        bi, bj = choose_blocks(8192, 8192, d)
        assert tile_vmem_bytes(bi, bj, d) <= VMEM_BUDGET
        # Larger bi must never increase the traffic model.
        assert pass_hbm_bytes(8192, 8192, d, bi, bj) <= \
            pass_hbm_bytes(8192, 8192, d, 128, 128)


def test_ops_dispatch_ref_on_cpu():
    """impl='auto' must pick the XLA path on CPU and agree with ref."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x, z = jax.random.normal(k1, (33, 5)), jax.random.normal(k2, (21, 5))
    a = jax.random.normal(k3, (21,))
    kern = kernels_fn.get_kernel("rbf", gamma=1.0)
    np.testing.assert_allclose(
        np.asarray(kops.kernel_matvec(x, z, a)),
        np.asarray(ref.ref_kernel_matvec(kern, x, z, a)),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_resolve_impl_explicit_pallas_without_tile_raises(impl, monkeypatch):
    """An explicit Pallas backend never quietly becomes the XLA path."""
    monkeypatch.delenv("REPRO_IMPL", raising=False)
    assert kops.resolve_impl(impl, "rbf") == impl
    assert kops.resolve_impl("auto", "rbf") == "ref"        # CPU
    with pytest.raises(ValueError, match="no Pallas tile"):
        kops.resolve_impl(impl, "no_such_kernel")


def test_ops_nonrbf_falls_back():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    x, z = jax.random.normal(k1, (16, 4)), jax.random.normal(k2, (12, 4))
    v = jax.random.normal(k3, (16,))
    out = kops.kernel_vecmat(x, z, v, kernel_name="polynomial",
                             kernel_params=(("gamma", 0.5), ("degree", 2)),
                             impl="pallas_interpret")
    kern = kernels_fn.get_kernel("polynomial", gamma=0.5, degree=2)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.ref_kernel_vecmat(kern, x, z, v)),
                               rtol=1e-5, atol=1e-5)


# --- hypothesis property tests on kernel functions -----------------------

finite_rows = st.integers(min_value=1, max_value=12)
finite_dim = st.integers(min_value=1, max_value=8)


@settings(max_examples=25, deadline=None)
@given(n=finite_rows, d=finite_dim, seed=st.integers(0, 2**16))
def test_rbf_properties(n, d, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    k = kernels_fn.rbf(x, x, gamma=0.5)
    arr = np.asarray(k)
    # symmetry, unit diagonal, range (0, 1]
    np.testing.assert_allclose(arr, arr.T, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.diag(arr), 1.0, rtol=1e-5)
    assert (arr > 0).all() and (arr <= 1.0 + 1e-6).all()
    # PSD (up to numerical jitter): eigenvalues >= -eps
    eig = np.linalg.eigvalsh(arr)
    assert eig.min() > -1e-4


@settings(max_examples=25, deadline=None)
@given(n=finite_rows, d=finite_dim, seed=st.integers(0, 2**16))
def test_sq_dists_nonnegative_and_zero_diag(n, d, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d)) * 3.0
    sq = np.asarray(kernels_fn.sq_dists(x, x))
    assert (sq >= 0).all()
    np.testing.assert_allclose(np.diag(sq), 0.0, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_kernels_registry_consistency(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (5, 3))
    z = jax.random.normal(jax.random.fold_in(key, 1), (4, 3))
    for name in kernels_fn.KERNELS:
        k = kernels_fn.get_kernel(name)(x, z)
        assert k.shape == (5, 4)
        assert np.isfinite(np.asarray(k)).all()
