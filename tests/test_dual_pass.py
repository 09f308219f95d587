"""Parity suite for the fused dual-pass kernel op (tentpole of PR 1).

For EVERY kernel in the registry x {float32, bfloat16} x ragged shapes that
are not multiples of the Pallas block size, asserts the three-way agreement

    pallas_interpret  ==  ref oracle  ==  composed (kernel_matvec, kernel_vecmat)

for both flavors of the op:
  * dual pass   — v given:   (f, g) = (K @ a, K^T @ v)
  * train pass  — loss fused: f = s*K@a, v = grad_f(f, y), g = K^T @ v
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kernels_fn
from repro.core import losses as losses_lib
from repro.kernels.dsekl import block, ops as kops, ref


# Ragged shapes deliberately not multiples of the 64/128 blocks.  The
# largest shape and the bf16 sweep ride in the slow lane (interpret-mode
# Pallas is CPU-bound); the fast tier-1 lane keeps full kernel coverage on
# the smaller f32 cases.
SHAPES = [
    (8, 8, 2),        # tiny, far below one block
    (100, 130, 7),    # ragged, multi-block in j
    pytest.param((257, 65, 33), marks=pytest.mark.slow),  # ragged both, odd D
]
DTYPES = [jnp.float32,
          pytest.param(jnp.bfloat16, marks=pytest.mark.slow)]

KERNEL_CASES = [
    ("rbf", (("gamma", 0.7),)),
    ("laplacian", (("gamma", 0.3),)),
    ("linear", ()),
    ("polynomial", (("gamma", 0.5), ("coef0", 1.0), ("degree", 2))),
    ("sigmoid", (("gamma", 0.5), ("coef0", 0.1))),
    ("matern32", (("length_scale", 1.3),)),
    ("matern52", (("length_scale", 0.8),)),
]


def _data(shape, dtype, seed=0):
    i, j, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed + i * 1000 + j), 5)
    x = jax.random.normal(ks[0], (i, d), dtype)
    z = jax.random.normal(ks[1], (j, d), dtype)
    a = jax.random.normal(ks[2], (j,), dtype)
    v = jax.random.normal(ks[3], (i,), dtype)
    y = jnp.sign(jax.random.normal(ks[4], (i,))).astype(jnp.float32)
    return x, z, a, v, y


def _tols(dtype, *refs):
    """(rtol, atol) with atol scaled to the oracle's magnitude: the bf16
    ref path rounds every summand to 8 mantissa bits, so unbounded kernels
    (linear/polynomial) see cancellation error proportional to the summand
    scale, not the result scale."""
    scale = max(1.0, *(float(jnp.abs(r).max()) for r in refs))
    if dtype == jnp.float32:
        return 2e-4, 1e-5 * scale
    return 5e-2, 3e-2 * scale


def test_registry_fully_covered():
    """Every registered kernel function has a Pallas tile (the tentpole's
    kernel-family generality claim)."""
    assert set(block.TILE_FNS) == set(kernels_fn.KERNELS)


@pytest.mark.parametrize("kernel_name,params", KERNEL_CASES,
                         ids=[k for k, _ in KERNEL_CASES])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_dual_pass_parity(kernel_name, params, shape, dtype):
    x, z, a, v, y = _data(shape, dtype)
    kern = kernels_fn.get_kernel(kernel_name, **dict(params))

    # Oracle on f32 inputs (the pallas paths accumulate in f32).
    xf, zf = x.astype(jnp.float32), z.astype(jnp.float32)
    af, vf = a.astype(jnp.float32), v.astype(jnp.float32)
    f_ref, g_ref = ref.ref_kernel_dual_pass(kern, xf, zf, af, vf)
    rtol, atol = _tols(dtype, f_ref, g_ref)

    # Composed single-product ops must tell the same story.
    rtol32, atol32 = _tols(jnp.float32, f_ref, g_ref)
    f_comp = kops.kernel_matvec(xf, zf, af, kernel_name=kernel_name,
                                kernel_params=params, impl="ref")
    g_comp = kops.kernel_vecmat(xf, zf, vf, kernel_name=kernel_name,
                                kernel_params=params, impl="ref")
    np.testing.assert_allclose(np.asarray(f_comp), np.asarray(f_ref),
                               rtol=rtol32, atol=atol32)
    np.testing.assert_allclose(np.asarray(g_comp), np.asarray(g_ref),
                               rtol=rtol32, atol=atol32)

    for impl in ("ref", "pallas_interpret"):
        f, g = kops.kernel_dual_pass(x, z, a, v, kernel_name=kernel_name,
                                     kernel_params=params, impl=impl)
        np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref),
                                   rtol=rtol, atol=atol, err_msg=impl)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=rtol, atol=atol, err_msg=impl)


@pytest.mark.parametrize("kernel_name,params", KERNEL_CASES,
                         ids=[k for k, _ in KERNEL_CASES])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_train_pass_parity(kernel_name, params, shape):
    """Loss-fused flavor: pallas_interpret == ref == composed three-step."""
    x, z, a, _, y = _data(shape, jnp.float32, seed=7)
    kern = kernels_fn.get_kernel(kernel_name, **dict(params))
    loss = losses_lib.get_loss("hinge")
    f_scale = 1.5

    # Composed: matvec -> loss grad -> vecmat (the two-pass training body).
    f_comp = f_scale * kops.kernel_matvec(x, z, a, kernel_name=kernel_name,
                                          kernel_params=params, impl="ref")
    v = loss.grad_f(f_comp, y)
    g_comp = kops.kernel_vecmat(x, z, v, kernel_name=kernel_name,
                                kernel_params=params, impl="ref")

    f_ref, g_ref = ref.ref_kernel_train_pass(kern, x, z, a, y, loss.grad_f,
                                             f_scale=f_scale)
    rtol, atol = _tols(jnp.float32, f_ref, g_ref)
    np.testing.assert_allclose(np.asarray(f_ref), np.asarray(f_comp),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_comp),
                               rtol=rtol, atol=atol)

    for impl in ("ref", "pallas_interpret"):
        f, g = kops.kernel_dual_pass(x, z, a, y, kernel_name=kernel_name,
                                     kernel_params=params, loss="hinge",
                                     f_scale=f_scale, impl=impl)
        np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref),
                                   rtol=rtol, atol=atol, err_msg=impl)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=rtol, atol=atol, err_msg=impl)


@pytest.mark.parametrize("loss_name", sorted(losses_lib.LOSSES))
def test_train_pass_all_losses(loss_name):
    """The in-kernel loss gradient must match the composed path for every
    registered loss — including 'square', whose nonzero gradient at f=0
    exercises the padded-row v masking."""
    x, z, a, _, y = _data((100, 70, 5), jnp.float32, seed=3)
    if not losses_lib.get_loss(loss_name).binary_labels:
        y = jax.random.normal(jax.random.PRNGKey(42), y.shape)
    loss = losses_lib.get_loss(loss_name)
    kern = kernels_fn.get_kernel("rbf", gamma=0.7)
    f_ref, g_ref = ref.ref_kernel_train_pass(kern, x, z, a, y, loss.grad_f)
    f, g = kops.kernel_dual_pass(x, z, a, y, kernel_name="rbf",
                                 kernel_params=(("gamma", 0.7),),
                                 loss=loss_name, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_dual_pass_block_shape_invariance():
    """Different tilings of the dual-pass kernel give identical results."""
    x, z, a, v, _ = _data((200, 150, 17), jnp.float32, seed=1)
    outs = [block.dual_pass_pallas(x, z, a, v, kernel_name="rbf",
                                   params={"gamma": 1.0}, interpret=True,
                                   block_i=bi, block_j=bj)
            for bi, bj in [(64, 64), (128, 128), (32, 128)]]
    for f, g in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0][0]), np.asarray(f),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(outs[0][1]), np.asarray(g),
                                   rtol=1e-5, atol=1e-6)


def test_train_pass_blocks_budget():
    """The K row-block scratch must respect the VMEM budget, and the chooser
    must refuse (-> two-sweep fallback) when even bi=128 cannot fit."""
    got = block.train_pass_blocks(4096, 2048, 64)
    assert got is not None
    bi, bj = got
    jp = -(-2048 // bj) * bj
    assert (block.tile_vmem_bytes(bi, bj, 64) + 4 * bi * (jp + 2 * 128)
            <= block.VMEM_BUDGET)
    assert block.train_pass_blocks(4096, 1 << 20, 64) is None


@pytest.mark.slow
def test_train_pass_fallback_path_correct(monkeypatch):
    """Force the over-budget fallback (two fused sweeps) THROUGH the real
    kernel_dual_pass entry point and check parity.  Shrinking the VMEM
    budget makes train_pass_blocks refuse; the shape is unique to this test
    so the jit cache cannot serve a trace made under the normal budget."""
    monkeypatch.setattr(block, "VMEM_BUDGET", 0)
    assert block.train_pass_blocks(41, 29, 3) is None
    x, z, a, _, y = _data((41, 29, 3), jnp.float32, seed=9)
    loss = losses_lib.get_loss("hinge")
    kern = kernels_fn.get_kernel("rbf", gamma=1.0)
    f_ref, g_ref = ref.ref_kernel_train_pass(kern, x, z, a, y, loss.grad_f,
                                             f_scale=1.5)
    f, g = kops.kernel_dual_pass(x, z, a, y, kernel_name="rbf",
                                 kernel_params=(("gamma", 1.0),),
                                 loss="hinge", f_scale=1.5,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel_name,params", KERNEL_CASES,
                         ids=[k for k, _ in KERNEL_CASES])
def test_generalized_matvec_vecmat_all_kernels(kernel_name, params):
    """The single-product Pallas sweeps now cover the whole registry too
    (previously RBF-only; everything else silently fell back to ref)."""
    x, z, a, v, _ = _data((70, 90, 6), jnp.float32, seed=5)
    kern = kernels_fn.get_kernel(kernel_name, **dict(params))
    f = kops.kernel_matvec(x, z, a, kernel_name=kernel_name,
                           kernel_params=params, impl="pallas_interpret")
    g = kops.kernel_vecmat(x, z, v, kernel_name=kernel_name,
                           kernel_params=params, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(f),
                               np.asarray(ref.ref_kernel_matvec(kern, x, z, a)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(ref.ref_kernel_vecmat(kern, x, z, v)),
                               rtol=1e-4, atol=1e-4)
