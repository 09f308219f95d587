"""Gradient compression + overlapped collectives: exactness/unbiasedness.

Multi-device parts run in a subprocess with 8 forced host devices.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed.compression import (quantize_stochastic,
                                           compression_error_bound)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**16), bits=st.sampled_from([4, 8]))
def test_stochastic_rounding_unbiased(seed, bits):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (64,)) * 3.0
    max_q = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x)) / max_q
    reps = 512
    qs = jax.vmap(lambda k: quantize_stochastic(x, scale, k, max_q))(
        jax.random.split(key, reps))
    mean_deq = jnp.mean(qs.astype(jnp.float32), axis=0) * scale
    # Unbiased: the empirical mean approaches x at ~scale/sqrt(reps).
    tol = 6.0 * float(scale) / np.sqrt(reps) + 1e-6
    np.testing.assert_allclose(np.asarray(mean_deq), np.asarray(x), atol=tol)


def test_error_bound_monotone():
    assert compression_error_bound(1.0, 8, 16) < compression_error_bound(
        1.0, 4, 16)


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import (
        allgather_matmul_overlapped, ring_psum_matmul)
    from repro.distributed.compression import compressed_psum

    from jax.sharding import AxisType
    mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)

    # --- allgather matmul: x row-sharded, w replicated ------------------
    x = jax.random.normal(k1, (64, 32))
    w = jax.random.normal(k2, (32, 16))
    got = jax.jit(jax.shard_map(
        lambda xs, ws: allgather_matmul_overlapped(xs, ws, "x"),
        mesh=mesh, in_specs=(P("x", None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=2e-5, atol=2e-5)

    # --- ring psum matmul: contraction sharded --------------------------
    xc = jax.random.normal(k1, (16, 64))
    wc = jax.random.normal(k2, (64, 24))
    got2 = jax.jit(jax.shard_map(
        lambda xs, ws: ring_psum_matmul(xs, ws, "x"),
        mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
        out_specs=P(None, None), check_vma=False))(xc, wc)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(xc @ wc),
                               rtol=2e-5, atol=2e-5)

    # --- compressed psum: 8-bit quantized all-reduce ---------------------
    g = jax.random.normal(k3, (8, 256))   # row per device
    def body(gs, key):
        return compressed_psum(gs[0], "x", key, bits=8)
    got3 = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("x", None), P()),
        out_specs=P(None), check_vma=False))(g, jax.random.PRNGKey(1))
    want3 = jnp.sum(g, axis=0)
    err = np.abs(np.asarray(got3) - np.asarray(want3)).max()
    bound = 8 * float(jnp.abs(g).max()) / 127 + 1e-6
    assert err <= bound, (err, bound)
    print("TRICKS_OK")
""")


@pytest.mark.slow
@pytest.mark.distributed
def test_collectives_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "TRICKS_OK" in out.stdout
