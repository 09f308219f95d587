"""Compile the DSEKL Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel at a deployment
width (D = 54, the covertype shape; D = 784, the MNIST shape) for a v5e
that is described, not attached, and asserts the Pallas kernel survived
into the program (``tpu_custom_call``).  This is what catches, at no chip
time, what interpret mode cannot: block shapes the TPU lowering refuses,
primitives Mosaic does not lower, and tile sets over the scoped VMEM limit.

Block sizes come from the same choosers the ops use
(``choose_blocks`` / ``train_pass_blocks`` / ``choose_predict_blocks``).
The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import dsekl, losses
from repro.kernels.dsekl import block

WIDTHS = [54, 784]
I_TRAIN = J_TRAIN = 4096          # a large sampled block / J union
N_QUERY, N_SV = 1024, 65536        # one serving query block x support set


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache; keep it out of the cache.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not compiled in"


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("d", WIDTHS)
def test_train_pass_compiles(one_chip, d):
    bi, bj = block.train_pass_blocks(I_TRAIN, J_TRAIN, d)
    grad = losses.get_loss("hinge").grad_f
    _compile(lambda x, z, a, y: block.train_pass_pallas(
        x, z, a, y, grad, block_i=bi, block_j=bj),
        _f32(one_chip, I_TRAIN, d), _f32(one_chip, J_TRAIN, d),
        _f32(one_chip, J_TRAIN), _f32(one_chip, I_TRAIN))


@pytest.mark.parametrize("d", WIDTHS)
def test_dual_pass_compiles(one_chip, d):
    bi, bj = block.choose_blocks(I_TRAIN, J_TRAIN, d)
    _compile(lambda x, z, a, v: block.dual_pass_pallas(
        x, z, a, v, block_i=bi, block_j=bj),
        _f32(one_chip, I_TRAIN, d), _f32(one_chip, J_TRAIN, d),
        _f32(one_chip, J_TRAIN), _f32(one_chip, I_TRAIN))


@pytest.mark.parametrize("d", WIDTHS)
def test_matvec_training_blocks_compile(one_chip, d):
    bi, bj = block.choose_blocks(I_TRAIN, J_TRAIN, d)
    _compile(lambda x, z, a: block.kernel_matvec_pallas(
        x, z, a, block_i=bi, block_j=bj),
        _f32(one_chip, I_TRAIN, d), _f32(one_chip, J_TRAIN, d),
        _f32(one_chip, J_TRAIN))


@pytest.mark.parametrize("d", WIDTHS)
def test_matvec_serving_blocks_compile(one_chip, d):
    bq, bs = block.choose_predict_blocks(N_QUERY, N_SV, d)
    _compile(lambda x, z, a: block.kernel_matvec_pallas(
        x, z, a, block_i=bq, block_j=bs),
        _f32(one_chip, N_QUERY, d), _f32(one_chip, N_SV, d),
        _f32(one_chip, N_SV))


@pytest.mark.parametrize("d", WIDTHS)
def test_vecmat_compiles(one_chip, d):
    bj, bi = block.choose_blocks(J_TRAIN, I_TRAIN, d)   # g resident: big J
    _compile(lambda x, z, v: block.kernel_vecmat_pallas(
        x, z, v, block_i=bi, block_j=bj),
        _f32(one_chip, I_TRAIN, d), _f32(one_chip, J_TRAIN, d),
        _f32(one_chip, I_TRAIN))


@pytest.mark.parametrize("d", WIDTHS)
def test_laplacian_tile_compiles(one_chip, d):
    bi, bj = block.train_pass_blocks(I_TRAIN, J_TRAIN, d)
    grad = losses.get_loss("hinge").grad_f
    _compile(lambda x, z, a, y: block.train_pass_pallas(
        x, z, a, y, grad, kernel_name="laplacian", block_i=bi, block_j=bj),
        _f32(one_chip, I_TRAIN, d), _f32(one_chip, J_TRAIN, d),
        _f32(one_chip, J_TRAIN), _f32(one_chip, I_TRAIN))


@pytest.mark.parametrize("d", WIDTHS)
def test_gradient_step_core_compiles(one_chip, d):
    """The jitted block-gradient core of a training step (what every plan
    runs), at n_grad = n_expand = 1024, reaches the fused train pass."""
    cfg = dsekl.DSEKLConfig(n_grad=1024, n_expand=1024, impl="pallas")
    _compile(lambda xi, yi, xj, aj: dsekl.grad_block(cfg, xi, yi, xj, aj)[0],
             _f32(one_chip, 1024, d), _f32(one_chip, 1024),
             _f32(one_chip, 1024, d), _f32(one_chip, 1024))


def test_covertype_epoch_keeps_the_kernel_name_and_the_step_scopes(one_chip):
    """The epoch scan the benchmark's training cell runs (covertype,
    572,820 x 54): the fused train pass keeps the HLO name
    ``kernel_dual_pass`` that a trace reader matches (the step's named
    scopes are metadata only), and the step's ops carry the scopes."""
    import re

    from repro.core import trainer

    n, d = 572820, 54
    cfg = dsekl.DSEKLConfig(n_grad=1024, n_expand=1024, lam=1.0 / n,
                            impl="pallas")
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: dsekl.init_state(n)))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = trainer._epoch_serial.lower(
        cfg, state, _f32(one_chip, n, d), _f32(one_chip, n), key
    ).compile().as_text()
    kernel = [ln for ln in text.splitlines() if re.search(
        r"^%kernel_dual_pass[.\d]* = .*tpu_custom_call", ln.strip())]
    assert len(kernel) == 1, kernel
    assert "/dsekl.train_pass/" in kernel[0]
    for scope in ("sample", "gather", "train_pass", "update"):
        assert f"/dsekl.{scope}/" in text, scope


def test_covertype_epoch_gathers_from_row_major_padded_rows(one_chip):
    """The same epoch through the lane-padded rows an in-memory plan makes
    on a TPU: X enters row-major (an f32 (N, 54) matrix would enter
    column-major, a row gather then reading 7 tiles a row), no gather
    reads the column-major matrix, and the fused train pass keeps its HLO
    name and its (1024, 54) operands."""
    import re

    from repro.core import trainer

    n, d = 572820, 54
    cfg = dsekl.DSEKLConfig(n_grad=1024, n_expand=1024, lam=1.0 / n,
                            impl="pallas")
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: dsekl.init_state(n)))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    rows = dsekl.PaddedRows(_f32(one_chip, n, 128), d)
    text = trainer._epoch_serial.lower(
        cfg, state, rows, _f32(one_chip, n), key).compile().as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    assert "f32[572820,128]{1,0" in entry.group(1), entry.group(1)
    assert "f32[572820,54]{0,1" not in text
    gathers = [ln for ln in text.splitlines()
               if re.search(r"= f32\[1024,128\]\{1,0.* gather\(", ln)]
    assert len(gathers) == 2, gathers
    kernel = [ln for ln in text.splitlines() if re.search(
        r"^%kernel_dual_pass[.\d]* = .*tpu_custom_call", ln.strip())]
    assert len(kernel) == 1, kernel
    assert ("operand_layout_constraints={f32[1024,54]{1,0}, "
            "f32[1024,54]{1,0}," in kernel[0]), kernel[0]


def test_higgs_mesh_step_holds_one_gradient_all_reduce(topo):
    """The mesh block step of the four-chip HIGGS cell (data = 4 x model =
    1; shards of 1024 x 28 rows; alpha over 10,500,000 rows) on the 2x2
    host: the data-axis psum of the expansion gradient is the step's one
    collective, an all-reduce of f32[1024], and the two Pallas passes keep
    the names the cell's trace readers match (``mesh_pass.roofline``,
    ``mesh.collective_share``)."""
    import re
    import sys

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as dist

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
    from chipbench import meshtrace

    n, d, rows = 10_500_000, 28, 1024
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    cfg = dsekl.DSEKLConfig(n_grad=rows, n_expand=rows, lam=1.0 / n,
                            kernel_params=(("gamma", 0.0285),),
                            schedule="inv_epoch", impl="pallas")

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    state = dist.ShardedDSEKLState(
        arg((n,), jnp.float32, "model"), arg((n,), jnp.float32, "model"),
        arg((), jnp.int32), arg((), jnp.int32))
    step = dist.make_distributed_block_step(cfg, mesh, n)
    text = step.jitted.lower(
        arg((4 * rows, d), jnp.float32, "data", None),
        arg((4 * rows,), jnp.float32, "data"),
        arg((rows, d), jnp.float32, "model", None),
        arg((rows,), jnp.int32, "model"), state,
        arg((2,), jnp.uint32)).compile().as_text()
    lines = [ln.strip() for ln in text.splitlines()]
    reduce = [ln for ln in lines if re.search(meshtrace.ALL_REDUCE, ln)]
    assert len(reduce) == 1, reduce
    assert re.search(r"= f32\[1024\]\S* all-reduce\(", reduce[0]), reduce[0]
    assert "replica_groups={{0,1,2,3}}" in reduce[0], reduce[0]
    for pattern in (meshtrace.MATVEC, meshtrace.VECMAT):
        kernel = [ln for ln in lines if re.search(pattern, ln)]
        assert len(kernel) == 1, (pattern, kernel)
        assert f"f32[{rows},{d}]" in kernel[0], kernel[0]
