"""The trace reduction, on hand-made intervals and on a small profiler
trace recorded on the CPU (whose XLA operations run on the host plane's
client thread, so the patterns below point there)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import trace as tr  # noqa: E402

CPU_PLANE = r"^/host:CPU$"
CPU_OPS = r"^tf_XLAPjRtCpuClient"


def test_union_merges_overlaps_and_clip_cuts_to_the_window():
    evs = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 20.0, 5.0),
           ("d", 40.0, 30.0)]
    ivs = tr.clip(evs, (2.0, 50.0))
    assert tr.union(ivs) == [(2.0, 15.0), (20.0, 25.0), (40.0, 50.0)]


def _synthetic():
    ops = [("fusion.1", 100.0, 50.0), ("train_kernel", 120.0, 100.0),
           ("train_kernel", 400.0, 100.0), ("all-reduce.3", 600.0, 20.0)]
    host = [("chipbench.window", 50.0, 700.0), ("chipbench.fit", 60.0, 680.0),
            ("gather", 250.0, 100.0)]
    return tr.Trace({"/device:TPU:0": {"XLA Ops": ops, "Steps": []},
                     "/host:CPU": {"python": host}})


def test_busy_kernel_time_and_idle_gaps_on_a_synthetic_trace():
    t = _synthetic()
    win = tr.window_of(t)
    assert win == (50.0, 750.0)
    assert tr.busy_ns(t, win) == {"/device:TPU:0": 120.0 + 100.0 + 20.0}
    assert tr.kernel_ns(t, r"train_kernel", win) == (200.0, 2)
    assert tr.kernel_ns(t, r"all-reduce", win) == (20.0, 1)
    gaps = dict(tr.idle_gaps(t, win))
    # The gap 220..400 is inside the host span "gather" at its midpoint;
    # the others only inside the fit span.
    assert gaps["gather"] == pytest.approx(180e-9)
    assert gaps["chipbench.fit"] == pytest.approx((50 + 100 + 130) * 1e-9)
    top = tr.top_ops(t, win)
    assert top[0] == ["train_kernel", pytest.approx(200e-9)]


def test_a_named_kernel_that_is_absent_raises():
    t = _synthetic()
    with pytest.raises(tr.NoEvents):
        tr.kernel_ns(t, r"matvec_kernel", tr.window_of(t))
    with pytest.raises(tr.NoEvents):
        tr.window_of(t, span="no.such.span")
    with pytest.raises(tr.NoEvents):
        tr.busy_ns(t, None, plane_re=r"^/device:GPU")


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.exp(a @ a).sum())
    a = jnp.ones((128, 128))
    f(a).block_until_ready()
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            f(a).block_until_ready()
    jax.profiler.stop_trace()
    return tr.load(logdir)


def test_reduction_of_a_recorded_cpu_trace(cpu_trace):
    win = tr.window_of(cpu_trace)
    assert win[1] > win[0]
    busy = tr.busy_ns(cpu_trace, win, CPU_PLANE, CPU_OPS)
    assert 0 < sum(busy.values()) <= win[1] - win[0]
    ns, count = tr.kernel_ns(cpu_trace, r"dot", win, CPU_PLANE, CPU_OPS)
    assert count >= 3 and 0 < ns <= sum(busy.values())
    with pytest.raises(tr.NoEvents):
        tr.kernel_ns(cpu_trace, r"matvec_kernel", win, CPU_PLANE, CPU_OPS)
    assert "python" in tr.describe(cpu_trace)


# Operation names as a TPU v5e trace shows them,
# shortened: each reader's pattern picks its own and no other.
CHIP_OPS = {
    "train_pass.roofline": "%kernel_dual_pass.8 = (f32[1024,1]{1,0:T(8,128)}, "
    "f32[2,1,1024]{2,1,0:T(1,128)S(1)}) custom-call(f32[1024,54]{1,0} %copy.15),"
    ' custom_call_target="tpu_custom_call"',
    "serve_matvec.roofline": "%kernel_matvec_tiled.1 = f32[1024,1]{1,0:T(8,128)S(1)}"
    " custom-call(f32[1024,54]{1,0} %copy.2), custom_call_target="
    '"tpu_custom_call"',
}
OTHER_OPS = ["%fusion.63 = f32[572820]{0:T(1024)S(1)} fusion(f32[572820] "
             "%get-tuple-element.208), kind=kCustom, calls=%fused_computation.4",
             "%copy.3 = f32[491520,54]{1,0:T(8,128)} copy(f32[491520,54] %xs.1)"]


@pytest.mark.parametrize("metric", sorted(CHIP_OPS))
def test_reader_pattern_matches_its_chip_operation(metric):
    import re

    from chipbench import harness

    pattern = harness.load_module("metrics", metric).PATTERN
    assert re.search(pattern, CHIP_OPS[metric])
    for name in OTHER_OPS + [v for k, v in CHIP_OPS.items() if k != metric]:
        assert not re.search(pattern, name), name
