"""The mesh cell's readers (``mesh.loader_wait``, ``mesh.collective_share``,
``mesh_pass.roofline``) on a hand-made four-chip trace."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness, meshtrace, peaks, spans, workmodel  # noqa: E402
from chipbench import trace as tr  # noqa: E402

MATVEC = ("%kernel_matvec.1 = f32[1024,1]{1,0} custom-call(%a, %b, %c), "
          "custom_call_target=\"tpu_custom_call\"")
VECMAT = ("%kernel_vecmat.1 = f32[1024,1]{1,0} custom-call(%b, %a, %d), "
          "custom_call_target=\"tpu_custom_call\"")
PSUM = ("%psum.6 = f32[1024]{0:T(1024)S(1)} all-reduce(%fusion), "
        "channel_id=1, replica_groups={{0,1,2,3}}")


def _trace(program_spans=True):
    # Every chip runs two steps in the window 0..1000: busy 100..300 and
    # 500..700; gaps 0..100, 300..500, 700..1000.
    ops = []
    for start in (100.0, 500.0):
        ops += [(MATVEC, start, 60.0), (VECMAT, start + 60.0, 60.0),
                (PSUM, start + 120.0, 20.0),
                ("%fusion.1 = f32[1024]", start + 140.0, 60.0)]
    host = [("chipbench.window", 0.0, 1000.0),
            ("chipbench.fit", 0.0, 1000.0)]
    if program_spans:
        host += [("dsekl.fit", 10.0, 980.0), ("dsekl.epoch", 20.0, 900.0),
                 ("dsekl.epoch.dispatch", 30.0, 800.0),
                 ("dsekl.mesh.step", 90.0, 20.0),
                 # The fit waits 300..480 for blocks the worker gathers
                 # 350..450 and copies 450..470: the worker's shorter spans
                 # cover the gap's midpoint, but the wait is the fit's.
                 ("dsekl.mesh.wait", 300.0, 180.0),
                 ("dsekl.mesh.gather", 350.0, 100.0),
                 ("dsekl.mesh.h2d", 450.0, 20.0),
                 ("dsekl.mesh.step", 480.0, 20.0),
                 ("dsekl.epoch.wait", 700.0, 130.0)]
    planes = {f"/device:TPU:{i}": {"XLA Ops": list(ops)} for i in range(4)}
    planes["/host:CPU"] = {"python": sorted(host, key=lambda e: e[1])}
    return tr.Trace(planes)


def _ctx(t, chips=4):
    win = tr.window_of(t)
    busy = tr.busy_ns(t, win)
    used = sorted(busy)[:chips]
    return SimpleNamespace(
        trace_data=t, trace_window=win, trace_window_s=(win[1] - win[0]) * 1e-9,
        busy_s=sum(busy[p] for p in used) / len(used) * 1e-9, chips=chips,
        peak=peaks.PEAKS["TPU v5 lite"],
        stash={"steps": 2, "n_data": 4, "block": (1024, 1024, 28)})


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


def test_loader_wait_keeps_the_gap_the_worker_spans_cover():
    ctx = _ctx(_trace())
    # 300..500: midpoint 400 lies in the fit's wait and the worker's gather.
    every = spans.idle_by_span(ctx.trace_data, ctx.trace_window)
    assert every["dsekl.mesh.gather"] == pytest.approx(200e-9)
    assert _read("mesh.loader_wait", ctx) == pytest.approx(
        100.0 * 200e-9 / ctx.trace_window_s)


def test_collective_share_is_all_reduce_time_over_busy_time():
    ctx = _ctx(_trace())
    # Per chip 2 x 20 of 400 busy: 10 %.
    assert _read("mesh.collective_share", ctx) == pytest.approx(10.0)


def test_mesh_pass_roofline_counts_every_shard_step():
    ctx = _ctx(_trace())
    t_min, _ = workmodel.min_seconds(workmodel.train_pass(1024, 1024, 28),
                                     ctx.peak.flops_per_s,
                                     ctx.peak.hbm_bytes_per_s)
    # 2 steps x 4 shards against 4 chips x 2 x (60 + 60) ns of kernels.
    assert _read("mesh_pass.roofline", ctx) == pytest.approx(
        100.0 * 8 * t_min / (4 * 2 * 120e-9))


@pytest.mark.parametrize("metric", ["mesh.loader_wait",
                                    "mesh.collective_share",
                                    "mesh_pass.roofline"])
def test_mesh_reader_without_its_events_reads_none(metric):
    bare = tr.Trace({"/device:TPU:0": {"XLA Ops": [("%fusion.1 = f32[8]",
                                                    10.0, 5.0)]},
                     "/host:CPU": {"python": [("chipbench.window", 0.0,
                                               100.0)]}})
    assert _read(metric, _ctx(bare, chips=1)) is None
    if metric == "mesh.loader_wait":
        assert _read(metric, _ctx(_trace(program_spans=False))) is None
    untraced = SimpleNamespace(trace_data=None, trace_window=None,
                               trace_window_s=None, busy_s=None, chips=4,
                               stash={})
    assert _read(metric, untraced) is None


def test_patterns_match_the_step_and_nothing_else():
    import re

    assert re.search(meshtrace.MATVEC, MATVEC)
    assert re.search(meshtrace.VECMAT, VECMAT)
    assert re.search(meshtrace.ALL_REDUCE, PSUM)
    assert not re.search(meshtrace.ALL_REDUCE,
                         "%fusion.2 = f32[1024] fusion(%all-reduce.3)")
    assert not re.search(meshtrace.MATVEC, VECMAT)
