"""Idle time put down to the program's ``dsekl.`` host spans
(``chipbench.spans``) and the four ``fit.*_idle`` readers, on a hand-made
trace."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness, readers, spans  # noqa: E402
from chipbench import trace as tr  # noqa: E402

READERS = {"fit.boundary_idle": "boundary", "fit.dispatch_idle": "dispatch",
           "fit.setup_idle": "setup",
           "fit.unattributed_idle": spans.UNATTRIBUTED}


def _trace(program_spans=True):
    # Device busy 100..200, 265..280, 300..600, 700..800, 1000..1100 of the
    # window 50..1250; gaps 50..100, 200..265, 280..300, 600..700,
    # 800..1000, 1100..1250.
    ops = [("fusion.1", 100.0, 100.0), ("fusion.2", 265.0, 15.0),
           ("while.5", 300.0, 300.0), ("kernel_dual_pass.8", 350.0, 50.0),
           ("while.5", 700.0, 100.0), ("while.5", 1000.0, 100.0)]
    host = [("chipbench.window", 50.0, 1200.0),
            ("chipbench.fit", 60.0, 1150.0),
            ("DoEnqueueProgram", 640.0, 30.0)]
    if program_spans:
        host += [("dsekl.fit", 80.0, 1120.0),
                 ("dsekl.fit.setup", 85.0, 170.0),
                 ("dsekl.epoch", 256.0, 524.0),
                 ("dsekl.epoch.plan", 256.0, 6.0),
                 ("dsekl.epoch.dispatch", 262.0, 48.0),
                 ("dsekl.epoch.wait", 310.0, 290.0),
                 # A jax span inside: the program's phase still wins.
                 ("dsekl.epoch.host_delta", 600.0, 120.0),
                 ("dsekl.epoch", 820.0, 300.0),
                 ("dsekl.epoch.hooks", 880.0, 60.0)]
    # Spans of one thread on one line, as the profiler writes them.
    return tr.Trace({"/device:TPU:0": {"XLA Ops": ops},
                     "/host:CPU": {"python": sorted(host,
                                                    key=lambda e: e[1])}})


def _ctx(t):
    win = tr.window_of(t)
    busy = tr.busy_ns(t, win)["/device:TPU:0"]
    return SimpleNamespace(trace_data=t, trace_window=win,
                           trace_window_s=(win[1] - win[0]) * 1e-9,
                           busy_s=busy * 1e-9, stash={})


def test_gaps_go_to_the_innermost_program_span():
    t = _trace()
    by = spans.idle_by_span(t, tr.window_of(t))
    assert by == pytest.approx({
        spans.NO_SPAN: 50e-9,                   # 50..100, before dsekl.fit
        "dsekl.fit.setup": 65e-9,               # 200..265
        "dsekl.epoch.dispatch": 20e-9,          # 280..300
        "dsekl.epoch.host_delta": 100e-9,       # 600..700, over the jax span
        "dsekl.epoch.hooks": 200e-9,            # 800..1000
        "dsekl.fit": 150e-9})                   # 1100..1250, after the epoch
    # The harness's own breakdown puts the 600..700 gap to the jax span.
    assert dict(tr.idle_gaps(t, tr.window_of(t)))["DoEnqueueProgram"] \
        == pytest.approx(100e-9)


def test_phases_split_the_idle_share_with_nothing_left_over():
    ctx = _ctx(_trace())
    shares = {m: harness.load_module("metrics", m).read(ctx) for m in READERS}
    assert sum(shares.values()) == pytest.approx(readers.idle_share(ctx))
    pct = 100.0 / ctx.trace_window_s
    assert shares == pytest.approx({
        "fit.boundary_idle": 300e-9 * pct, "fit.dispatch_idle": 20e-9 * pct,
        "fit.setup_idle": 65e-9 * pct, "fit.unattributed_idle": 200e-9 * pct})


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_without_program_spans_reads_none(metric):
    read = harness.load_module("metrics", metric).read
    assert read(_ctx(_trace(program_spans=False))) is None
    untraced = SimpleNamespace(trace_data=None, trace_window=None,
                               trace_window_s=None, busy_s=None, stash={})
    assert read(untraced) is None


def test_every_program_span_has_one_phase():
    named = [n for names in spans.PHASES.values() for n in names]
    assert len(named) == len(set(named))
    assert spans.phase_of("dsekl.fit") == spans.UNATTRIBUTED
    assert spans.phase_of(spans.NO_SPAN) == spans.UNATTRIBUTED
