"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.
Each returns None where the run has nothing to read."""
from __future__ import annotations

from typing import Optional

from chipbench import trace as tr
from chipbench import workmodel


def idle_share(ctx) -> Optional[float]:
    if ctx.busy_s is None or not ctx.trace_window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)


def kernel_s(ctx, pattern: str, line_re: Optional[str] = None
             ) -> Optional[float]:
    """Device seconds of the kernel's events in the traced window, summed
    over the chips; None where the trace holds no such event."""
    if ctx.trace_data is None:
        return None
    try:
        ns, count = tr.kernel_ns(ctx.trace_data, pattern, ctx.trace_window,
                                 line_re=line_re)
    except tr.NoEvents as e:
        from chipbench.harness import log

        log(f"{e}")
        return None
    ctx.stash.setdefault("kernel_events", {})[pattern] = count
    return ns * 1e-9


def train_work(ctx):
    i, j, d = ctx.stash["block"]
    return workmodel.train_pass(i, j, d)


def serve_works(ctx):
    s, d = ctx.stash["support_rows"], ctx.config["n_features"]
    return [workmodel.serve(q, s, d) for q in ctx.stash["q_per_call"] if q]
