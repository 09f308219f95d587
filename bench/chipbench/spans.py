"""Device idle time of the traced window put down to the program's own
phases.

The program writes host spans named ``dsekl.*`` with
``jax.profiler.TraceAnnotation`` (``repro.core.solver.fit``,
``repro.core.trainer.fit_loop``; docs/OPERATIONS.md, "Tracing a fit").
Each idle gap of the first device plane inside the window goes to the
innermost such span that covers the gap's midpoint, by
``trace.idle_gaps`` over the host plane with every other span left out:
a span of jax itself (``DoEnqueueProgram``, ``np.asarray(jax.Array)``)
says what jax was doing, not which phase of the program asked for it.
A gap that no ``dsekl.`` span covers goes to ``NO_SPAN``.

The phases split the idle time with nothing left over: ``unattributed``
takes every gap whose span is in no other phase (``dsekl.fit`` itself,
between its set-up and its first epoch or after its last, and gaps
outside any fit).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from chipbench import trace as tr

PREFIX = "dsekl."
NO_SPAN = "host: no span"          # ``trace.idle_gaps``'s label
PHASES = {
    "boundary": ("dsekl.epoch.plan", "dsekl.epoch.host_delta",
                 "dsekl.epoch.eval", "dsekl.epoch.hooks",
                 "dsekl.epoch.snapshot"),
    "dispatch": ("dsekl.epoch", "dsekl.epoch.dispatch", "dsekl.epoch.wait"),
    "setup": ("dsekl.fit.setup",),
}
UNATTRIBUTED = "unattributed"


def idle_by_span(trace: tr.Trace, window: tr.Interval,
                 prefix: str = PREFIX) -> Optional[Dict[str, float]]:
    """Idle seconds of the window by the innermost host span named
    ``prefix*`` over each gap's midpoint (``NO_SPAN`` where none is);
    None where no such span reaches into the window."""
    planes, kept = {}, 0
    for p, lines in trace.planes.items():
        if re.search(tr.HOST_PLANE, p):
            lines = {ln: [e for e in evs if e[0].startswith(prefix)
                          and tr.clip([e], window)]
                     for ln, evs in lines.items()}
            kept += sum(len(evs) for evs in lines.values())
        planes[p] = lines
    if not kept:
        return None
    # n: every label there is (one a kept span at most, and NO_SPAN).
    return dict(tr.idle_gaps(tr.Trace(planes), window, n=kept + 1))


def phase_of(span: str) -> str:
    for phase, names in PHASES.items():
        if span in names:
            return phase
    return UNATTRIBUTED


def idle_share(ctx, phase: str) -> Optional[float]:
    """Idle time under the phase's spans as a share of the traced window,
    in %; None where the trace has no ``dsekl.`` span to read."""
    if ctx.trace_data is None or not ctx.trace_window_s:
        return None
    if "idle_by_span" not in ctx.stash:
        ctx.stash["idle_by_span"] = idle_by_span(ctx.trace_data,
                                                 ctx.trace_window)
    by_span = ctx.stash["idle_by_span"]
    if by_span is None:
        return None
    s = sum(v for name, v in by_span.items() if phase_of(name) == phase)
    return 100.0 * s / ctx.trace_window_s
