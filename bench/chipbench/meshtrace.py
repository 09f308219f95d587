"""Trace reductions of the mesh training cell (``bench/drivers/mesh_fit.py``).

* ``loader_wait``: device idle time under the fit's own ``dsekl.mesh.wait``
  span.  ``MeshPlan.run_epoch`` writes ``dsekl.mesh.wait`` around each
  wait for the prefetcher's blocks and ``dsekl.mesh.step`` around each
  step's dispatch, on the thread that runs the fit; the prefetcher's
  worker writes ``dsekl.mesh.gather`` and ``dsekl.mesh.h2d`` on its own
  thread.  ``chipbench.spans`` gives each idle gap to the innermost
  ``dsekl.`` span of any host thread, so a short worker span over a gap's
  midpoint would take the gap from the fit's wait; here the worker's
  spans are left out.  They are told apart by name: ``trace.load`` keys
  a plane's lines by name, and both threads' lines are named "python".
* ``PATTERNS``: the mesh step's device events by name.  The Pallas passes
  keep the names of their ops (``%kernel_matvec.<n>``,
  ``%kernel_vecmat.<n>``); the data-axis reduction is matched by its HLO
  opcode, since XLA names it after the ``psum`` that made it
  (``%psum.6 = f32[1024]{0} all-reduce(...)``) and ``XLA Ops`` events
  carry no scope.
"""
from __future__ import annotations

import re
from typing import Optional

from chipbench import spans
from chipbench import trace as tr
from chipbench.harness import log

WAIT_SPAN = "dsekl.mesh.wait"
WORKER_SPANS = ("dsekl.mesh.gather", "dsekl.mesh.h2d")
MATVEC = r"^%kernel_matvec[.\d]* = .*tpu_custom_call"
VECMAT = r"^%kernel_vecmat[.\d]* = .*tpu_custom_call"
ALL_REDUCE = r" all-reduce(?:-start|-done)?\("


def without_worker(trace: tr.Trace) -> tr.Trace:
    """``trace`` without the prefetcher worker's host spans."""
    planes = {}
    for p, lines in trace.planes.items():
        if re.search(tr.HOST_PLANE, p):
            lines = {ln: [e for e in evs if e[0] not in WORKER_SPANS]
                     for ln, evs in lines.items()}
        planes[p] = lines
    return tr.Trace(planes)


def loader_wait(ctx) -> Optional[float]:
    """Idle time of the first chip under the fit thread's innermost
    ``dsekl.mesh.wait``, % of the traced window; None where the fit wrote
    no such span (a program without the mesh spans)."""
    if ctx.trace_data is None or not ctx.trace_window_s:
        return None
    own = without_worker(ctx.trace_data)
    if not any(e[0] == WAIT_SPAN
               for p, lines in own.planes.items()
               if re.search(tr.HOST_PLANE, p)
               for evs in lines.values() for e in evs):
        return None
    by_span = spans.idle_by_span(own, ctx.trace_window) or {}
    every = spans.idle_by_span(ctx.trace_data, ctx.trace_window) or {}
    log(f"mesh idle by the fit thread's spans (s): {by_span}; by every "
        f"thread's: {every}")
    return 100.0 * by_span.get(WAIT_SPAN, 0.0) / ctx.trace_window_s
