"""Reduction of a JAX profiler trace to device busy time, kernel time,
top device operations and idle gaps attributed to host activity.

A trace is read with ``jax.profiler.ProfileData`` into plain tuples
``(name, start_ns, duration_ns)`` per plane and line.  Device planes and
their operation lines are picked by regular expressions (TPU defaults
below: "/device:TPU:<n>" planes, whose "XLA Ops" line holds one event per
operation, named by its HLO text, e.g. ``%kernel_dual_pass.8 = ...
custom-call(...)`` for a Pallas call); the window is the host span the harness opens around the timed
loop.  Matching a kernel by name that finds no event raises ``NoEvents``:
a share is never read as 0 for want of events.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"
WINDOW_SPAN = "chipbench.window"

Event = Tuple[str, float, float]            # (name, start_ns, duration_ns)
Interval = Tuple[float, float]


class NoEvents(LookupError):
    pass


class Trace:
    def __init__(self, planes: Dict[str, Dict[str, List[Event]]]):
        self.planes = planes

    def events(self, plane_re: Optional[str] = None,
               line_re: Optional[str] = None) -> Dict[str, List[Event]]:
        """Events of every matching line, keyed by plane name (the device
        planes' operation lines by default)."""
        plane_re = plane_re or DEVICE_PLANE
        line_re = line_re or OP_LINE
        out: Dict[str, List[Event]] = {}
        for p, lines in self.planes.items():
            if not re.search(plane_re, p):
                continue
            for ln, evs in lines.items():
                if re.search(line_re, ln):
                    out.setdefault(p, []).extend(evs)
        return out


def load(logdir: str) -> Trace:
    import jax

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return Trace(planes)


def clip(evs: Iterable[Event], window: Optional[Interval]) -> List[Interval]:
    out = []
    for _, s, d in evs:
        a, b = s, s + d
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def window_of(trace: Trace, span: str = WINDOW_SPAN,
              host_plane: Optional[str] = None) -> Interval:
    for p, lines in trace.planes.items():
        if not re.search(host_plane or HOST_PLANE, p):
            continue
        for evs in lines.values():
            for name, s, d in evs:
                if name == span:
                    return (s, s + d)
    raise NoEvents(f"no host span {span!r} in the trace")


def busy_ns(trace: Trace, window: Optional[Interval],
            plane_re: Optional[str] = None,
            line_re: Optional[str] = None
            ) -> Dict[str, float]:
    """Per device plane: length of the union of its operation intervals
    inside the window."""
    per = trace.events(plane_re, line_re)
    if not per:
        raise NoEvents(f"no device plane {plane_re or DEVICE_PLANE!r}")
    return {p: sum(b - a for a, b in union(clip(evs, window)))
            for p, evs in per.items()}


def kernel_ns(trace: Trace, pattern: str, window: Optional[Interval],
              plane_re: Optional[str] = None,
              line_re: Optional[str] = None
              ) -> Tuple[float, int]:
    """Device time and count of the events whose name matches, summed over
    the planes; on each plane the union of their intervals, so an
    operation seen on two lines (an async collective) counts once."""
    total, count = 0.0, 0
    for evs in trace.events(plane_re, line_re).values():
        hits = [e for e in evs if re.search(pattern, e[0])]
        total += sum(b - a for a, b in union(clip(hits, window)))
        count += len(hits)
    if count == 0:
        raise NoEvents(f"no device event matches {pattern!r}")
    return total, count


def top_ops(trace: Trace, window: Optional[Interval], n: int = 10,
            plane_re: Optional[str] = None,
            line_re: Optional[str] = None
            ) -> List[List]:
    """The n device operations with the most time, averaged over planes."""
    per = trace.events(plane_re, line_re)
    acc: Dict[str, float] = {}
    for evs in per.values():
        for e in evs:
            for a, b in clip([e], window):
                acc[e[0]] = acc.get(e[0], 0.0) + (b - a)
    k = max(len(per), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in ranked]


def idle_gaps(trace: Trace, window: Interval, n: int = 10,
              plane_re: Optional[str] = None,
              line_re: Optional[str] = None,
              host_plane: Optional[str] = None,
              skip: Sequence[str] = (WINDOW_SPAN,)) -> List[List]:
    """Idle time of the first device plane inside the window, summed by
    the innermost host span that covers each gap's midpoint."""
    per = trace.events(plane_re, line_re)
    if not per:
        raise NoEvents(f"no device plane {plane_re or DEVICE_PLANE!r}")
    busy = union(clip(per[sorted(per)[0]], window))
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if window[1] > t:
        gaps.append((t, window[1]))
    host = [e for p, lines in trace.planes.items()
            if re.search(host_plane or HOST_PLANE, p)
            for evs in lines.values() for e in evs
            if e[2] > 0 and e[0] not in skip]
    host.sort(key=lambda e: e[1])
    acc: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []     # heap of (end, dur, name)
    k = 0
    for a, b in gaps:                               # sweep: gaps are sorted
        mid = 0.5 * (a + b)
        while k < len(host) and host[k][1] <= mid:
            name, s, d = host[k]
            heapq.heappush(active, (s + d, d, name))
            k += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        label = min(active, key=lambda e: e[1])[2] if active else \
            "host: no span"
        acc[label] = acc.get(label, 0.0) + (b - a)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def describe(trace: Trace, top: int = 8) -> str:
    """Planes, lines, event counts and the most frequent names: what one
    looks at by hand before writing patterns against a trace."""
    rows = []
    for p, lines in trace.planes.items():
        for ln, evs in lines.items():
            names: Dict[str, int] = {}
            for e in evs:
                names[e[0]] = names.get(e[0], 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            span = ((min(e[1] for e in evs), max(e[1] + e[2] for e in evs))
                    if evs else (0, 0))
            rows.append(f"{p} | {ln} | {len(evs)} events | span "
                        f"{span[0]:.0f}..{span[1]:.0f} ns | {common}")
    return "\n".join(rows)
