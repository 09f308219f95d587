"""Operations and bytes the algorithm needs, from shapes alone.

The same numbers whatever implements the step, so a re-tiling or a new
kernel cannot move the yardstick.  ``exp`` is not counted as a FLOP and
padding is not counted: rows the engine pads a query tile with are work
the user did not ask for.

* Train pass on one sampled I x J block with D features: the cross term
  (2D per entry), the distance and scale (2 per entry), f = K a and
  g = K^T v (2 each): FLOPs = I*J*(2D + 4).  Least bytes: x_I, x_J, a_J,
  y_I read once, f_I and g_J written once: 4*(I*D + J*D + 2I + 2J).
* Serving q real query rows against S real support rows: cross term plus
  distance and scale, and f = K a: FLOPs = q*S*(2D + 2).  Least bytes:
  4*(S*D + S + q*D + q).
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Tuple


class Work(NamedTuple):
    flops: float
    bytes: float


def train_pass(i: int, j: int, d: int) -> Work:
    return Work(float(i) * j * (2 * d + 4), 4.0 * (i * d + j * d + 2 * i + 2 * j))


def serve(q: int, s: int, d: int) -> Work:
    return Work(float(q) * s * (2 * d + 2), 4.0 * (s * d + s + q * d + q))


def min_seconds(work: Work, flops_per_s: float,
                bytes_per_s: float) -> Tuple[float, str]:
    """Least time on a chip, and which bound sets it."""
    t_c, t_m = work.flops / flops_per_s, work.bytes / bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def min_seconds_sum(works: Iterable[Work], flops_per_s: float,
                    bytes_per_s: float) -> Tuple[float, str]:
    """Sum of per-call least times; the bound named is the one that sets
    most of the sum."""
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for w in works:
        t, b = min_seconds(w, flops_per_s, bytes_per_s)
        total += t
        by[b] += t
    return total, max(by, key=by.get)
