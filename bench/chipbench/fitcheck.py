"""The numbers a training cell's check compares, shared by the fit drivers,
and ``judge``, with which every traffic kind's check holds numbers to limits.

A fit's step, as the program exposes it, is one epoch (one compiled
program, ``fit``'s unit of work).  For alphas after each of the first
epochs, the program's (or a control's) ``got`` against the reference's
``want``, all from alpha = 0:

* ``alpha_norm_gap_e1``: the first update, |norm(got_1) - norm(want_1)|
  over norm(want_1) -- the gradient as the step applies it, read from the
  state after one step;
* ``alpha_norm_gap_e<k>``: the same for the change after the k-th, last
  checked epoch;
* ``loss_gap``: each checked epoch's hinge loss on held-out rows, both
  evaluated by the float32 reference decision function; the worst
  relative gap over the epochs.

Gaps of norms and not norms of differences: a hinge step is discontinuous
at the margin, so rounding flips a few rows and the two trajectories part
even when both are right.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax.numpy as jnp
import numpy as np

from chipbench import refs


def compare(got: Sequence, want: Sequence, x, xv, yv, gamma: float
            ) -> Dict[str, float]:
    e = len(want)
    a = jnp.stack([jnp.asarray(v) for v in list(got) + list(want)], axis=1)
    f, _ = refs.ref_decision(xv, x, a, gamma=gamma)
    loss = np.asarray(jnp.mean(refs.hinge(f, yv[:, None]), axis=0),
                      np.float64)
    g = [np.linalg.norm(np.asarray(v, np.float64)) for v in got]
    w = [np.linalg.norm(np.asarray(v, np.float64)) for v in want]
    return {
        "alpha_norm_gap_e1": abs(g[0] - w[0]) / w[0],
        f"alpha_norm_gap_e{e}": abs(g[e - 1] - w[e - 1]) / w[e - 1],
        "loss_gap": max(_gap(loss[k], loss[e + k]) for k in range(e)),
    }


def _gap(got: float, want: float) -> float:
    """Relative gap; two losses of exactly 0 (every held-out margin at 1 or
    beyond) agree."""
    if got == want:
        return 0.0
    return abs(got - want) / want if want else float("inf")


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """Each compared number beside its limit; a number without a limit in
    the traffic file is logged by the caller and not judged."""
    return [{"name": k, "value": float(v), "limit": limits[k],
             "ok": bool(np.isfinite(v) and v <= limits[k])}
            for k, v in numbers.items() if k in limits]
