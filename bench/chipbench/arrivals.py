"""Open-loop arrival schedules and request sizes.

``fixed_count_poisson`` is a Poisson process conditioned on its count:
``round(rate * duration)`` arrival times drawn uniformly and sorted, so
every seed offers exactly the same number of requests.  Request sizes are
the same multiset for every seed (the lognormal's quantiles at
(i + 0.5) / n, clipped and rounded to whole rows), shuffled by the seed.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def fixed_count_poisson(rng: np.random.Generator, rate_hz: float,
                        duration_s: float) -> np.ndarray:
    n = int(round(rate_hz * duration_s))
    return np.sort(rng.uniform(0.0, duration_s, size=n))


def lognormal_sizes(rng: np.random.Generator, n: int, median: float,
                    sigma: float, lo: int, hi: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi)
    return rng.permutation(sizes.astype(np.int64))
