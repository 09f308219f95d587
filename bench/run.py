#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload covertype-rbf.train --seed 7 \
        --seconds 20 --trace 0

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with the reference beside its
limit).  Without a TPU, or with fewer chips than the cell asks for, it
exits nonzero and prints no result.  See ``chipbench/harness.py``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
