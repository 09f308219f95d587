#!/usr/bin/env python3
"""Run one traced window of a cell and write what the trace holds (planes,
lines, event counts, the most frequent names, the window's span) to a
file: what one reads by hand before writing kernel patterns against it.

    python3 bench/tools/probe_trace.py --workload covertype-rbf.train \
        --seed 3 --seconds 3 --out trace_train.txt
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness, trace  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    def dump(ctx):
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(f"window {ctx.trace_window} busy_s {ctx.busy_s}\n")
            fh.write(trace.describe(ctx.trace_data, top=12) + "\n")

    bench = harness.with_pending(harness.load_benchmark(), args.workload)
    out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                           t_start=time.perf_counter(), on_trace=dump,
                           bench=bench)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
