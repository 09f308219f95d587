#!/usr/bin/env python3
"""Find the highest request rate a serving cell sustains: one set-up, then
a window of ``--seconds`` at each offered rate, printing the offered and
completed query rate and the latency percentiles.

    python3 bench/tools/knee_sweep.py \
        --workload covertype-rbf.serve-poisson --seed 5 \
        --rates 500,1000,1500,2000 --seconds 5

The knee is the last rate whose completed rate keeps up with the offered
one (no growing backlog); the cell offers about four fifths of it.  Each
line also counts the compilations inside its window.  A cell that
``BENCHMARK.json`` does not list yet is taken from ``bench/pending``.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402


def main():
    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    bench = harness.with_pending(harness.load_benchmark(), args.workload)
    ctx, driver, _, _ = harness.prepare(args.workload, args.seed,
                                        args.seconds, False, bench=bench)
    compiles = harness.CompileCounter()
    driver.setup(ctx)
    for rate in [float(r) for r in args.rates.split(",")]:
        driver.schedule(ctx, rate, args.seconds)
        n0 = compiles.n
        e2e = driver.window(ctx)
        st = ctx.stash
        offered = float(np.sum(st["sizes"])) / args.seconds
        print(json.dumps({"rate_rps": rate, "offered_qps": offered,
                          "completed_qps": e2e["serve_queries_per_s"],
                          "p95_ms": e2e["serve_p95_ms"],
                          "p50_ms": st["lat_p50_ms"],
                          "p99_ms": st["lat_p99_ms"],
                          "window_s": ctx.window_s,
                          "requests": st["attempted"],
                          "compiles_in_window": compiles.n - n0,
                          "serve_calls": st["serve_calls"]}), flush=True)


if __name__ == "__main__":
    main()
