"""Benchmark harness: one module per paper table/figure + the roofline
reader.  Prints ``name,us_per_call,derived`` CSV rows.

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig2,table1,...]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: fig2,table1,fig3a,fig3b,roofline")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (covertype_scale, parallel_speedup, perf_dsekl,
                            roofline, small_benchmarks, xor_comparison)
    suites = {
        "fig2": xor_comparison.run,
        "table1": small_benchmarks.run,
        "fig3a": covertype_scale.run,
        "fig3b": parallel_speedup.run,
        "roofline": roofline.run,
        "perf_dsekl": perf_dsekl.run,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # pragma: no cover
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"{name}/_suite_seconds,{(time.time()-t0)*1e6:.0f},done",
              flush=True)
    if failed:
        sys.exit(f"benchmark suite(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
