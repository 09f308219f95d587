#!/usr/bin/env python3
"""Read the numbers a cell's check compares, for the program and for the
control, on several seeds in one process: the readings the limits in
``bench/traffic/<cell>.json`` are set from.

    python3 bench/tools/calibrate.py --workload covertype-rbf.train \
        --seeds 11,12,13 [--faults]

The control is the float32 reference computed at matmul precision
``high`` (three bf16 passes) put in the program's place.  ``--faults``
also reads, for training cells, the faults each driver plants in the
reference (``fault_alphas``: half of each step's batch).  Serving
cells run a window of ``--seconds`` at the cell's own rate first.  One
JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness, refs  # noqa: E402


def fit_numbers(ctx, driver, faults):
    driver.release(ctx)
    want = driver.reference_alphas(ctx, "highest")
    out = {"program": driver.compare(ctx, ctx.stash["prog_alphas"], want),
           "control": driver.compare(
               ctx, driver.reference_alphas(ctx, "high"), want)}
    if faults:
        for name, alphas in driver.fault_alphas(ctx).items():
            out[name] = driver.compare(ctx, alphas, want)
    return out


def serve_numbers(ctx, driver):
    import jax.numpy as jnp
    import numpy as np

    st, conf = ctx.stash, ctx.config
    pick = driver.sample(ctx)
    q = jnp.asarray(driver.queries(ctx, pick))
    got = np.concatenate([np.asarray(st["served"][b]) for b in pick])
    f_ref, f_abs = refs.ref_decision(q, st["x"], st["alpha"],
                                     gamma=conf["gamma"])
    f_ctl, _ = refs.ref_decision(q, st["x"], st["alpha"],
                                 gamma=conf["gamma"], precision="high")
    return {"program": driver.compare(got, f_ref, f_abs),
            "control": driver.compare(f_ctl, f_ref, f_abs)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ctx, driver, res, _ = harness.prepare(
            args.workload, seed, args.seconds, False,
            bench=harness.with_pending(harness.load_benchmark(),
                                       args.workload))
        driver.setup(ctx)
        if hasattr(driver, "reference_alphas"):
            nums = fit_numbers(ctx, driver, args.faults)
        else:
            driver.window(ctx)
            driver.release(ctx)
            nums = serve_numbers(ctx, driver)
        nums = {k: {n: float(v) for n, v in d.items()}
                for k, d in nums.items()}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0, **nums}),
              flush=True)


if __name__ == "__main__":
    main()
