"""Traffic kind ``serve_open``: open-loop requests into the DSEKL
prediction engine (``repro.serving.DSEKLPredictionEngine``).

Set-up makes the training rows and a dual vector with the configuration's
number of support rows on the device from the seed, builds the engine
(truncate, pad, place), copies a pool of query rows of the same
distribution to the host, draws the schedule (a fixed count of Poisson
arrivals at the traffic's rate, lognormal sizes in whole rows) and warms
the serve program and the joins of one to ``warm_tiles`` query tiles.

The window: one serving thread submits every request that is due, calls
``flush_async`` and stamps each request's completion.  Latency runs from
each request's scheduled arrival to that stamp.  The engine splits what
it is given as it would for any client: more than ``max_queue`` queued
requests are served in sweeps of that many, and every sweep is padded to
whole query tiles inside the engine.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arrivals, datagen, fitcheck, program, refs
from chipbench.harness import log


def _engine(ctx, x, alpha):
    from repro.serving import DSEKLPredictionEngine
    from repro.serving.dsekl_engine import EngineConfig

    conf = ctx.config
    cfg = program.dsekl_config(conf, conf["n_train"])
    return DSEKLPredictionEngine(
        cfg, alpha, x, engine_cfg=EngineConfig(
            query_block=conf["query_block"]))


def _flush(eng, reqs):
    for r in reqs:
        eng.submit(r)
    return eng.flush_async()


def _rows_per_call(sizes, max_queue, qb):
    """Real query rows in each serve call: the engine serves its queue in
    sweeps of at most ``max_queue`` requests, each in whole tiles."""
    out = []
    for lo in range(0, len(sizes), max_queue):
        rows = int(sum(sizes[lo:lo + max_queue]))
        out += [min(qb, rows - t * qb) for t in range(-(-rows // qb))]
    return out


def setup(ctx):
    conf, tr = ctx.config, ctx.traffic
    n, qb = conf["n_train"], conf["query_block"]
    x, _ = program.rows(conf, ctx.seed, n, stream=0)
    alpha = datagen.sparse_alpha(program.seed_key(ctx.seed, 3), n=n,
                                 nnz=conf["serve_support_rows"],
                                 scale=conf["serve_alpha_scale"])
    xq, _ = program.rows(conf, ctx.seed, tr["query_pool_rows"], stream=2)
    pool = np.asarray(xq, np.float32)
    ctx.mark("data made")
    eng = _engine(ctx, x, alpha)
    ctx.mark("engine built")
    for k in range(1, tr["warm_tiles"] + 1):
        jax.block_until_ready(_flush(eng, [pool[:k * qb]]))
    ctx.mark("shapes warmed")
    ctx.stash.update(x=x, alpha=alpha, pool=pool, eng=eng)
    schedule(ctx, tr["rate_rps"], ctx.length)


def schedule(ctx, rate_rps, seconds):
    """The window's requests: arrival times, sizes and pool offsets."""
    tr = ctx.traffic
    rng = np.random.default_rng(ctx.seed)
    arr = arrivals.fixed_count_poisson(rng, rate_rps, seconds)
    sizes = arrivals.lognormal_sizes(
        rng, len(arr), tr["size_median"], tr["size_sigma"], tr["size_min"],
        tr["size_max"])
    span = ctx.stash["pool"].shape[0] - tr["size_max"]
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % span
    ctx.stash.update(arr=arr, sizes=sizes, offs=offs)


def window(ctx):
    st = ctx.stash
    qb = ctx.config["query_block"]
    eng, pool = st["eng"], st["pool"]
    arr, sizes, offs = st["arr"], st["sizes"], st["offs"]
    n_req = len(arr)
    lat = np.full(n_req, np.nan)
    lag = np.full(n_req, np.nan)
    served = [None] * n_req
    q_per_call = []
    calls0 = eng.serve_calls
    i, t_done = 0, 0.0
    t0 = time.perf_counter()
    while i < n_req:
        now = time.perf_counter() - t0
        if arr[i] > now:
            wait = arr[i] - now
            if wait > 2e-4:
                with jax.profiler.TraceAnnotation("chipbench.await_arrival"):
                    time.sleep(wait - 1e-4)
            continue
        batch = []
        while i < n_req and arr[i] <= now:
            batch.append(i)
            i += 1
        lag[batch] = now - arr[batch]
        reqs = [pool[offs[b]:offs[b] + sizes[b]] for b in batch]
        with jax.profiler.TraceAnnotation("chipbench.flush"):
            outs = _flush(eng, reqs)
        t_done = time.perf_counter() - t0
        for b, f in zip(batch, outs):
            served[b] = f
            lat[b] = t_done - arr[b]
        q_per_call += _rows_per_call(sizes[batch], eng.engine_cfg.max_queue,
                                     qb)
    ctx.window_s = t_done
    real = int(np.sum(sizes))
    calls = eng.serve_calls - calls0
    missing = sum(f is None for f in served)
    log(f"serve: {n_req} requests, {real} query rows, {calls} serve calls; "
        f"submit lag p50 {np.nanpercentile(lag, 50) * 1e3:.3f} ms, p99 "
        f"{np.nanpercentile(lag, 99) * 1e3:.3f} ms, max "
        f"{np.nanmax(lag) * 1e3:.3f} ms")
    st.update(lat_p50_ms=float(np.percentile(lat, 50) * 1e3),
              lat_p99_ms=float(np.percentile(lat, 99) * 1e3))
    st.update(attempted=n_req, failed=missing, served=served,
              q_per_call=q_per_call, serve_calls=calls, real_rows=real,
              support_rows=ctx.config["serve_support_rows"])
    return {"serve_p95_ms": float(np.percentile(lat, 95) * 1e3),
            "serve_queries_per_s": real / t_done}


def release(ctx):
    ctx.stash.pop("eng", None)
    gc.collect()


def sample(ctx):
    """Requests to compare, drawn from the seed, with the longest in it."""
    st = ctx.stash
    done = np.array([f is not None for f in st["served"]])
    idx = np.flatnonzero(done)
    rng = np.random.default_rng([ctx.seed, 7])
    pick = rng.choice(idx, size=min(ctx.traffic["check_requests"], idx.size),
                      replace=False)
    longest = idx[np.argmax(st["sizes"][idx])]
    return np.union1d(pick, [longest])


def queries(ctx, pick):
    st = ctx.stash
    return np.concatenate([st["pool"][st["offs"][b]:st["offs"][b]
                                      + st["sizes"][b]] for b in pick])


def compare(got, want, scale):
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return {"serve_rel_err": float(np.max(
        err / np.maximum(np.asarray(scale, np.float64), 1e-30)))}


def check(ctx):
    st, conf = ctx.stash, ctx.config
    pick = sample(ctx)
    got = np.concatenate([np.asarray(st["served"][b]) for b in pick])
    f_ref, f_abs = refs.ref_decision(jnp.asarray(queries(ctx, pick)),
                                     st["x"], st["alpha"],
                                     gamma=conf["gamma"])
    numbers = compare(got, f_ref, f_abs)
    numbers["serve_nonfinite"] = float(np.sum(~np.isfinite(got)))
    log(f"compared: {numbers}")
    return fitcheck.judge(numbers, ctx.traffic["limits"])
