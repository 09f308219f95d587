"""Production serving launcher: batched prefill + decode on a mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-27b \
        --batch 4 --new-tokens 16 [--data-par 2 --model-par 1]

DSEKL kernel-prediction serving (the empirical-kernel-map model; engine of
serving/dsekl_engine.py — truncate + pad, tiled kernel evaluation, support
set sharded over the ``data`` axis, micro-batched front door).  The stream
is served through the async double-buffered pipeline by default
(``flush_async``: host padding/bucketing overlaps device execution);
``--sync`` falls back to the blocking ``flush`` path, ``--cache-blocks N``
enables the kernel-map tile cache for repeated query blocks:

    PYTHONPATH=src python -m repro.launch.serve --dsekl \
        --n-train 65536 --queries 4096 --request 64 \
        [--data-par 2] [--sync] [--cache-blocks 8]

``--tenants`` puts the multi-tenant front door (DESIGN.md §12) in front
of the engine: per-tenant submit queues drained by deficit round-robin,
over-budget submits shed with typed responses, per-tenant cache quotas.
The spec is ``name[:weight[:max_tickets[:cache_quota]]],...`` (or a bare
integer for N equal tenants); ``--qos off`` swaps the scheduler for the
naive global-FIFO baseline (no shedding, no cache attribution) so the
two disciplines can be A/B'd on identical traffic:

    PYTHONPATH=src python -m repro.launch.serve --dsekl \
        --tenants "gold:2,standard:1,batch:1:4:0" --qos on \
        --queries 4096 --request 64 --cache-blocks 8

``--online`` fuses serving with continuous training (DESIGN.md §11): an
``OnlineService`` trains in a background thread over snapshots of an
appendable ``RingSource`` fed by a deterministic event stream, publishing
a new model version at every epoch boundary while the foreground loop
keeps pushing query traffic; serving latency (p50/p99) and publish
staleness are reported at the end.  ``--checkpoint-dir``/``--resume``
make the whole service kill-and-resume safe (the kill-and-resume test
drives this mode as a subprocess):

    PYTHONPATH=src python -m repro.launch.serve --dsekl --online \
        --capacity 4096 --n-prefill 1024 --events-per-epoch 128 \
        --epochs 8 [--checkpoint-dir /tmp/ck [--resume]]
"""
import os

if __name__ == "__main__" and os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_FORCE_DEVICES"])

import argparse          # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.distributed.sharding import MeshCtx              # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_production_mesh  # noqa: E402
from repro.models.model import LanguageModel                # noqa: E402
from repro.serving import ServingEngine                     # noqa: E402


def serve_dsekl(args):
    """Serve kernel predictions: build a (synthetic) trained DSEKL model,
    compact it into the prediction engine, and push a micro-batched query
    stream through the front door."""
    from repro.core.dsekl import DSEKLConfig
    from repro.serving import DSEKLPredictionEngine, EngineConfig

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x_train = jax.random.normal(ks[0], (args.n_train, args.dim))
    # Synthetic trained model: DSEKL only ever updates sampled J
    # coordinates, so a trained alpha is sparse — keep that shape here.
    alpha = jax.random.normal(ks[1], (args.n_train,))
    alpha = alpha * (jax.random.uniform(ks[2], (args.n_train,))
                     < args.support_frac)

    cfg = DSEKLConfig(kernel=args.kernel, impl="auto")
    mesh = (make_local_mesh(args.data_par, args.model_par)
            if args.data_par * args.model_par > 1 else None)
    engine = DSEKLPredictionEngine(
        cfg, alpha, x_train,
        engine_cfg=EngineConfig(query_block=args.query_block,
                                sv_block=args.sv_block,
                                max_queue=args.max_queue,
                                cache_blocks=args.cache_blocks),
        mesh=mesh)
    st = engine.stats()
    mode = "sync" if args.sync else "async"
    print(f"[serve-dsekl] n_train={st['n_train']} n_sv={st['n_sv']} "
          f"(padded {st['n_sv_padded']}, {st['n_shards']} shard(s) x "
          f"{st['sv_rows_per_shard']} rows) kernel={st['kernel']} "
          f"query_block={st['query_block']} mode={mode} "
          f"cache_blocks={args.cache_blocks}")

    queries = jax.random.normal(ks[3], (args.queries, args.dim))
    # Warm the one compiled serve function, then stream the traffic.
    engine.predict(queries[: args.query_block]).block_until_ready()
    flush = engine.flush if args.sync else engine.flush_async
    t0 = time.perf_counter()
    outs = []
    for start in range(0, args.queries, args.request):
        engine.submit(queries[start:start + args.request])
        if engine.queued == args.max_queue:
            outs.extend(flush())
    outs.extend(flush())
    outs[-1].block_until_ready()
    dt = time.perf_counter() - t0
    done = sum(int(o.shape[0]) for o in outs)
    print(f"[serve-dsekl] {done} queries in {len(outs)} requests: "
          f"{dt:.3f}s = {done / dt:,.0f} queries/s "
          f"({engine.serve_calls} serve calls)")
    if args.cache_blocks:
        ci = engine.cache_info()
        print(f"[serve-dsekl] cache: {ci['hits']} hits / "
              f"{ci['misses']} misses / {ci['evictions']} evictions "
              f"({ci['size']}/{ci['capacity']} tiles resident)")


def parse_tenants(spec: str):
    """Parse the ``--tenants`` spec into ``{name: TenantConfig}``.

    A bare integer means that many equal tenants (``t0..tN-1``);
    otherwise a comma list of ``name[:weight[:max_tickets[:cache_quota]]]``
    — e.g. ``gold:2,standard:1,batch:1:4:0`` gives ``gold`` double DRR
    credit and caps ``batch`` at 4 in-flight tickets with cache
    admission denied (quota 0)."""
    from repro.serving import TenantConfig

    if spec.strip().isdigit():
        return {f"t{i}": TenantConfig() for i in range(int(spec))}
    tenants = {}
    for part in spec.split(","):
        fields = part.strip().split(":")
        if not fields[0]:
            raise ValueError(f"empty tenant name in --tenants spec {spec!r}")
        tenants[fields[0]] = TenantConfig(
            weight=float(fields[1]) if len(fields) > 1 else 1.0,
            max_tickets=int(fields[2]) if len(fields) > 2 else 64,
            cache_quota=int(fields[3]) if len(fields) > 3 else None)
    return tenants


def serve_tenants(args):
    """Multi-tenant DSEKL serving: the same synthetic engine as
    ``serve_dsekl`` behind a ``TenantFrontDoor``, with each tenant
    pushing its own query stream through interleaved submit rounds and
    one ``pump()`` per round (so fairness, shedding, and cache
    attribution are all visible in the final per-tenant report)."""
    from repro.core.dsekl import DSEKLConfig
    from repro.serving import (DSEKLPredictionEngine, EngineConfig,
                               QoSConfig, ShedResponse, TenantFrontDoor)

    tenants = parse_tenants(args.tenants)
    key = jax.random.PRNGKey(args.seed)
    ks = jax.random.split(key, 3)
    x_train = jax.random.normal(ks[0], (args.n_train, args.dim))
    alpha = jax.random.normal(ks[1], (args.n_train,))
    alpha = alpha * (jax.random.uniform(ks[2], (args.n_train,))
                     < args.support_frac)
    engine = DSEKLPredictionEngine(
        DSEKLConfig(kernel=args.kernel, impl="auto"), alpha, x_train,
        engine_cfg=EngineConfig(query_block=args.query_block,
                                sv_block=args.sv_block,
                                max_queue=args.max_queue,
                                cache_blocks=args.cache_blocks))
    qos_on = args.qos == "on"
    fd = TenantFrontDoor(engine, tenants, qos=QoSConfig(enabled=qos_on))
    print(f"[serve-tenants] {len(tenants)} tenant(s) "
          f"({', '.join(tenants)}) qos={args.qos} "
          f"query_block={args.query_block} cache_blocks={args.cache_blocks}")

    # Interleaved rounds: every tenant submits one request-sized batch,
    # then one pump drains a DRR rotation (or a FIFO quantum).  Per-
    # ticket latency is measured from submit to pump completion.
    rounds = max(1, args.queries // (args.request * len(tenants)))
    rngs = {n: np.random.default_rng((args.seed, i))
            for i, n in enumerate(tenants)}
    t_sub, lat = {}, {n: [] for n in tenants}
    t0 = time.perf_counter()
    for _ in range(rounds):
        for name, rng in rngs.items():
            q = rng.standard_normal((args.request, args.dim)) \
                   .astype(np.float32)
            now = time.perf_counter()
            r = fd.submit(name, q)
            if not isinstance(r, ShedResponse):
                t_sub[r] = now
        for resp in fd.pump():
            lat[resp.tenant].append(time.perf_counter() - t_sub[resp.ticket])
    for resp in fd.flush():
        lat[resp.tenant].append(time.perf_counter() - t_sub[resp.ticket])
    wall = time.perf_counter() - t0

    st = fd.stats()
    total_rows = sum(t["served_rows"] for t in st["tenants"].values())
    print(f"[serve-tenants] {total_rows} queries in {wall:.3f}s = "
          f"{total_rows / wall:,.0f} queries/s over {st['pumps']} pumps")
    print(f"{'tenant':<12} {'weight':>6} {'served':>8} {'p50ms':>8} "
          f"{'p99ms':>8} {'shed%':>6}")
    for name, ts in st["tenants"].items():
        p50 = float(np.percentile(lat[name], 50) * 1e3) if lat[name] else 0.0
        p99 = float(np.percentile(lat[name], 99) * 1e3) if lat[name] else 0.0
        print(f"{name:<12} {ts['weight']:>6.1f} {ts['served_rows']:>8} "
              f"{p50:>8.2f} {p99:>8.2f} {100 * ts['shed_rate']:>6.1f}")
    if args.cache_blocks and qos_on:
        for name, oc in fd.cache_info()["owners"].items():
            print(f"[serve-tenants] cache[{name}]: {oc['hits']} hits / "
                  f"{oc['misses']} misses / {oc['bypasses']} bypasses "
                  f"({oc['resident']} resident, quota={oc['quota']})")
    print(f"TENANTS_DONE served={total_rows} pumps={st['pumps']}")


def make_event_stream(seed: int, d: int):
    """Deterministic labeled-event stream: ``chunk(epoch, m)`` returns the
    same rows for the same ``(seed, epoch)`` forever — what makes a
    resumed service replayable (the launcher re-feeds epochs < the
    restored one, then the ingest hook continues the sequence).  Labels
    are the memmap-dataset family's learnable nonlinear score."""
    w = np.random.default_rng(seed).standard_normal(d).astype(np.float32)

    def chunk(epoch: int, m: int):
        r = np.random.default_rng((seed, epoch + 1))  # epoch -1 = prefill
        x = r.standard_normal((m, d)).astype(np.float32)
        score = (np.tanh(x @ w / np.sqrt(d)) + 0.5 * np.sin(2.0 * x[:, 0])
                 + 0.18)
        return x, np.where(score >= 0.0, 1.0, -1.0).astype(np.float32)

    return chunk


def serve_online(args):
    """Continuous learning under live traffic: one ``OnlineService``
    (background fit thread + live serving engine) driven to
    ``--epochs``, with the foreground thread hammering the front door
    and measuring per-flush latency."""
    from repro.core.dsekl import DSEKLConfig
    from repro.data import RingSource
    from repro.serving import EngineConfig, OnlineService

    d = args.dim
    chunk = make_event_stream(args.seed, d)
    ring = RingSource(args.capacity, d)
    ring.append(*chunk(-1, args.n_prefill))

    replay_to = 0
    if args.resume and args.checkpoint_dir:
        from repro.checkpoint import CheckpointManager
        man = CheckpointManager(args.checkpoint_dir)
        step = man.latest_valid_step()
        if step is not None:
            _, _, extra = man.restore(step)
            replay_to = int(extra["epoch"])
    # Replay the event stream up to the restored epoch: the ring ends up
    # exactly where the interrupted run's ring was at its checkpoint.
    for e in range(replay_to):
        ring.append(*chunk(e, args.events_per_epoch))

    def feed(svc, epoch):
        svc.append(*chunk(epoch, args.events_per_epoch))

    cfg = DSEKLConfig(n_grad=args.n_grad, n_expand=args.n_expand,
                      kernel=args.kernel, impl="auto")
    svc = OnlineService(
        cfg, ring, key=jax.random.PRNGKey(args.seed),
        engine_cfg=EngineConfig(query_block=args.query_block,
                                sv_block=args.sv_block),
        publish_every=args.publish_every,
        rebuild_drift=args.rebuild_drift,
        max_epochs=args.epochs,
        checkpoint_dir=args.checkpoint_dir or None,
        resume=args.resume,
        train_nice=args.train_nice or None,
        ingest_hook=feed)
    print(f"[serve-online] n0={ring.n} capacity={args.capacity} "
          f"events/epoch={args.events_per_epoch} epochs={args.epochs} "
          f"resume@{svc.epoch} version={svc.version}")
    svc.start()

    qrng = np.random.default_rng((args.seed, "queries".__hash__() & 0xffff))
    lat = []
    served = 0
    while svc.running:
        q = qrng.standard_normal((args.request, d)).astype(np.float32)
        svc.submit(q)
        t0 = time.perf_counter()
        outs = svc.flush()
        lat.append(time.perf_counter() - t0)
        served += sum(int(np.asarray(r.f).shape[0]) for r in outs)
    svc.join()
    if svc.error is not None:
        raise svc.error
    svc.submit(qrng.standard_normal((args.request, d)).astype(np.float32))
    served += sum(int(np.asarray(r.f).shape[0]) for r in svc.flush())
    st = svc.stats()
    p50 = float(np.percentile(lat, 50) * 1e3) if lat else 0.0
    p99 = float(np.percentile(lat, 99) * 1e3) if lat else 0.0
    print(f"[serve-online] served {served} queries in {len(lat)} flushes: "
          f"p50={p50:.2f}ms p99={p99:.2f}ms")
    print(f"[serve-online] publishes={st['publishes']} "
          f"rebuilds={st['rebuilds']} staleness mean="
          f"{st['staleness_mean']:.1f} max={st['staleness_max']} "
          f"events-behind")
    print(f"ONLINE_DONE epochs={svc.epoch} version={svc.version} "
          f"publishes={st['publishes']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-27b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    # DSEKL kernel-prediction serving
    ap.add_argument("--dsekl", action="store_true",
                    help="serve DSEKL kernel predictions instead of an LM")
    ap.add_argument("--n-train", type=int, default=65_536)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--kernel", default="rbf")
    ap.add_argument("--queries", type=int, default=4096)
    ap.add_argument("--request", type=int, default=64,
                    help="queries per submitted request batch")
    ap.add_argument("--query-block", type=int, default=1024)
    ap.add_argument("--sv-block", type=int, default=4096)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--support-frac", type=float, default=0.5)
    ap.add_argument("--sync", action="store_true",
                    help="blocking flush() instead of the default async "
                         "double-buffered pipeline")
    ap.add_argument("--cache-blocks", type=int, default=0,
                    help="LRU kernel-map tile cache capacity (0 = off)")
    # Multi-tenant front door (DESIGN.md §12)
    ap.add_argument("--tenants", default="",
                    help="serve through the multi-tenant front door: "
                         "'name[:weight[:max_tickets[:cache_quota]]],...' "
                         "or a bare integer for N equal tenants")
    ap.add_argument("--qos", choices=["on", "off"], default="on",
                    help="'on' = weighted DRR + shedding + cache quotas; "
                         "'off' = global-FIFO baseline (A/B arm)")
    # Online train-to-serve mode (DESIGN.md §11)
    ap.add_argument("--online", action="store_true",
                    help="serve while a background thread keeps training "
                         "over an appendable RingSource")
    ap.add_argument("--capacity", type=int, default=4096,
                    help="ring-buffer capacity (resident event window)")
    ap.add_argument("--n-prefill", type=int, default=1024,
                    help="labeled events preloaded before serving starts")
    ap.add_argument("--events-per-epoch", type=int, default=128,
                    help="labeled events ingested at each epoch boundary")
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--n-grad", type=int, default=64)
    ap.add_argument("--n-expand", type=int, default=64)
    ap.add_argument("--publish-every", type=int, default=1)
    ap.add_argument("--rebuild-drift", type=float, default=0.5,
                    help="rebuild the serving engine when events-behind "
                         "exceeds this fraction of the training window")
    ap.add_argument("--train-nice", type=int, default=0,
                    help="run the fit thread this many nice levels below "
                         "the serving threads (Linux; 0 = same priority)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="checkpoint the service (kill-and-resume safe)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    setup_compile_cache()

    if args.tenants and args.online:
        ap.error("--tenants fronts the one-shot engine mode; for a "
                 "front door over a live OnlineService build a "
                 "TenantFrontDoor(service, ...) directly "
                 "(docs/OPERATIONS.md)")
    if args.dsekl and args.tenants:
        serve_tenants(args)
        return
    if args.dsekl and args.online:
        serve_online(args)
        return
    if args.dsekl:
        serve_dsekl(args)
        return

    if args.full:
        if "COORDINATOR_ADDRESS" in os.environ:
            jax.distributed.initialize()
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg = get_config(args.arch)
    else:
        mesh = make_local_mesh(args.data_par, args.model_par)
        cfg = get_config(args.arch, reduced=True)

    ctx = MeshCtx.for_mesh(mesh, "decode")
    model = LanguageModel(cfg)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        engine = ServingEngine(model, ctx, cache_len=args.cache_len)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
        frontend = None
        if cfg.n_frontend_tokens:
            frontend = jax.random.normal(
                jax.random.PRNGKey(2),
                (args.batch, cfg.n_frontend_tokens, cfg.d_model))
        t0 = time.perf_counter()
        out = engine.generate(params, tokens, args.new_tokens,
                              frontend=frontend)
        out.block_until_ready()
        dt = time.perf_counter() - t0
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"generated {args.new_tokens} tokens/seq in {dt:.2f}s")
    print(f"[serve] seq0: {np.asarray(out[0]).tolist()}")


if __name__ == "__main__":
    main()
