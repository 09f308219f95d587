"""Sharded streaming DSEKL prediction engine (DESIGN.md §6-§7).

The empirical-kernel-map model keeps the training set as its
parameterization: serving is ``f(x) = K(x, X_train) @ alpha``, and at
production traffic the support set — not training — is the scaling
bottleneck.  The engine turns the research-path chunk loop
(``core/dsekl.decision_function_ref``: one jitted dispatch per train chunk,
re-dispatched per query batch) into a compile-once serving stack:

  1. **Truncate + pad.**  The trained model is compacted to its support set
     (``dsekl.truncate`` — zero-weight rows contribute exactly nothing) and
     zero-padded up to a fixed tile geometry: ``n_shards * sv_block``
     support rows, ``query_block`` query rows.  One jitted function at ONE
     shape serves every query batch forever after.

  2. **Tiled evaluation.**  Each serve call runs the streaming matvec
     (``kops.kernel_matvec_tiled``): a single compiled ``lax.scan`` over
     (query_block x sv_block) kernel tiles on the ref path, or the Pallas
     block kernels (``block.choose_predict_blocks`` orientation, K never in
     HBM) on TPU — the same tiling machinery as the streaming train pass.

  3. **Support-set sharding.**  With a mesh, the padded support rows and
     their alpha shard over the ``data`` axis (queries replicated); each
     device computes the partial kernel map over its shard and one psum of
     |query_block| floats completes f.  Throughput scales with devices;
     per-call communication is independent of the support-set size.

  4. **Micro-batching front door.**  ``submit()`` queues ragged query
     batches; ``flush()`` / ``flush_async()`` concatenate them, pad/bucket
     into fixed ``query_block`` tiles, serve every tile through the one
     compiled function, and split results back per request — the DSEKL
     analogue of ``ServingEngine``'s batched prefill/decode split.

  5. **Async double buffering** (DESIGN.md §7).  ``flush_async()`` pipelines
     the serve sweep: while the device executes query tile *n*, the host
     pads/buckets tile *n+1* into one of two reusable ping-pong staging
     buffers (input buffers donated to XLA where the backend supports
     donation).  ``jax.block_until_ready`` runs only at result handoff, so
     host batching work and device kernel work overlap instead of
     alternating.

  6. **Query-block caching** (DESIGN.md §7).  With ``cache_blocks > 0`` the
     engine keeps an LRU cache of *materialized kernel-map tiles*
     ``K(tile, X_sv)`` keyed on the tile's content hash.  A repeated query
     tile (the solver's validation set every epoch, duplicate production
     batches) skips the kernel evaluation entirely — the hit path is one
     (query_block x n_sv_padded) matvec against the current alpha, which
     stays correct across ``update_alpha()`` because K is
     alpha-independent.  ``cache_info()`` surfaces hit/miss/eviction
     counters.

  7. **Cache admission / ownership accounting** (DESIGN.md §12).  The
     multi-tenant front door (``serving/tenancy.py``) attributes cache
     traffic to an *owner* (``set_cache_owner``) and can pin per-owner
     residency quotas (``set_cache_quota``): an owner over its quota
     evicts its OWN least-recently-used tile, and a ``quota == 0`` owner
     bypasses the cache entirely (served through the streaming path, no
     dense K materialized) — so a unique-query-heavy tenant cannot evict
     hot tenants' tiles.  ``cache_info()["owners"]`` reports per-owner
     hit/miss/eviction/bypass/resident counters.

Thread-safety contract (documented per method below): the engine is a
single-serving-thread object.  ``submit``/``flush*``/``predict`` and the
cache/owner mutators must be called from ONE thread at a time (the
tenancy front door and ``OnlineService`` serialize them behind their
serve locks); the ONLY method safe to call concurrently with an
in-flight serve sweep is ``update_alpha`` (the sweep completes on the
``(alpha, version)`` it captured at sweep start).  ``stats()`` and
``cache_info()`` return fresh snapshot dicts — mutating them never
touches engine state.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dsekl
from repro.core.dsekl import DSEKLConfig
from repro.kernels.dsekl import ops as kops

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static serving geometry (fixed at engine build; hashable)."""
    query_block: int = 1024     # padded query rows per serve call
    sv_block: int = 4096        # support rows per kernel tile (ref scan)
    truncate_tol: float = 1e-8  # |alpha| below this is not a support vector
                                # (negative keeps EVERY row: required for
                                # update_alpha, used by the solver eval path)
    max_queue: int = 64         # submitted batches before submit auto-flushes
    data_axis: str = "data"     # mesh axis the support set shards over
    cache_blocks: int = 0       # LRU capacity in cached kernel-map tiles;
                                # 0 disables the cache.  Each cached tile is
                                # query_block * n_sv_padded * 4 bytes, and a
                                # MISS materializes that tile densely (ref
                                # evaluation — the memory/recompute trade of
                                # a KV-style cache).  Enable only for traffic
                                # with repeated query blocks; unique-heavy
                                # traffic is better served cache-off.


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


class DSEKLPredictionEngine:
    """Compile-once batched kernel-prediction engine for a trained model.

    >>> eng = DSEKLPredictionEngine(cfg, state.alpha, x_train)
    >>> f = eng.predict(x_query)                   # any number of rows
    >>> t0 = eng.submit(batch_a); t1 = eng.submit(batch_b)
    >>> outs = eng.flush_async()                   # [f_a, f_b], pipelined
    """

    def __init__(self, cfg: DSEKLConfig, alpha: Array, x_train: Array, *,
                 engine_cfg: EngineConfig = EngineConfig(),
                 mesh: Optional[Mesh] = None, alpha_version: int = 0):
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.mesh = mesh
        ec = engine_cfg

        # --- 1. truncate to the support set (host-side, build time) -------
        a_sv, x_sv = dsekl.truncate(alpha, x_train, ec.truncate_tol)
        self.n_train = int(x_train.shape[0])
        self.n_sv = int(a_sv.shape[0])
        self.d = int(x_train.shape[1])

        # --- 2. pad to the fixed tile geometry ----------------------------
        shards = int(mesh.shape[ec.data_axis]) if mesh is not None else 1
        self.n_shards = shards
        # Shrink the SV tile for small support sets so padding stays bounded
        # (still a fixed, compile-time constant for this engine).
        per_shard = max(1, -(-max(self.n_sv, 1) // shards))
        self.sv_block = min(ec.sv_block, _round_up(per_shard, 128))
        self.n_sv_padded = _round_up(max(self.n_sv, 1),
                                     shards * self.sv_block)
        pad = self.n_sv_padded - self.n_sv
        a_p = jnp.pad(a_sv.astype(jnp.float32), (0, pad))
        x_p = jnp.pad(x_sv.astype(jnp.float32), ((0, pad), (0, 0)))

        # --- 3. place the support set on the mesh -------------------------
        if mesh is not None:
            self._x_sv = jax.device_put(
                x_p, NamedSharding(mesh, P(ec.data_axis, None)))
            self._a_sv = jax.device_put(
                a_p, NamedSharding(mesh, P(ec.data_axis)))
        else:
            self._x_sv, self._a_sv = x_p, a_p

        self._serve = self._build_serve(donate=False)
        # Async path: the query-tile argument is donated so XLA recycles the
        # ping-pong input buffers.  CPU jax does not implement donation and
        # warns on every call, so only donate where it is honoured.
        self._serve_donated = (
            self._build_serve(donate=True)
            if jax.default_backend() in ("gpu", "tpu") else self._serve)
        self._queue: List[Array] = []
        # Results carried by auto-flush, tagged with the alpha version
        # their sweep captured.
        self._done: List[Tuple[Array, int]] = []
        self.serve_calls = 0
        self.async_flushes = 0
        # Published-model versioning (DESIGN.md §11): ``update_alpha``
        # bumps the version under ``_alpha_lock``; every serve sweep
        # captures ``(alpha, version)`` ONCE at sweep start, so a swap
        # landing mid-sweep can never produce a torn mix of alphas.
        self.alpha_version = int(alpha_version)
        self._alpha_lock = threading.Lock()

        # --- kernel-map tile cache (LRU, content-hash keyed) --------------
        self._cache: "OrderedDict[bytes, Array]" = OrderedDict()
        self._cache_hits = 0
        self._cache_misses = 0
        self._cache_evictions = 0
        # Multi-tenant cache accounting (DESIGN.md §12): tiles are
        # attributed to the owner set at insert time; per-owner quotas
        # bound residency, quota 0 bypasses the cache.
        self._cache_owner: Optional[str] = None
        self._cache_quota: dict = {}        # owner -> max resident tiles
        self._tile_owner: dict = {}         # tile key -> owner
        self._owner_cache: dict = {}        # owner -> counter dict
        self._kmap = None                   # compiled lazily on first miss
        self._apply = jax.jit(jnp.matmul)   # f = K_cached @ alpha
        self._staging: Optional[List[np.ndarray]] = None  # ping-pong bufs

    # ------------------------------------------------------------------
    # The one compiled serve function: (query_block, D) -> (query_block,).
    # ------------------------------------------------------------------

    def _build_serve(self, donate: bool = False):
        cfg, ec = self.cfg, self.engine_cfg
        sv_block = self.sv_block

        def local_f(xq: Array, xs: Array, a: Array) -> Array:
            return kops.kernel_matvec_tiled(
                xq, xs, a, kernel_name=cfg.kernel,
                kernel_params=cfg.kernel_params, z_block=sv_block,
                impl=cfg.impl)

        donate_kw = {"donate_argnums": (0,)} if donate else {}
        if self.mesh is None:
            return jax.jit(local_f, **donate_kw)

        axis = ec.data_axis

        def sharded_f(xq: Array, xs: Array, a: Array) -> Array:
            # Partial kernel map over the local SV shard, completed by one
            # psum of |query_block| floats over the data axis.
            return jax.lax.psum(local_f(xq, xs, a), axis)

        mapped = jax.shard_map(
            sharded_f, mesh=self.mesh,
            in_specs=(P(None, None), P(axis, None), P(axis)),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(mapped, **donate_kw)

    def _build_kmap(self):
        """Compiled kernel-map materializer: (query_block, D) -> K tile of
        shape (query_block, n_sv_padded) — the cache-miss path.

        Materializing K is the point of the cache (the hit path contracts
        it against any future alpha), so this path is inherently the dense
        ref evaluation — the Pallas kernels exist to NEVER materialize K
        and cannot produce one.  Peak memory is O(query_block *
        n_sv_padded), the same as the cached tile itself; size
        ``cache_blocks`` accordingly."""
        cfg, ec = self.cfg, self.engine_cfg

        def local_k(xq: Array, xs: Array) -> Array:
            return kops.kernel_block(xq, xs, kernel_name=cfg.kernel,
                                     kernel_params=cfg.kernel_params)

        if self.mesh is None:
            return jax.jit(local_k)
        axis = ec.data_axis
        mapped = jax.shard_map(
            local_k, mesh=self.mesh,
            in_specs=(P(None, None), P(axis, None)),
            out_specs=P(None, axis),        # K tile sharded like the SVs
            check_vma=False,
        )
        return jax.jit(mapped)

    # ------------------------------------------------------------------
    # Kernel-map tile cache.
    # ------------------------------------------------------------------

    @property
    def _cache_on(self) -> bool:
        return self.engine_cfg.cache_blocks > 0

    @staticmethod
    def _tile_key(tile: np.ndarray) -> bytes:
        return hashlib.sha1(tile.tobytes()).digest()

    # --- multi-tenant cache accounting (DESIGN.md §12) ----------------

    def set_cache_owner(self, owner: Optional[str]) -> None:
        """Attribute subsequent cache traffic (hits, inserts, bypasses) to
        ``owner`` (``None`` = the anonymous default owner).  Called by the
        tenancy front door before each per-tenant drain.  NOT thread-safe
        against an in-flight serve sweep — set it from the serving thread
        only."""
        self._cache_owner = owner

    def set_cache_quota(self, owner: Optional[str],
                        quota: Optional[int]) -> None:
        """Bound ``owner``'s resident kernel-map tiles to ``quota``.

        ``quota >= 1``: when an insert by this owner exceeds the quota,
        the owner's OWN least-recently-used tile is evicted — other
        owners' tiles are untouched.  ``quota == 0``: the owner's misses
        bypass the cache entirely (served through the streaming path; no
        dense K tile is ever materialized for it).  ``None`` removes the
        quota.  Serving-thread only, like ``set_cache_owner``."""
        if quota is None:
            self._cache_quota.pop(owner, None)
        else:
            self._cache_quota[owner] = int(quota)
        self._owner_counters(owner)         # materialize the counter row

    def _owner_counters(self, owner: Optional[str]) -> dict:
        c = self._owner_cache.get(owner)
        if c is None:
            c = {"hits": 0, "misses": 0, "evictions": 0, "bypasses": 0,
                 "resident": 0}
            self._owner_cache[owner] = c
        return c

    def _evict_tile(self, key: bytes) -> None:
        del self._cache[key]
        victim_owner = self._tile_owner.pop(key, None)
        self._cache_evictions += 1
        self._owner_counters(victim_owner)["evictions"] += 1
        self._owner_counters(victim_owner)["resident"] -= 1

    def _owner_lru_key(self, owner: Optional[str],
                       exclude: Optional[bytes] = None) -> Optional[bytes]:
        for k in self._cache:                # oldest -> newest
            if k != exclude and self._tile_owner.get(k) == owner:
                return k
        return None

    def _serve_tile_cached(self, tile: np.ndarray, a_sv: Array) -> Array:
        """Serve one padded (query_block, D) host tile through the cache:
        hit = one matvec against the cached kernel-map tile (no kernel
        evaluation); miss = materialize K(tile, X_sv), cache it, matvec.
        ``a_sv`` is the sweep's CAPTURED alpha — the hit path must
        contract against the alpha the sweep started with, not whatever
        ``update_alpha`` may have published since.

        Per-owner admission: an owner at ``quota == 0`` never inserts
        (its misses run the streaming serve — no dense K); an owner over
        a positive quota evicts its own LRU tile, so one owner's churn
        cannot push another owner's hot tiles out."""
        owner = self._cache_owner
        oc = self._owner_counters(owner)
        key = self._tile_key(tile)
        k_tile = self._cache.get(key)
        if k_tile is not None:
            self._cache.move_to_end(key)
            self._cache_hits += 1
            oc["hits"] += 1
            return self._apply(k_tile, a_sv)
        self._cache_misses += 1
        oc["misses"] += 1
        quota = self._cache_quota.get(owner)
        if quota == 0:                       # admission denied: stream it
            oc["bypasses"] += 1
            self.serve_calls += 1
            return self._serve(jnp.asarray(tile), self._x_sv, a_sv)
        if self._kmap is None:
            self._kmap = self._build_kmap()
        k_tile = self._kmap(jnp.asarray(tile), self._x_sv)
        self.serve_calls += 1
        self._cache[key] = k_tile
        self._tile_owner[key] = owner
        oc["resident"] += 1
        if quota is not None and oc["resident"] > quota:
            self._evict_tile(self._owner_lru_key(owner))
        while len(self._cache) > self.engine_cfg.cache_blocks:
            # Global pressure: prefer recycling the inserting owner's own
            # LRU tile so churn stays inside the churning owner's share.
            victim = self._owner_lru_key(owner, exclude=key)
            self._evict_tile(victim if victim is not None
                             else next(iter(self._cache)))
        return self._apply(k_tile, a_sv)

    def cache_info(self) -> dict:
        """Hit/miss/eviction counters of the kernel-map tile cache, plus
        per-owner accounting under ``"owners"`` (DESIGN.md §12).

        Returns an immutable SNAPSHOT: a fresh dict (fresh nested dicts
        included) built at call time — callers may mutate it freely
        without corrupting engine counters, and it never reflects later
        serving activity."""
        return {
            "enabled": self._cache_on,
            "capacity": self.engine_cfg.cache_blocks,
            "size": len(self._cache),
            "hits": self._cache_hits,
            "misses": self._cache_misses,
            "evictions": self._cache_evictions,
            "tile_bytes": 4 * self.engine_cfg.query_block * self.n_sv_padded,
            "owners": {
                (o if o is not None else "_default"): {
                    **c, "quota": self._cache_quota.get(o)}
                for o, c in self._owner_cache.items()},
        }

    def cache_clear(self) -> None:
        """Drop every resident tile (cumulative hit/miss/eviction counters
        are kept; per-owner ``resident`` counts reset).  Serving-thread
        only."""
        self._cache.clear()
        self._tile_owner.clear()
        for c in self._owner_cache.values():
            c["resident"] = 0

    # ------------------------------------------------------------------
    # Model update (the solver's eval path).
    # ------------------------------------------------------------------

    def _capture_alpha(self) -> Tuple[Array, int]:
        """The sweep-start capture: one coherent ``(alpha, version)``
        pair.  Every serve path reads the model exactly once, here — a
        concurrent ``update_alpha`` lands either entirely before or
        entirely after a sweep, never inside it."""
        with self._alpha_lock:
            return self._a_sv, self.alpha_version

    def update_alpha(self, alpha: Array, *,
                     version: Optional[int] = None) -> None:
        """Swap in new dual coefficients without rebuilding the engine.

        Only legal on a *keep-all* engine (``truncate_tol < 0``, so no row
        was dropped and the padded geometry is alpha-independent) — the
        solver's eval path builds one of these and calls ``update_alpha``
        every epoch.  Cached kernel-map tiles stay valid: K depends on the
        support points only, so repeated validation blocks keep hitting
        across alpha updates.

        The swap is atomic with respect to in-flight serve sweeps: a
        ``flush_async`` already running completes against the alpha it
        captured at sweep start, and the NEXT sweep sees the new model.
        ``alpha_version`` advances monotonically (or to an explicit
        ``version`` — the online service stamps service-global version
        numbers so tags survive engine rebuilds); tagged results report
        which version served them.

        This is the ONE engine method that is safe to call from a thread
        other than the serving thread (it publishes under the alpha
        lock); everything else is serving-thread only.
        """
        if self.n_sv != self.n_train:
            raise ValueError(
                "update_alpha requires a keep-all engine (truncate_tol < 0):"
                f" {self.n_train - self.n_sv} rows were truncated at build")
        alpha = jnp.asarray(alpha, jnp.float32)
        if alpha.shape != (self.n_train,):
            raise ValueError(
                f"alpha must be ({self.n_train},); got {alpha.shape}")
        a_p = jnp.pad(alpha, (0, self.n_sv_padded - self.n_train))
        if self.mesh is not None:
            a_p = jax.device_put(
                a_p, NamedSharding(self.mesh, P(self.engine_cfg.data_axis)))
        with self._alpha_lock:
            self._a_sv = a_p
            self.alpha_version = (self.alpha_version + 1
                                  if version is None else int(version))

    # ------------------------------------------------------------------
    # Direct path: predict any number of query rows.
    # ------------------------------------------------------------------

    def predict(self, x_query: Array) -> Array:
        """f(x_query) — pads/buckets into ``query_block`` tiles, every tile
        served by the same compiled function (through the kernel-map cache
        when enabled).  The model is captured once at entry: the whole
        call evaluates one alpha version.

        Blocking: returns after dispatching every tile (jax async — the
        caller blocks on first use of the result).  Serving-thread only;
        safe to overlap with ``update_alpha`` from another thread."""
        return self._predict(x_query, self._capture_alpha()[0])

    def _predict(self, x_query: Array, a_sv: Array) -> Array:
        n = x_query.shape[0]
        if n == 0:
            return jnp.zeros((0,), jnp.float32)
        if self._cache_on:
            merged = np.asarray(x_query, np.float32)
            qb = self.engine_cfg.query_block
            outs = []
            for start in range(0, n, qb):
                tile = np.zeros((qb, self.d), np.float32)
                rows = merged[start:start + qb]
                tile[: rows.shape[0]] = rows
                outs.append(self._serve_tile_cached(tile, a_sv))
            return jnp.concatenate(outs)[:n]
        tiles = kops.tile_rows(jnp.asarray(x_query, jnp.float32),
                               self.engine_cfg.query_block)
        outs = []
        for b in range(tiles.shape[0]):
            outs.append(self._serve(tiles[b], self._x_sv, a_sv))
            self.serve_calls += 1
        return jnp.concatenate(outs)[:n]

    # ------------------------------------------------------------------
    # Async double-buffered pipeline (DESIGN.md §7).
    # ------------------------------------------------------------------

    def _predict_pipelined(self, merged: np.ndarray, a_sv: Array) -> Array:
        """Serve a merged (n, D) host array with host/device overlap.

        Tile *n* is dispatched (async) and while the device executes it the
        host pads/buckets tile *n+1* into the other ping-pong staging
        buffer.  Before reusing staging buffer ``b % 2`` for tile *b* the
        pipeline blocks on tile *b - 2*'s result — the double-buffer
        discipline that both bounds in-flight memory to two tiles and
        guarantees the buffer's previous host-to-device transfer completed.
        The only other synchronization is one ``block_until_ready`` on the
        concatenated result at handoff.  ``a_sv`` is the sweep's captured
        alpha: every tile of one sweep serves the same model version.
        """
        n = merged.shape[0]
        if n == 0:
            return jnp.zeros((0,), jnp.float32)
        qb = self.engine_cfg.query_block
        n_tiles = -(-n // qb)
        if self._staging is None:
            self._staging = [np.zeros((qb, self.d), np.float32)
                             for _ in range(2)]
        outs: List[Array] = []
        for b in range(n_tiles):
            if b >= 2:
                jax.block_until_ready(outs[b - 2])
            buf = self._staging[b % 2]
            lo = b * qb
            rows = merged[lo: lo + qb]
            buf[: rows.shape[0]] = rows
            buf[rows.shape[0]:] = 0.0
            if self._cache_on:
                outs.append(self._serve_tile_cached(buf, a_sv))
                continue
            xq = jax.device_put(buf)        # async H2D into a fresh buffer
            outs.append(self._serve_donated(xq, self._x_sv, a_sv))
            self.serve_calls += 1
        f = jnp.concatenate(outs)[:n]
        jax.block_until_ready(f)            # the one handoff sync
        return f

    # ------------------------------------------------------------------
    # Micro-batching front door: queue -> pad/bucket -> serve -> split.
    # ------------------------------------------------------------------

    def submit(self, x_query: Array) -> int:
        """Queue one ragged query batch; returns its ticket — the batch's
        index into the list the next ``flush()`` / ``flush_async()``
        returns.

        When ``max_queue`` batches are already pending, ``submit`` no
        longer raises: it auto-flushes the pending queue through the async
        pipeline, holds those results engine-side, and enqueues the new
        batch.  Tickets keep counting across auto-flushes, so the next
        explicit flush returns every batch submitted since the previous
        one, in submission order.

        Auto-flush bounds the *queue*, not the *results*: every held
        result stays resident until an explicit ``flush()`` /
        ``flush_async()`` collects it, so an unbounded submit-only loop
        grows memory linearly with traffic.  Producers on long streams
        must flush periodically (the consumption point of their results
        is the natural place).

        Blocking: O(1) unless the auto-flush fires, in which case it
        runs a full async serve sweep inline.  NOT thread-safe — one
        serving thread owns submit/flush (``OnlineService`` and the
        tenancy front door put a lock in front; multi-threaded producers
        go through those).
        """
        if x_query.ndim != 2 or x_query.shape[1] != self.d:
            raise ValueError(
                f"query batch must be (n, {self.d}); got {x_query.shape}")
        if len(self._queue) >= self.engine_cfg.max_queue:
            self._done.extend(self._flush_queue(pipelined=True))
        self._queue.append(jnp.asarray(x_query, jnp.float32))
        return len(self._done) + len(self._queue) - 1

    def _flush_queue(self, pipelined: bool) -> List[Tuple[Array, int]]:
        """Serve the pending queue micro-batched and split per ticket.
        One sweep = one captured ``(alpha, version)``; every returned
        result is tagged with that version."""
        if not self._queue:
            return []
        a_sv, version = self._capture_alpha()
        sizes = [int(b.shape[0]) for b in self._queue]
        if pipelined:
            merged = np.concatenate(
                [np.asarray(b, np.float32) for b in self._queue], axis=0)
            self._queue = []
            self.async_flushes += 1
            f = self._predict_pipelined(merged, a_sv)
        else:
            merged = jnp.concatenate(self._queue, axis=0)
            self._queue = []
            f = self._predict(merged, a_sv)
        outs, start = [], 0
        for s in sizes:
            outs.append((f[start:start + s], version))
            start += s
        return outs

    def flush(self) -> List[Array]:
        """Serve every pending batch micro-batched: one concatenation, one
        pad to ``query_block`` tiles, one serve sweep, split per ticket.
        The support set is streamed once per TILE, not once per request.
        Results auto-flushed by ``submit`` are returned first, preserving
        submission order.

        Blocking: dispatches every tile synchronously (host and device
        alternate).  Serving-thread only, like ``submit``."""
        return [f for f, _ in self.flush_tagged()]

    def flush_async(self) -> List[Array]:
        """``flush()`` through the double-buffered pipeline: host-side
        padding/bucketing of each query tile overlaps device execution of
        the previous one, with a single ``block_until_ready`` at result
        handoff.  Same results, same ordering contract as ``flush()``.

        Blocking: returns only after the whole sweep's results are
        device-complete (the one handoff sync).  Serving-thread only."""
        return [f for f, _ in self.flush_async_tagged()]

    def flush_tagged(self) -> List[Tuple[Array, int]]:
        """``flush()`` with version tags: each result is paired with the
        ``alpha_version`` its serve sweep captured.  Batches auto-flushed
        by ``submit`` keep the tag of the sweep that actually served
        them, which may be older than the tag of this flush's sweep."""
        outs = self._done + self._flush_queue(pipelined=False)
        self._done = []
        return outs

    def flush_async_tagged(self) -> List[Tuple[Array, int]]:
        """``flush_async()`` with version tags (see ``flush_tagged``)."""
        outs = self._done + self._flush_queue(pipelined=True)
        self._done = []
        return outs

    @property
    def queued(self) -> int:
        return len(self._queue)

    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Serving geometry — what the compile-once contract is bound to.

        Like ``cache_info()``, returns an immutable snapshot: fresh
        top-level and nested dicts, safe for callers to mutate and never
        updated in place by later serving."""
        return {
            "n_train": self.n_train,
            "n_sv": self.n_sv,
            "n_sv_padded": self.n_sv_padded,
            "support_fraction": self.n_sv / max(self.n_train, 1),
            "sv_block": self.sv_block,
            "query_block": self.engine_cfg.query_block,
            "n_shards": self.n_shards,
            "sv_rows_per_shard": self.n_sv_padded // self.n_shards,
            "kernel": self.cfg.kernel,
            "impl": self.cfg.impl,
            "serve_calls": self.serve_calls,
            "async_flushes": self.async_flushes,
            "alpha_version": self.alpha_version,
            "cache": self.cache_info(),
        }


def engine_from_fit(cfg: DSEKLConfig, result, x_train: Array,
                    **kwargs) -> DSEKLPredictionEngine:
    """Build the serving engine straight from a ``solver.fit`` result."""
    return DSEKLPredictionEngine(cfg, result.state.alpha, x_train, **kwargs)
