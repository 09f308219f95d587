"""The host-resident data plane's plumbing (data/source.py + epoch plans).

Fast-lane tests: gather semantics of ``HostSource`` (arrays and memmaps,
local row-range views), the double-buffered ``BlockPrefetcher`` (ordering,
staging-buffer safety, error propagation), and the host-side epoch plans
reproducing exactly what the jitted in-memory epochs sample.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sampler
from repro.data.source import (BlockPrefetcher, HostSource, InMemorySource,
                               MeshPrefetcher, SyncGather, SyncMeshGather,
                               make_memmap_dataset, open_memmap_dataset)


@pytest.fixture
def xy():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((97, 5)).astype(np.float32)
    y = np.sign(rng.standard_normal(97)).astype(np.float32)
    return x, y


# --- HostSource ----------------------------------------------------------

def test_gather_indices_and_slices(xy):
    x, y = xy
    src = HostSource(x, y)
    assert (src.n, src.d) == (97, 5)
    idx = np.array([3, 96, 3, 0])
    xr, yr = src.gather(idx)
    np.testing.assert_array_equal(xr, x[idx])
    np.testing.assert_array_equal(yr, y[idx])
    xs, ys = src.gather(slice(10, 20))
    np.testing.assert_array_equal(xs, x[10:20])
    np.testing.assert_array_equal(ys, y[10:20])


def test_gather_into_out_buffers(xy):
    x, y = xy
    src = HostSource(x, y)
    out_x = np.zeros((4, 5), np.float32)
    out_y = np.zeros((4,), np.float32)
    idx = np.array([1, 2, 3, 4])
    xr, yr = src.gather(idx, out_x=out_x, out_y=out_y)
    assert xr.base is out_x or xr is out_x
    np.testing.assert_array_equal(out_x, x[idx])
    np.testing.assert_array_equal(out_y, y[idx])


def test_local_views_and_split(xy):
    x, y = xy
    src = HostSource(x, y)
    v = src.local(10, 20)
    assert v.n == 20
    xr, _ = v.gather(np.array([0, 19]))
    np.testing.assert_array_equal(xr, x[[10, 29]])
    # nested views compose offsets
    vv = v.local(5, 5)
    np.testing.assert_array_equal(vv.gather(np.array([0]))[0], x[[15]])
    parts = HostSource(x[:96], y[:96]).split(4)
    assert [p.n for p in parts] == [24] * 4
    np.testing.assert_array_equal(parts[2].gather(np.array([0]))[0], x[[48]])
    with pytest.raises(ValueError):
        src.split(7)                    # 97 does not divide
    with pytest.raises(ValueError):
        src.local(90, 20)               # out of range


def test_slice_gather_owns_its_rows(tmp_path, xy):
    """Slice gathers must COPY out of the backing store — a float32 view
    (memmap included) would silently track later writes to the file."""
    x, y = xy
    mm_x = np.memmap(tmp_path / "x.f32", np.float32, mode="w+",
                     shape=(64, 5))
    mm_y = np.memmap(tmp_path / "y.f32", np.float32, mode="w+", shape=(64,))
    mm_x[:], mm_y[:] = x[:64], y[:64]
    for src in (HostSource(x, y), HostSource(mm_x, mm_y)):
        xr, yr = src.gather(slice(0, 4))
        before = xr.copy()
        src._x[0:4] = -123.0
        src._y[0:4] = -123.0
        np.testing.assert_array_equal(xr, before)
        assert not (yr == -123.0).any()


def test_non_f32_backing_converts(xy):
    x, y = xy
    src = HostSource(x.astype(np.float64), y.astype(np.int32))
    xr, yr = src.gather(np.array([0, 1]))
    assert xr.dtype == np.float32 and yr.dtype == np.float32


def test_inmemory_source_wraps_device_arrays(xy):
    x, y = xy
    src = InMemorySource(jnp.asarray(x), jnp.asarray(y))
    assert isinstance(src.x, jax.Array)
    assert (src.n, src.d) == (97, 5)
    assert not src._host_ready          # no device->host copy until needed
    xr, _ = src.gather(np.array([5, 6]))
    assert src._host_ready
    np.testing.assert_array_equal(xr, x[[5, 6]])


def test_view_cannot_read_neighbor_shard_rows(xy):
    """A local/split view must never return rows outside its range — an
    overlong slice clamps to the view, out-of-range indices raise."""
    x, y = xy
    shard = HostSource(x[:96], y[:96]).split(4)[1]      # rows 24..48
    xs, _ = shard.gather(slice(0, 100))
    assert xs.shape[0] == 24
    np.testing.assert_array_equal(xs, x[24:48])
    # negative slice bounds follow numpy semantics relative to the VIEW
    tail, _ = shard.gather(slice(-4, None))
    np.testing.assert_array_equal(tail, x[44:48])
    head, _ = shard.gather(slice(0, -20))
    np.testing.assert_array_equal(head, x[24:28])
    with pytest.raises(IndexError):
        shard.gather(np.array([0, 24]))
    with pytest.raises(IndexError):
        shard.gather(np.array([-1]))


def test_memmap_dataset_roundtrip(tmp_path):
    src = make_memmap_dataset(str(tmp_path), 256, 8, seed=3, granule=100)
    assert (src.n, src.d) == (256, 8)
    again = open_memmap_dataset(str(tmp_path), 256, 8)
    a, b = src.gather(slice(0, 256)), again.gather(slice(0, 256))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert set(np.unique(a[1])) <= {-1.0, 1.0}
    # deterministic in (seed, granule); a different seed differs
    same = make_memmap_dataset(str(tmp_path / "c2"), 256, 8, seed=3,
                               granule=100)
    np.testing.assert_array_equal(same.gather(slice(0, 256))[0], a[0])
    other = make_memmap_dataset(str(tmp_path / "c3"), 256, 8, seed=4,
                                granule=100)
    assert not np.array_equal(other.gather(slice(0, 256))[0], a[0])


# --- prefetcher ----------------------------------------------------------

@pytest.mark.parametrize("to_device", [True, False])
def test_prefetcher_delivers_plan_order(xy, to_device):
    x, y = xy
    src = HostSource(x, y)
    rng = np.random.default_rng(1)
    plan_i = rng.integers(0, 97, (7, 16))
    plan_j = rng.integers(0, 97, (7, 12))
    with BlockPrefetcher(src, plan_i, plan_j,
                         to_device=to_device) as loader:
        for t in range(7):
            xi, yi, xj = loader.get()
            np.testing.assert_array_equal(np.asarray(xi), x[plan_i[t]])
            np.testing.assert_array_equal(np.asarray(yi), y[plan_i[t]])
            np.testing.assert_array_equal(np.asarray(xj), x[plan_j[t]])
        st = loader.stats()
    assert st["steps"] == 7 and st["gather_s"] >= 0.0


def test_prefetched_device_blocks_survive_later_steps(xy):
    """The staging discipline: blocks handed to the consumer must stay
    valid after the worker has moved on (the device_put aliasing trap)."""
    x, y = xy
    src = HostSource(x, y)
    plan = np.tile(np.arange(8), (6, 1))
    plan_i = np.stack([np.arange(t, t + 8) for t in range(6)])
    held = []
    with BlockPrefetcher(src, plan_i, plan) as loader:
        for _ in range(6):
            held.append(loader.get())
    for t, (xi, _, _) in enumerate(held):
        np.testing.assert_array_equal(np.asarray(xi), x[plan_i[t]])


def test_prefetcher_propagates_worker_errors(xy):
    x, y = xy

    class Exploding(HostSource):
        def gather(self, idx, out_x=None, out_y=None):
            raise RuntimeError("backing store went away")

    with BlockPrefetcher(Exploding(x, y), np.zeros((3, 4), np.int64),
                         np.zeros((3, 4), np.int64)) as loader:
        with pytest.raises(RuntimeError, match="backing store"):
            loader.get()


def test_sync_gather_matches_prefetcher(xy):
    x, y = xy
    src = HostSource(x, y)
    rng = np.random.default_rng(2)
    plan_i = rng.integers(0, 97, (5, 8))
    plan_j = rng.integers(0, 97, (5, 8))
    with SyncGather(src, plan_i, plan_j) as s, \
            BlockPrefetcher(src, plan_i, plan_j) as p:
        for _ in range(5):
            a, b = s.get(), p.get()
            for u, v in zip(a, b):
                np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_prefetcher_close_unblocks_failed_worker(xy):
    """A worker that dies while the ready queue is full must not hang
    close(): the error put respects the stop flag."""
    x, y = xy

    class ExplodesLate(HostSource):
        calls = 0

        def gather(self, idx, out_x=None, out_y=None):
            ExplodesLate.calls += 1
            if ExplodesLate.calls > 4:          # after depth=2 steps staged
                raise RuntimeError("boom")
            return super().gather(idx, out_x=out_x, out_y=out_y)

    plan = np.zeros((10, 8), np.int64)
    loader = BlockPrefetcher(ExplodesLate(x, y), plan, plan)
    import time as _t
    _t.sleep(0.3)                               # let the worker fill + die
    loader.close()                              # must return promptly
    assert not loader._thread.is_alive()


def test_prefetcher_close_midstream_terminates(xy):
    x, y = xy
    src = HostSource(x, y)
    plan = np.zeros((1000, 8), np.int64)
    loader = BlockPrefetcher(src, plan, plan)
    loader.get()
    loader.close()                      # must not hang
    assert not loader._thread.is_alive()


# --- host-side epoch plans ------------------------------------------------

def test_epoch_plan_matches_stepwise_sampling():
    key = jax.random.PRNGKey(9)
    idx_i, idx_j = sampler.epoch_plan(key, 301, 32, 24, steps=9)
    assert idx_i.shape == (9, 32) and idx_j.shape == (9, 24)
    keys = jax.random.split(key, 9)
    for t in range(9):
        ki, kj = jax.random.split(keys[t])
        np.testing.assert_array_equal(
            np.asarray(idx_i[t]),
            np.asarray(sampler.sample_uniform(ki, 301, 32)))
        np.testing.assert_array_equal(
            np.asarray(idx_j[t]),
            np.asarray(sampler.sample_uniform(kj, 301, 24)))


def test_parallel_epoch_plan_matches_epoch_parallel_assignment():
    key = jax.random.PRNGKey(4)
    n, i_b, j_b, workers = 160, 20, 10, 3
    i_batches, idx_jk = sampler.parallel_epoch_plan(key, n, i_b, j_b, workers)
    ki, kj = jax.random.split(key)
    np.testing.assert_array_equal(
        np.asarray(i_batches), np.asarray(sampler.epoch_batches(ki, n, i_b)))
    j_batches = sampler.epoch_batches(kj, n, j_b)
    n_i, n_j = i_batches.shape[0], j_batches.shape[0]
    k = min(workers, n_j)
    assert idx_jk.shape == (n_i, k, j_b)
    assign = (np.arange(n_i)[:, None] * k + np.arange(k)[None, :]) % n_j
    np.testing.assert_array_equal(np.asarray(idx_jk),
                                  np.asarray(j_batches)[assign])


def test_mesh_step_plan_matches_fold_in_scheme():
    key = jax.random.PRNGKey(11)
    idx_i, idx_j = sampler.mesh_step_plan(key, 8, 6, (50, 50), (25, 25, 25, 25))
    assert idx_i.shape == (2, 8) and idx_j.shape == (4, 6)
    for d in range(2):
        k_i = jax.random.fold_in(jax.random.fold_in(key, 0), d)
        np.testing.assert_array_equal(
            np.asarray(idx_i[d]),
            np.asarray(sampler.sample_uniform(k_i, 50, 8)))
    for m in range(4):
        k_j = jax.random.fold_in(jax.random.fold_in(key, 1), m)
        np.testing.assert_array_equal(
            np.asarray(idx_j[m]),
            np.asarray(sampler.sample_uniform(k_j, 25, 6)))


def test_mesh_epoch_plan_matches_step_chain():
    """satellite 1: the whole-epoch mesh plan (one vmapped dispatch, one
    host sync) is index-for-index the per-step mesh_step_plan chain the
    inline path computes."""
    key = jax.random.PRNGKey(13)
    rows_d, rows_m = (40, 40), (20, 20, 20, 20)
    plan_i, plan_j = sampler.mesh_epoch_plan(key, 8, 6, rows_d, rows_m,
                                             steps=5)
    assert isinstance(plan_i, np.ndarray) and isinstance(plan_j, np.ndarray)
    assert plan_i.shape == (5, 2, 8) and plan_j.shape == (5, 4, 6)
    keys = jax.random.split(key, 5)
    for t in range(5):
        si, sj = sampler.mesh_step_plan(keys[t], 8, 6, rows_d, rows_m)
        np.testing.assert_array_equal(plan_i[t], np.asarray(si))
        np.testing.assert_array_equal(plan_j[t], np.asarray(sj))


# --- sharded (mesh) prefetch ---------------------------------------------

def _mesh_fixture(xy, n_data=2, n_model=4, steps=6):
    x, y = xy
    src = HostSource(x[:96], y[:96])
    data_sources = src.split(n_data)
    model_sources = src.split(n_model)
    plan_i, plan_j = sampler.mesh_epoch_plan(
        jax.random.PRNGKey(3), 8, 6, tuple(s.n for s in data_sources),
        tuple(s.n for s in model_sources), steps=steps)
    sh = tuple(jax.sharding.SingleDeviceSharding(jax.devices()[0])
               for _ in range(4))
    return src, data_sources, model_sources, plan_i, plan_j, sh


def test_mesh_prefetcher_matches_inline_shard_gathers(xy):
    """The worker's per-shard gather + placed transfer delivers, step for
    step, exactly the blocks the inline SyncMeshGather assembles (and
    both match a hand concatenation of per-shard rows)."""
    src, ds, ms, plan_i, plan_j, sh = _mesh_fixture(xy)
    x96 = src.gather(slice(0, 96))[0]
    with MeshPrefetcher(ds, ms, sh, plan_i, plan_j) as p, \
            SyncMeshGather(ds, ms, sh, plan_i, plan_j) as s:
        for t in range(6):
            a, b = p.get(), s.get()
            for u, v in zip(a, b):
                np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
            want_xi = np.concatenate(
                [x96[48 * d:][plan_i[t, d]] for d in range(2)])
            want_xj = np.concatenate(
                [x96[24 * m:][plan_j[t, m]] for m in range(4)])
            np.testing.assert_array_equal(np.asarray(a[0]), want_xi)
            np.testing.assert_array_equal(np.asarray(a[2]), want_xj)
            np.testing.assert_array_equal(np.asarray(a[3]),
                                          plan_j[t].reshape(-1))
        assert p.stats()["steps"] == 6 and s.stats()["steps"] == 6
    # inline baseline reports wait == gather (nothing hidden), by design
    st = s.stats()
    assert st["wait_s"] == st["gather_s"]


def test_mesh_prefetcher_refuses_mismatched_shard_counts(xy):
    """Per-shard plans do not survive a mesh reshape: a later segment
    with different shard counts must be refused loudly."""
    _, ds, ms, plan_i, plan_j, sh = _mesh_fixture(xy)
    with MeshPrefetcher(ds, ms, sh, plan_i, plan_j) as p:
        plan_i4, plan_j2 = sampler.mesh_epoch_plan(
            jax.random.PRNGKey(5), 8, 6, (24, 24, 24, 24), (48, 48),
            steps=6)
        with pytest.raises(ValueError, match="shard counts"):
            p.extend(plan_i4, plan_j2)
        # same shard counts but a different block width: the base
        # one-geometry rule still applies
        plan_i_w, plan_j_w = sampler.mesh_epoch_plan(
            jax.random.PRNGKey(5), 16, 6, (48, 48), (24, 24, 24, 24),
            steps=6)
        with pytest.raises(ValueError, match="geometry"):
            p.extend(plan_i_w, plan_j_w)
    with SyncMeshGather(ds, ms, sh, plan_i, plan_j) as s:
        with pytest.raises(ValueError, match="shard counts"):
            s.extend(plan_i4, plan_j2)


def test_mesh_prefetcher_refuses_flat_segments(xy):
    """A flat (steps, width) plan is the FLAT prefetcher's shape; the
    sharded classes demand (steps, shards, width)."""
    _, ds, ms, plan_i, plan_j, sh = _mesh_fixture(xy)
    with pytest.raises(ValueError, match="steps, shards, width"):
        MeshPrefetcher(ds, ms, sh, plan_i[:, 0], plan_j[:, 0])
    with pytest.raises(ValueError, match="steps, shards, width"):
        SyncMeshGather(ds, ms, sh, plan_i[:, 0], plan_j[:, 0])


def test_mesh_prefetcher_transfers_to_given_shardings(xy):
    """Blocks arrive PLACED: each one's .sharding is the very object the
    prefetcher was built with, so the step's pre-placed pass-through
    (sharding equality) skips its device_put."""
    _, ds, ms, plan_i, plan_j, sh = _mesh_fixture(xy)
    with MeshPrefetcher(ds, ms, sh, plan_i, plan_j) as p:
        blocks = p.get()
        for b, want in zip(blocks, sh):
            assert b.sharding == want


# --- the accelerator staging path, run on the CPU ---------------------------

class _LateCopy:
    """A transfer that reads its host buffers only when it is waited on,
    as an asynchronous copy may read them any time before it lands: a
    slot refilled before its copy was waited for hands over the next
    step's rows.  Each landing is logged as ("landed", step, thread)."""

    def __init__(self, arrays, step, log):
        self._src, self._step, self._log = arrays, step, log
        self._out = None
        self._lock = threading.Lock()

    def block_until_ready(self):
        with self._lock:
            if self._out is None:
                self._out = tuple(np.array(a) for a in self._src)
                self._log.append(("landed", self._step,
                                  threading.current_thread().name))
        return self

    def blocks(self):
        return self.block_until_ready()._out


def _late_copy_prefetcher(kind, xy, monkeypatch, depth):
    """A prefetcher of ``kind`` ("flat" or "mesh") on the staging path
    accelerators take, its transfers replaced by ``_LateCopy``; returns
    it, the log, and the blocks each of its 2 x 6 steps must deliver."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    log = []

    def transfer(self, arrays):
        step = sum(1 for e in log if e[0] == "issued")
        log.append(("issued", step, threading.current_thread().name))
        return _LateCopy(arrays, step, log)

    if kind == "flat":
        x, y = xy
        src = HostSource(x, y)
        rng = np.random.default_rng(4)
        segs = [(rng.integers(0, 97, (6, 16)), rng.integers(0, 97, (6, 12)))
                for _ in range(2)]
        want = [(x[pi], y[pi], x[pj]) for si, sj in segs
                for pi, pj in zip(si, sj)]
        cls = type("LateBlockPrefetcher", (BlockPrefetcher,),
                   {"_transfer": transfer})
        p = cls(src, *segs[0], depth=depth)
    else:
        _, ds, ms, plan_i, plan_j, sh = _mesh_fixture(xy)
        segs = [(plan_i, plan_j), (plan_i[::-1], plan_j[::-1])]
        with SyncMeshGather(ds, ms, sh, *segs[0]) as s:
            s.extend(*segs[1])
            want = [tuple(np.array(b) for b in s.get()) for _ in range(12)]
        cls = type("LateMeshPrefetcher", (MeshPrefetcher,),
                   {"_transfer": transfer})
        p = cls(ds, ms, sh, *segs[0], depth=depth)
    p.extend(*segs[1])
    return p, log, want


@pytest.mark.parametrize("kind,depth", [("flat", 1), ("flat", 2),
                                        ("flat", 3), ("mesh", 1),
                                        ("mesh", 2)])
def test_staging_slot_reused_only_after_its_copy_landed(xy, monkeypatch,
                                                        kind, depth):
    """Over two extended segments, every step's blocks are read out of
    their staging slot no earlier than the worker waits for them: the
    consumer takes all 12 steps before reading one, so a slot refilled
    before its copy landed would deliver another step's rows."""
    p, log, want = _late_copy_prefetcher(kind, xy, monkeypatch, depth)
    with p:
        items = [p.get() for _ in range(12)]
        assert p.stats()["steps"] == 12
    for t, (item, w) in enumerate(zip(items, want)):
        assert len(item.blocks()) == len(w)
        for u, v in zip(item.blocks(), w):
            np.testing.assert_array_equal(u, v, err_msg=f"step {t}")


@pytest.mark.parametrize("kind", ["flat", "mesh"])
def test_worker_waits_for_a_copy_after_issuing_the_next(xy, monkeypatch,
                                                        kind):
    """The worker gathers and issues step t+1 before it waits for step
    t's copies, so they land while it gathers; the consumer never waits
    for them."""
    p, log, _ = _late_copy_prefetcher(kind, xy, monkeypatch, depth=2)
    with p:
        for _ in range(12):
            p.get()
        worker = p._thread.name
    mine = [(e, k) for e, k, name in log if name == worker]
    assert len(mine) == 12 + 11 and all(
        name == worker for e, k, name in log)
    for k in range(1, 12):
        assert mine.index(("landed", k - 1)) > mine.index(("issued", k))
    assert ("landed", 11) not in mine     # the last slot is still held


# --- the global manifest + range-mapped sources (multi-host resume) ------

def test_manifest_written_and_reopen_without_shape(tmp_path):
    from repro.data.source import ManifestSource, read_manifest

    src = make_memmap_dataset(str(tmp_path), 200, 6, seed=5, granule=64)
    meta = read_manifest(str(tmp_path))
    assert meta["n"] == 200 and meta["d"] == 6
    assert meta["dtype"] == "float32" and meta["version"] == 1
    # n/d omitted: resolved from the manifest
    again = open_memmap_dataset(str(tmp_path))
    np.testing.assert_array_equal(again.gather(slice(0, 200))[0],
                                  src.gather(slice(0, 200))[0])
    ms = ManifestSource(str(tmp_path))
    assert (ms.n, ms.d) == (200, 6)


def test_manifest_source_maps_lazily_per_range(tmp_path):
    """Each host/shard view opens ONLY its own row range: the root stays
    unmapped after split(), a shard maps on first gather with the right
    file offset, and the union of shard rows is the full set."""
    from repro.data.source import ManifestSource

    make_memmap_dataset(str(tmp_path), 200, 6, seed=5, granule=64)
    full_x, full_y = open_memmap_dataset(str(tmp_path)).gather(slice(0, 200))
    root = ManifestSource(str(tmp_path))
    shards = root.split(4)
    assert not root.mapped and all(not s.mapped for s in shards)
    for k, s in enumerate(shards):
        assert (s.global_offset, s.n) == (50 * k, 50)
        xs, ys = s.gather(np.arange(50))
        assert s.mapped and not root.mapped
        # the backing memmap starts AT the shard's global row, not row 0
        assert s._x.offset == 4 * 50 * k * 6
        assert s._x.shape == (50, 6)
        np.testing.assert_array_equal(xs, full_x[50 * k:50 * (k + 1)])
        np.testing.assert_array_equal(ys, full_y[50 * k:50 * (k + 1)])
    # nested views compose offsets globally
    v = root.local(30, 100).local(20, 10)
    assert (v.global_offset, v.n) == (50, 10)
    np.testing.assert_array_equal(v.gather(np.arange(10))[0],
                                  full_x[50:60])
    with pytest.raises(ValueError, match="outside"):
        root.local(150, 100)


def test_manifest_source_rejects_broken_manifests(tmp_path):
    import json

    from repro.data.source import ManifestSource, read_manifest

    make_memmap_dataset(str(tmp_path), 64, 4, seed=1)
    path = tmp_path / "manifest.json"
    meta = json.loads(path.read_text())
    del meta["x_file"]
    path.write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="missing 'x_file'"):
        read_manifest(str(tmp_path))
    meta["x_file"] = "x_64x4.f32"
    meta["dtype"] = "float64"
    path.write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="dtype"):
        ManifestSource(str(tmp_path))
