"""Host-resident training data plane (DESIGN.md §8).

The paper's pitch is that doubly stochastic optimization "takes into
account the entire data set" — but the seed training entry points kept the
whole (N, D) array device-resident, capping training at device memory
while serving already streamed.  This module is the missing data plane:

  * ``DataSource`` — the protocol the training stack gathers rows through.
    A source owns ``n`` rows of dimension ``d`` and serves
    ``gather(idx) -> (x_rows, y_rows)`` as float32 numpy arrays.
  * ``InMemorySource`` — wraps device (or host) arrays; `solver.fit`
    routes it straight onto the existing fully-jitted in-memory epochs
    (current behavior, zero overhead).
  * ``HostSource`` — numpy / ``np.memmap`` backing.  Rows live on host
    (or on disk); only the sampled blocks of a step ever reach the
    device.  ``local(offset, length)`` carves the per-shard views the
    distributed path gives each data-axis shard.
  * ``BlockPrefetcher`` — the double-buffered gather pipeline: a host
    thread gathers the sampled I/J rows for step t+1 into ping-pong
    staging buffers while the device runs step t (the training-side
    sibling of the serving engine's ``flush_async`` pipeline; on GPU/TPU
    the staging buffers would be pinned host memory).

Together with the block-parametrized step core (``core/dsekl.grad_block``
— compiled shapes are (n_grad, n_expand, D) only, never N) this trains
datasets larger than device memory: see ``solver.fit`` with a
``HostSource``, ``launch/train.py --dsekl --data mmap``, and
``examples/train_outofcore.py``.
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
from typing import List, Optional, Protocol, Tuple, Union, runtime_checkable

import numpy as np

Index = Union[np.ndarray, slice]


@runtime_checkable
class DataSource(Protocol):
    """What the training stack needs from a dataset: sized row access."""

    @property
    def n(self) -> int: ...

    @property
    def d(self) -> int: ...

    def gather(self, idx: Index,
               out_x: Optional[np.ndarray] = None,
               out_y: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]: ...

    def gather_x(self, idx: Index,
                 out: Optional[np.ndarray] = None) -> np.ndarray: ...


class HostSource:
    """Rows on host memory or disk (``np.ndarray`` / ``np.memmap``).

    ``offset``/``length`` make a zero-copy view over a row range — the
    distributed path gives each data-axis shard a local view so a shard
    only ever reads (and pages in) its own rows.
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, *,
                 offset: int = 0, length: Optional[int] = None):
        if x.ndim != 2 or y.ndim != 1 or x.shape[0] != y.shape[0]:
            raise ValueError(
                f"x must be (n, d) and y (n,); got {x.shape} / {y.shape}")
        length = x.shape[0] - offset if length is None else length
        if offset < 0 or offset + length > x.shape[0]:
            raise ValueError(
                f"row range [{offset}, {offset + length}) outside 0..{x.shape[0]}")
        self._x, self._y = x, y
        self._offset, self._n = int(offset), int(length)

    @property
    def n(self) -> int:
        return self._n

    @property
    def d(self) -> int:
        return int(self._x.shape[1])

    @property
    def nbytes(self) -> int:
        """Bytes the full backing rows of THIS view would occupy as f32 —
        what a device-resident copy would cost (the "device budget" the
        out-of-core path avoids)."""
        return 4 * self._n * (self.d + 1)

    def _absolute(self, idx: Index) -> Index:
        if isinstance(idx, slice):
            # Numpy slice semantics relative to THIS view (negative bounds
            # count from the view's end), then clamp before offsetting: a
            # local/split view must never read (or page in) a neighboring
            # shard's rows.
            if idx.step not in (None, 1):
                raise ValueError("strided row slices are not supported; "
                                 "gather an index array instead")
            start = idx.start or 0
            stop = self._n if idx.stop is None else idx.stop
            if start < 0:
                start += self._n
            if stop < 0:
                stop += self._n
            start = min(max(start, 0), self._n)
            stop = min(max(stop, 0), self._n)
            return slice(start + self._offset, stop + self._offset)
        idx = np.asarray(idx)
        if idx.size and (idx.min() < 0 or idx.max() >= self._n):
            raise IndexError(
                f"indices outside the view's [0, {self._n}) row range")
        return idx + self._offset if self._offset else idx

    @staticmethod
    def _finish(rows: np.ndarray, out: Optional[np.ndarray],
                sliced: bool) -> np.ndarray:
        """Land gathered rows in ``out`` (staging buffer) or as an OWNED
        float32 array.  Fancy indexing already copied; a SLICE of the
        backing store is a view (np.asarray is a no-op at matching dtype,
        memmap included), so it must be copied explicitly or the
        "gathered" rows would alias the file mapping / backing array."""
        if out is not None:
            out[: rows.shape[0]] = rows
            return out[: rows.shape[0]]
        if sliced:
            return np.array(rows, np.float32)
        return np.asarray(rows, np.float32)

    def gather(self, idx: Index,
               out_x: Optional[np.ndarray] = None,
               out_y: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Copy the requested rows out of the backing store as float32.

        With ``out_*`` staging buffers the copy lands in-place (the
        prefetcher's ping-pong buffers); otherwise fresh arrays are
        returned.  For a memmap this is the actual disk read.
        """
        ai = self._absolute(idx)
        sliced = isinstance(ai, slice)
        return (self._finish(self._x[ai], out_x, sliced),
                self._finish(self._y[ai], out_y, sliced))

    def gather_x(self, idx: Index,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
        """``gather`` for feature rows only — expansion-block and
        prediction-streaming callers never need the labels, and for a
        memmap skipping y skips its disk pages."""
        ai = self._absolute(idx)
        return self._finish(self._x[ai], out, isinstance(ai, slice))

    def local(self, offset: int, length: int) -> "HostSource":
        """A view over rows [offset, offset + length) of THIS view."""
        return HostSource(self._x, self._y,
                          offset=self._offset + offset, length=length)

    def split(self, n_shards: int) -> List["HostSource"]:
        """Equal per-shard local views (row order preserved; requires
        ``n % n_shards == 0``, matching the mesh sharding contract)."""
        if self._n % n_shards:
            raise ValueError(f"{self._n} rows do not split into {n_shards}")
        rows = self._n // n_shards
        return [self.local(s * rows, rows) for s in range(n_shards)]


class InMemorySource(HostSource):
    """Current behavior: the dataset is device-resident.

    ``solver.fit`` unwraps ``.x``/``.y`` and runs the fully-jitted
    in-memory epochs; the host-side ``gather`` (inherited) exists so the
    same source also works anywhere a ``DataSource`` is expected — that is
    what the HostSource-vs-InMemorySource parity tests compare.  The host
    mirror is materialized lazily, on the first host-side access — the
    standard fit path never pays the device-to-host copy.
    """

    def __init__(self, x, y):
        import jax.numpy as jnp
        self.x = jnp.asarray(x, jnp.float32)
        self.y = jnp.asarray(y, jnp.float32)
        if self.x.ndim != 2 or self.y.ndim != 1 \
                or self.x.shape[0] != self.y.shape[0]:
            raise ValueError(f"x must be (n, d) and y (n,); got "
                             f"{self.x.shape} / {self.y.shape}")
        self._host_ready = False

    def _ensure_host(self) -> None:
        if not self._host_ready:
            super().__init__(np.asarray(self.x), np.asarray(self.y))
            self._host_ready = True

    @property
    def n(self) -> int:
        return int(self.x.shape[0])

    @property
    def d(self) -> int:
        return int(self.x.shape[1])

    @property
    def nbytes(self) -> int:
        return 4 * self.n * (self.d + 1)

    def gather(self, idx: Index,
               out_x: Optional[np.ndarray] = None,
               out_y: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        self._ensure_host()
        return super().gather(idx, out_x=out_x, out_y=out_y)

    def gather_x(self, idx: Index,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
        self._ensure_host()
        return super().gather_x(idx, out=out)

    def local(self, offset: int, length: int) -> HostSource:
        self._ensure_host()
        return super().local(offset, length)

    def split(self, n_shards: int) -> List[HostSource]:
        self._ensure_host()
        return super().split(n_shards)


# ---------------------------------------------------------------------------
# Appendable ring source (online training; DESIGN.md §11).
# ---------------------------------------------------------------------------

class RingSnapshot(HostSource):
    """A frozen, owned copy of a ring window — what one training epoch
    replays while the writer keeps appending.

    ``snapshot()`` copies the live window out of the ring, so the view is
    immutable by construction: later appends (including wrap-around
    overwrites of the very rows it captured) can never alias it.  The
    snapshot carries its identity in *absolute event coordinates*:
    ``high_water`` is the writer's total at snapshot time, so the
    snapshot covers absolute rows ``[base, high_water)`` with
    ``base = high_water - n`` — the coordinate system the online service
    uses to carry alpha across support-set rebuilds and to measure
    staleness (events behind at publish).  Reads past ``n`` are rejected
    by the inherited bounds check.
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, *, version: int,
                 high_water: int):
        super().__init__(x, y)
        self.version = int(version)
        self.high_water = int(high_water)

    @property
    def base(self) -> int:
        """Absolute event id of row 0 (``high_water - n``)."""
        return self.high_water - self.n


class RingSource(HostSource):
    """Appendable ring-buffer ``HostSource``: bounded backing, unbounded
    stream.

    The writer ``append``s labeled events; ``total`` counts every event
    ever appended (monotonic), while only the most recent
    ``min(total, capacity)`` rows stay resident — older rows are
    overwritten in ring order.  Training never reads the live ring
    directly: it takes a ``snapshot()`` — a monotonically *versioned*,
    frozen ``HostSource`` copy of the current window — so an in-flight
    epoch replays a fixed index range while events keep arriving
    (``solver.fit`` snapshots automatically when handed a live ring).

    Row 0 of the live view is always the OLDEST resident event; gathers
    through the ``DataSource`` protocol are mapped through the ring and
    serialized against ``append`` (torn rows are impossible), but the
    window they read from can shift between calls — hence the snapshot
    discipline for anything that needs repeatable indices.

    ``RingSource.memmap(directory, capacity, d)`` backs the ring with
    disk memmaps (append persistence for large windows); the in-memory
    default is plain numpy.
    """

    def __init__(self, capacity: int, d: int, *,
                 x: Optional[np.ndarray] = None,
                 y: Optional[np.ndarray] = None):
        capacity, d = int(capacity), int(d)
        if capacity <= 0 or d <= 0:
            raise ValueError(f"capacity and d must be positive; got "
                             f"{capacity} / {d}")
        xb = np.zeros((capacity, d), np.float32) if x is None else x
        yb = np.zeros((capacity,), np.float32) if y is None else y
        if xb.shape != (capacity, d) or yb.shape != (capacity,):
            raise ValueError(
                f"backing must be ({capacity}, {d}) / ({capacity},); got "
                f"{xb.shape} / {yb.shape}")
        super().__init__(xb, yb)
        self._capacity = capacity
        self._total = 0
        self._version = 0
        self._lock = threading.Lock()

    # -- sizes ----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def total(self) -> int:
        """Events ever appended (monotonic high-water mark)."""
        return self._total

    @property
    def n(self) -> int:
        """Resident rows: ``min(total, capacity)``."""
        return min(self._total, self._capacity)

    @property
    def nbytes(self) -> int:
        return 4 * self.n * (self.d + 1)

    # -- writer ---------------------------------------------------------
    def append(self, x_rows: np.ndarray, y_rows: np.ndarray) -> int:
        """Append labeled events; returns the new ``total``.

        An append larger than the ring would overwrite part of itself,
        so it is rejected rather than silently truncated.
        """
        x_rows = np.asarray(x_rows, np.float32)
        y_rows = np.asarray(y_rows, np.float32)
        if x_rows.ndim != 2 or y_rows.ndim != 1 \
                or x_rows.shape[0] != y_rows.shape[0] \
                or x_rows.shape[1] != self.d:
            raise ValueError(
                f"events must be (m, {self.d}) / (m,); got "
                f"{x_rows.shape} / {y_rows.shape}")
        m = int(x_rows.shape[0])
        if m > self._capacity:
            raise ValueError(
                f"append of {m} rows exceeds ring capacity "
                f"{self._capacity}")
        with self._lock:
            pos = self._total % self._capacity
            end = pos + m
            if end <= self._capacity:
                self._x[pos:end] = x_rows
                self._y[pos:end] = y_rows
            else:
                k = self._capacity - pos
                self._x[pos:] = x_rows[:k]
                self._y[pos:] = y_rows[:k]
                self._x[: end - self._capacity] = x_rows[k:]
                self._y[: end - self._capacity] = y_rows[k:]
            self._total += m
            return self._total

    # -- reader ---------------------------------------------------------
    def _window(self) -> Tuple[int, int]:
        """(live row count, physical index of logical row 0); callers
        hold ``self._lock``."""
        n = min(self._total, self._capacity)
        start = self._total % self._capacity if self._total > self._capacity \
            else 0
        return n, start

    def _ring_index(self, idx: Index) -> np.ndarray:
        """Map a logical index (0 = oldest resident row) onto physical
        ring positions — always a fancy index, since the window may wrap
        the physical buffer edge.  Callers hold ``self._lock``."""
        n, start = self._window()
        if isinstance(idx, slice):
            if idx.step not in (None, 1):
                raise ValueError("strided row slices are not supported; "
                                 "gather an index array instead")
            start_l = idx.start or 0
            stop_l = n if idx.stop is None else idx.stop
            if start_l < 0:
                start_l += n
            if stop_l < 0:
                stop_l += n
            idx = np.arange(min(max(start_l, 0), n),
                            min(max(stop_l, 0), n))
        else:
            idx = np.asarray(idx)
            if idx.size and (idx.min() < 0 or idx.max() >= n):
                raise IndexError(
                    f"indices outside the view's [0, {n}) row range")
        return (start + idx) % self._capacity

    def gather(self, idx: Index,
               out_x: Optional[np.ndarray] = None,
               out_y: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        with self._lock:
            ai = self._ring_index(idx)
            return (self._finish(self._x[ai], out_x, False),
                    self._finish(self._y[ai], out_y, False))

    def gather_x(self, idx: Index,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
        with self._lock:
            ai = self._ring_index(idx)
            return self._finish(self._x[ai], out, False)

    def local(self, offset: int, length: int) -> HostSource:
        raise TypeError("a live RingSource has no stable row range; take "
                        "a snapshot() and carve views from that")

    def split(self, n_shards: int) -> List[HostSource]:
        raise TypeError("a live RingSource has no stable row range; take "
                        "a snapshot() and split that")

    # -- snapshots ------------------------------------------------------
    def snapshot(self) -> RingSnapshot:
        """Freeze the current window: a versioned, owned ``HostSource``
        copy training can replay while appends continue."""
        with self._lock:
            n, start = self._window()
            self._version += 1
            phys = (start + np.arange(n)) % self._capacity
            # Fancy indexing copies — the snapshot owns its rows and can
            # never observe later appends (wrap-around included).
            return RingSnapshot(
                np.asarray(self._x[phys], np.float32),
                np.asarray(self._y[phys], np.float32),
                version=self._version, high_water=self._total)

    @classmethod
    def memmap(cls, directory: str, capacity: int, d: int) -> "RingSource":
        """A ring with disk-memmap backing (``w+`` — reuses existing
        files of the same shape): the memmap-append variant for windows
        larger than comfortable host memory."""
        os.makedirs(directory, exist_ok=True)
        x = np.memmap(os.path.join(directory, f"ring_x_{capacity}x{d}.f32"),
                      np.float32, mode="w+", shape=(capacity, d))
        y = np.memmap(os.path.join(directory, f"ring_y_{capacity}.f32"),
                      np.float32, mode="w+", shape=(capacity,))
        return cls(capacity, d, x=x, y=y)


# ---------------------------------------------------------------------------
# Double-buffered prefetch.
# ---------------------------------------------------------------------------

class _Buffers:
    """One ping-pong staging slot: the gathered blocks of one step."""

    __slots__ = ("xi", "yi", "xj")

    def __init__(self, n_grad: int, n_flat_expand: int, d: int):
        self.xi = np.zeros((n_grad, d), np.float32)
        self.yi = np.zeros((n_grad,), np.float32)
        self.xj = np.zeros((n_flat_expand, d), np.float32)


class BlockPrefetcher:
    """Gather (and stage) step t+1's sampled rows while the device runs
    step t.

    Built from host-side epoch plans (``sampler.epoch_plan`` /
    ``parallel_epoch_plan``): ``plan_i (steps, n_grad)`` indexes the
    gradient rows, ``plan_j (steps, m)`` the (flattened) expansion rows.
    A worker thread fills one of ``depth`` (default 2, ping-pong)
    preallocated staging-buffer sets per step and — with ``to_device``
    (the default) — immediately issues the host-to-device transfer from
    the staging buffer and hands the (possibly still landing) device
    blocks to the consumer.  The runtime may read a staging buffer until
    its copy lands, so the worker recycles a slot only after waiting for
    its copies, and waits for them only once it has issued the NEXT
    step's transfer: the copies land while it gathers, and the consumer
    never waits on them on the host.  The staging buffers are plain numpy
    arrays; on GPU/TPU the runtime copies out of them on its own
    transfer threads.  On CPU, where ``device_put`` aliases host memory,
    no staging buffers exist (see ``__init__``).

    The prefetcher is **multi-epoch**: the constructor's plan is only the
    first *segment*, and ``extend(plan_i, plan_j)`` queues further epochs
    onto the SAME worker thread and staging buffers.  The unified trainer
    (``core/trainer.HostedPlan``) plans each epoch one ahead, so the
    worker streams straight across epoch boundaries instead of draining,
    re-spawning, and re-warming at every edge; ``stats()`` therefore
    accumulates over the prefetcher's whole life.  A segment with zero
    steps (an epoch whose I-partition is empty) is legal and skipped.

    The consumer's ``get()`` hands over the next step's ready (device)
    blocks; the ready queue is bounded at ``depth`` so at most ``depth``
    steps of blocks are in flight — the same double-buffer discipline as
    the serving engine's ``flush_async``, with the one epoch-boundary
    ``block_until_ready`` living in the driver.  With
    ``to_device=False`` the returned numpy views are valid until the next
    ``get()``.

    ``stats()`` reports how much of the gather work the overlap hid:
    ``gather_s`` is worker time spent copying/transferring rows,
    ``wait_s`` is consumer time blocked on an unfilled buffer, and
    ``not_ready`` counts the steps whose blocks were not ready when the
    consumer asked for them.  Under ``jax.profiler.trace`` the worker
    writes a ``GATHER_SPAN`` and an ``H2D_SPAN`` host span per step.
    """

    GATHER_SPAN = "dsekl.loader.gather"
    H2D_SPAN = "dsekl.loader.h2d"

    def __init__(self, source: DataSource,
                 plan_i: Optional[np.ndarray] = None,
                 plan_j: Optional[np.ndarray] = None, *, depth: int = 2,
                 to_device: bool = True):
        self._source = source
        self._to_device = to_device
        self._depth = max(depth, 1)
        # The ping-pong staging buffers exist for accelerators, where the
        # H2D DMA wants a stable (pinned) host source and the copy out of
        # the buffer is real.  CPU jax instead ALIASES aligned host memory
        # on device_put — there the worker gathers into FRESH per-step
        # arrays (one copy total, exactly what the sync baseline pays) and
        # hands ownership to the device, so no staging buffers exist.
        import jax
        self._staging = (not to_device
                         or jax.default_backend() in ("gpu", "tpu"))
        self._free: "queue.Queue[_Buffers]" = queue.Queue()
        self._buffers_ready = False
        # Plan segments (one per epoch) feeding the single worker thread.
        self._segments: "queue.Queue[Tuple[np.ndarray, np.ndarray]]" = \
            queue.Queue()
        self.steps = 0
        self._widths: Optional[Tuple[int, int]] = None
        self._ready: "queue.Queue[object]" = queue.Queue(maxsize=self._depth)
        self._inflight: Optional[_Buffers] = None
        self._stop = False
        self.gather_s = 0.0
        self.wait_s = 0.0
        self.not_ready = 0
        if plan_i is not None:
            self.extend(plan_i, plan_j)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # -- geometry hooks (overridden by the sharded MeshPrefetcher) ------
    def _segment_widths(self, plan_i: np.ndarray,
                        plan_j: np.ndarray) -> Tuple[int, ...]:
        """The block geometry one segment implies — must match across
        every segment of the prefetcher's life."""
        return (int(plan_i.shape[1]),
                int(plan_j[0].size) if plan_i.shape[0] else
                int(np.prod(plan_j.shape[1:], dtype=int)))

    def _width_error(self, widths: Tuple[int, ...]) -> ValueError:
        return ValueError(
            f"segment step widths {widths} != first segment's "
            f"{self._widths}; one prefetcher serves one block geometry")

    def _make_buffers(self) -> "_Buffers":
        return _Buffers(self._widths[0], self._widths[1], self._source.d)

    def extend(self, plan_i: np.ndarray, plan_j: np.ndarray) -> None:
        """Queue another epoch's plan onto the live worker (called from
        the consumer thread).  Step widths must match the first segment —
        the staging buffers are shared across the prefetcher's life."""
        plan_i, plan_j = np.asarray(plan_i), np.asarray(plan_j)
        if plan_j.shape[0] != plan_i.shape[0]:
            raise ValueError("plan_i / plan_j step counts differ")
        widths = self._segment_widths(plan_i, plan_j)
        if self._widths is None:
            self._widths = widths
            if self._staging:
                for _ in range(self._depth):
                    self._free.put(self._make_buffers())
                self._buffers_ready = True
        elif widths != self._widths and plan_i.shape[0]:
            raise self._width_error(widths)
        self.steps += int(plan_i.shape[0])
        self._segments.put((plan_i, plan_j))

    def _next_indices(self):
        """Worker-side generator of per-step (idx_i, idx_j), blocking
        between segments until the consumer extends the plan; ends when
        ``close()`` raises the stop flag."""
        while True:
            if self._stop:
                return
            try:
                seg_i, seg_j = self._segments.get(timeout=0.05)
            except queue.Empty:
                continue
            for t in range(seg_i.shape[0]):
                yield seg_i[t], seg_j[t]

    # -- gather/transfer hooks (overridden by the sharded MeshPrefetcher)
    def _gather_staged(self, idx_i: np.ndarray, idx_j: np.ndarray,
                       bufs: "_Buffers") -> Tuple:
        """Fill the staging slot with one step's rows; returns the host
        views to transfer."""
        self._source.gather(idx_i, out_x=bufs.xi, out_y=bufs.yi)
        self._source.gather_x(idx_j.reshape(-1), out=bufs.xj)
        return bufs.xi, bufs.yi, bufs.xj

    def _gather_fresh(self, idx_i: np.ndarray, idx_j: np.ndarray) -> Tuple:
        """Gather one step's rows into fresh owned arrays (the CPU path,
        where ``device_put`` aliases aligned host memory)."""
        xi, yi = self._source.gather(idx_i)
        xj = self._source.gather_x(idx_j.reshape(-1))
        return xi, yi, xj

    def _transfer(self, arrays: Tuple) -> Tuple:
        """Issue the host-to-device transfer for one step's blocks."""
        import jax
        return jax.device_put(arrays)

    def _worker(self) -> None:
        try:
            import jax
            from jax.profiler import TraceAnnotation
            # The slot whose transfer was issued last, with its blocks:
            # the runtime may still be copying out of it, so it re-enters
            # the free queue only after its copies have landed.
            landing = None

            def land(slot, blocks):
                jax.block_until_ready(blocks)
                self._free.put(slot)

            for idx_i, idx_j in self._next_indices():
                bufs = None
                if self._staging:
                    while bufs is None:
                        if self._stop:
                            return
                        if landing is not None and self._free.empty():
                            land(*landing)       # depth 1: the only slot
                            landing = None
                        try:
                            bufs = self._free.get(timeout=0.05)
                        except queue.Empty:
                            continue
                t0 = time.perf_counter()
                if self._staging:
                    with TraceAnnotation(self.GATHER_SPAN):
                        host = self._gather_staged(idx_i, idx_j, bufs)
                    if self._to_device:
                        with TraceAnnotation(self.H2D_SPAN):
                            item = self._transfer(host)
                            # The previous step's copies ran while this
                            # step gathered: wait for them (worker-side
                            # only) and recycle their slot.
                            if landing is not None:
                                land(*landing)
                        landing = (bufs, item)
                    else:
                        item = bufs
                else:
                    with TraceAnnotation(self.GATHER_SPAN):
                        host = self._gather_fresh(idx_i, idx_j)
                    with TraceAnnotation(self.H2D_SPAN):
                        item = self._transfer(host)
                        jax.block_until_ready(item)
                self.gather_s += time.perf_counter() - t0
                while True:
                    if self._stop:
                        return
                    try:
                        self._ready.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
        except Exception as e:                   # surface in the consumer
            while not self._stop:                # never block a dead queue:
                try:                             # close() must still join
                    self._ready.put(e, timeout=0.05)
                    break
                except queue.Full:
                    continue

    def get(self) -> Tuple:
        """Blocks until the next step's blocks are ready; returns
        ``(xi, yi, xj_flat)`` — device arrays with ``to_device`` (the
        default), else numpy views valid until the next ``get()``."""
        if self._inflight is not None:
            self._free.put(self._inflight)
            self._inflight = None
        t0 = time.perf_counter()
        try:
            item = self._ready.get_nowait()
        except queue.Empty:
            self.not_ready += 1
            item = self._ready.get()
        self.wait_s += time.perf_counter() - t0
        if isinstance(item, Exception):
            raise item
        if isinstance(item, _Buffers):
            self._inflight = item
            return item.xi, item.yi, item.xj
        return item

    def close(self) -> None:
        self._stop = True
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "BlockPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def stats(self) -> dict:
        return {"steps": self.steps, "gather_s": self.gather_s,
                "wait_s": self.wait_s, "not_ready": self.not_ready}


class SyncGather:
    """The no-overlap baseline with the same ``get()``/``extend()``
    contract: every gather (and transfer) runs inline on the consumer
    thread — what the prefetch-overlap benchmark cell compares against."""

    def __init__(self, source: DataSource,
                 plan_i: Optional[np.ndarray] = None,
                 plan_j: Optional[np.ndarray] = None, *,
                 to_device: bool = True):
        import collections
        self._source = source
        # Consumed entries are popped so a fit-lived loader never retains
        # the whole run's plans (at most the planned-ahead epoch is held).
        self._steps: "collections.deque[Tuple[np.ndarray, np.ndarray]]" = \
            collections.deque()
        self.steps = 0
        self._to_device = to_device
        self.gather_s = 0.0
        self.not_ready = 0
        if plan_i is not None:
            self.extend(plan_i, plan_j)

    def extend(self, plan_i: np.ndarray, plan_j: np.ndarray) -> None:
        plan_i, plan_j = np.asarray(plan_i), np.asarray(plan_j)
        if plan_j.shape[0] != plan_i.shape[0]:
            raise ValueError("plan_i / plan_j step counts differ")
        for t in range(plan_i.shape[0]):
            self._steps.append((plan_i[t], plan_j[t]))
        self.steps += int(plan_i.shape[0])

    def get(self) -> Tuple:
        t0 = time.perf_counter()
        idx_i, idx_j = self._steps.popleft()
        self.not_ready += 1
        xi, yi = self._source.gather(idx_i)
        xj = self._source.gather_x(idx_j.reshape(-1))
        if self._to_device:
            import jax
            xi, yi, xj = jax.device_put((xi, yi, xj))
        self.gather_s += time.perf_counter() - t0
        return xi, yi, xj

    def close(self) -> None:
        pass

    def __enter__(self) -> "SyncGather":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def stats(self) -> dict:
        # Inline: the consumer waits out every gather, and no block is
        # ever ready before it is asked for.
        return {"steps": self.steps, "gather_s": self.gather_s,
                "wait_s": self.gather_s, "not_ready": self.not_ready}


# ---------------------------------------------------------------------------
# Sharded (mesh) prefetch: the same worker/segment machinery over per-shard
# source views, transferring straight to the mesh step's shardings.
# ---------------------------------------------------------------------------

class _MeshBuffers:
    """One ping-pong staging slot for a SHARDED step: the concatenated
    per-shard blocks plus the flattened local expansion indices."""

    __slots__ = ("xi", "yi", "xj", "ij")

    def __init__(self, n_data: int, n_grad: int, n_model: int,
                 n_expand: int, d: int):
        self.xi = np.zeros((n_data * n_grad, d), np.float32)
        self.yi = np.zeros((n_data * n_grad,), np.float32)
        self.xj = np.zeros((n_model * n_expand, d), np.float32)
        self.ij = np.zeros((n_model * n_expand,), np.int32)

    def views(self) -> Tuple:
        return self.xi, self.yi, self.xj, self.ij


class MeshPrefetcher(BlockPrefetcher):
    """``BlockPrefetcher`` generalized to SHARDED plan segments — the mesh
    fit's data plane (DESIGN.md §13).

    Segments are whole-epoch mesh plans (``sampler.mesh_epoch_plan``):
    ``plan_i (steps, n_data, n_grad)`` / ``plan_j (steps, n_model,
    n_expand)``, LOCAL indices into the per-shard ``HostSource`` views.
    The worker gathers step t+1's per-shard ``(xi, yi, xj, idx_j)``
    blocks (``distributed.gather_mesh_blocks_from`` semantics: per-shard
    rows concatenated in shard order) and issues ``jax.device_put``
    STRAIGHT to the mesh step's shardings — so by the time the consumer
    calls the step, every block is placed (or landing) and ``step_host``'s
    device_put is a no-op: the H2D transfer leaves the critical path,
    exactly like the flat prefetcher's.  Worker/segment/staging
    machinery, stats, error propagation, and the multi-epoch ``extend``
    contract are all inherited.

    ``shardings`` is ``step_host.shardings`` — the ``(xi, yi, xj,
    idx_j)`` ``NamedSharding`` tuple of ``make_distributed_block_step``.
    A segment whose SHARD COUNTS differ from the first segment's is
    refused: per-shard plans are meaningless across a mesh reshape, so
    an elastic rescale must re-split the sources and build a fresh
    prefetcher (which resume does — the loader never outlives the plan).
    """

    GATHER_SPAN = "dsekl.mesh.gather"
    H2D_SPAN = "dsekl.mesh.h2d"

    def __init__(self, data_sources: List[DataSource],
                 model_sources: List[DataSource], shardings: Tuple,
                 plan_i: Optional[np.ndarray] = None,
                 plan_j: Optional[np.ndarray] = None, *, depth: int = 2):
        self._data_sources = list(data_sources)
        self._model_sources = list(model_sources)
        self._shardings = tuple(shardings)
        super().__init__(self._data_sources[0], plan_i, plan_j,
                         depth=depth, to_device=True)

    # -- geometry -------------------------------------------------------
    def _segment_widths(self, plan_i: np.ndarray,
                        plan_j: np.ndarray) -> Tuple[int, ...]:
        if plan_i.ndim != 3 or plan_j.ndim != 3:
            raise ValueError(
                f"mesh plan segments are (steps, shards, width); got "
                f"{plan_i.shape} / {plan_j.shape}")
        return (int(plan_i.shape[1]), int(plan_i.shape[2]),
                int(plan_j.shape[1]), int(plan_j.shape[2]))

    def _width_error(self, widths: Tuple[int, ...]) -> ValueError:
        if (widths[0], widths[2]) != (self._widths[0], self._widths[2]):
            return ValueError(
                f"segment shard counts (data={widths[0]}, "
                f"model={widths[2]}) != first segment's "
                f"(data={self._widths[0]}, model={self._widths[2]}); "
                "per-shard plans do not survive a mesh reshape — re-split "
                "the sources and build a fresh prefetcher (elastic "
                "rescale resumes do this)")
        return super()._width_error(widths)

    def _make_buffers(self) -> _MeshBuffers:
        return _MeshBuffers(*self._widths, self._data_sources[0].d)

    # -- gather/transfer ------------------------------------------------
    def _gather_staged(self, idx_i: np.ndarray, idx_j: np.ndarray,
                       bufs: _MeshBuffers) -> Tuple:
        ng, ne = idx_i.shape[1], idx_j.shape[1]
        for d, s in enumerate(self._data_sources):
            s.gather(idx_i[d], out_x=bufs.xi[d * ng:(d + 1) * ng],
                     out_y=bufs.yi[d * ng:(d + 1) * ng])
        for m, s in enumerate(self._model_sources):
            s.gather_x(idx_j[m], out=bufs.xj[m * ne:(m + 1) * ne])
        bufs.ij[:] = idx_j.reshape(-1)
        return bufs.views()

    def _gather_fresh(self, idx_i: np.ndarray, idx_j: np.ndarray) -> Tuple:
        gi = [s.gather(idx_i[d]) for d, s in enumerate(self._data_sources)]
        xi = np.concatenate([g[0] for g in gi])
        yi = np.concatenate([g[1] for g in gi])
        xj = np.concatenate([s.gather_x(idx_j[m])
                             for m, s in enumerate(self._model_sources)])
        return xi, yi, xj, np.ascontiguousarray(idx_j.reshape(-1))

    def _transfer(self, arrays: Tuple) -> Tuple:
        import jax
        return tuple(jax.device_put(a, sh)
                     for a, sh in zip(arrays, self._shardings))


class SyncMeshGather:
    """The inline mesh baseline with the prefetcher's ``get()``/
    ``extend()`` contract: per-shard gathers run on the consumer thread
    and the blocks are returned as HOST arrays (``step_host`` pays the
    H2D inline, exactly the pre-overlap shipping path) — the
    ``--no-prefetch`` A/B arm of the ``mesh_overlap`` bench cell."""

    def __init__(self, data_sources: List[DataSource],
                 model_sources: List[DataSource], shardings: Tuple = (),
                 plan_i: Optional[np.ndarray] = None,
                 plan_j: Optional[np.ndarray] = None):
        import collections
        del shardings                   # constructor-compatible; unused
        self._data_sources = list(data_sources)
        self._model_sources = list(model_sources)
        self._steps: "collections.deque[Tuple[np.ndarray, np.ndarray]]" = \
            collections.deque()
        self.steps = 0
        self.gather_s = 0.0
        self.not_ready = 0
        self._n_shards: Optional[Tuple[int, int]] = None
        if plan_i is not None:
            self.extend(plan_i, plan_j)

    def extend(self, plan_i: np.ndarray, plan_j: np.ndarray) -> None:
        plan_i, plan_j = np.asarray(plan_i), np.asarray(plan_j)
        if plan_j.shape[0] != plan_i.shape[0]:
            raise ValueError("plan_i / plan_j step counts differ")
        if plan_i.ndim != 3 or plan_j.ndim != 3:
            raise ValueError(
                f"mesh plan segments are (steps, shards, width); got "
                f"{plan_i.shape} / {plan_j.shape}")
        shards = (int(plan_i.shape[1]), int(plan_j.shape[1]))
        if self._n_shards is None:
            self._n_shards = shards
        elif shards != self._n_shards and plan_i.shape[0]:
            raise ValueError(
                f"segment shard counts (data={shards[0]}, "
                f"model={shards[1]}) != first segment's "
                f"(data={self._n_shards[0]}, model={self._n_shards[1]})")
        for t in range(plan_i.shape[0]):
            self._steps.append((plan_i[t], plan_j[t]))
        self.steps += int(plan_i.shape[0])

    def get(self) -> Tuple:
        t0 = time.perf_counter()
        idx_i, idx_j = self._steps.popleft()
        self.not_ready += 1
        gi = [s.gather(idx_i[d]) for d, s in enumerate(self._data_sources)]
        xi = np.concatenate([g[0] for g in gi])
        yi = np.concatenate([g[1] for g in gi])
        xj = np.concatenate([s.gather_x(idx_j[m])
                             for m, s in enumerate(self._model_sources)])
        self.gather_s += time.perf_counter() - t0
        return xi, yi, xj, idx_j.reshape(-1)

    def close(self) -> None:
        pass

    def __enter__(self) -> "SyncMeshGather":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def stats(self) -> dict:
        # Inline: the consumer waits out every gather, and no block is
        # ever ready before it is asked for.
        return {"steps": self.steps, "gather_s": self.gather_s,
                "wait_s": self.gather_s, "not_ready": self.not_ready}


# ---------------------------------------------------------------------------
# Memmapped synthetic datasets (examples / benchmarks / launch --data mmap).
# ---------------------------------------------------------------------------

def split_holdout(source: HostSource, *, cap: int = 2048, frac: int = 8
                  ) -> Tuple[HostSource, np.ndarray, np.ndarray]:
    """The standard out-of-core train/validation split: hold out the LAST
    ``min(cap, n // frac)`` rows (at least one) as the validation slice
    and return ``(train_view, x_val, y_val)`` — the train view never sees
    the held-out rows.  Shared by the example, the launcher's
    ``--data mmap`` mode, and the ``train_outofcore`` bench cell so all
    three measure the identical split.  The validation rows are gathered
    through a LOCAL view of their range, so a range-mapping source
    (``ManifestSource``) maps only the holdout's file pages, never the
    whole set."""
    n_val = max(min(cap, source.n // frac), 1)
    train = source.local(0, source.n - n_val)
    x_val, y_val = source.local(source.n - n_val, n_val).gather(
        slice(0, n_val))
    return train, x_val, y_val


def make_memmap_dataset(directory: str, n: int, d: int, *, seed: int = 0,
                        granule: int = 8192) -> HostSource:
    """Write a learnable synthetic (N, D) classification set to disk as
    float32 memmaps, one ``granule`` of rows at a time — peak host memory
    is O(granule·D) no matter how large N is — and return a ``HostSource``
    over it.  Each granule is seeded by ``(seed, row_start)``, so the data
    is deterministic in ``(seed, granule)``.

    The labels use a covertype-LIKE nonlinear score (same family as
    ``data/synthetic.make_covertype_like``, all-continuous features, not
    the identical dataset): a smooth function of a fixed random projection
    plus low-order interactions — learnable well past chance by an RBF
    DSEKL fit, which the out-of-core example asserts.
    """
    os.makedirs(directory, exist_ok=True)
    x_path = os.path.join(directory, f"x_{n}x{d}.f32")
    y_path = os.path.join(directory, f"y_{n}.f32")
    x_mm = np.memmap(x_path, np.float32, mode="w+", shape=(n, d))
    y_mm = np.memmap(y_path, np.float32, mode="w+", shape=(n,))
    root = np.random.default_rng(seed)
    w = root.standard_normal(d).astype(np.float32)
    for start in range(0, n, granule):
        stop = min(start + granule, n)
        rng = np.random.default_rng((seed, start))
        xc = rng.standard_normal((stop - start, d)).astype(np.float32)
        score = (np.tanh(xc @ w / np.sqrt(d)) + 0.5 * np.sin(2.0 * xc[:, 0])
                 + 0.25 * xc[:, 1] * xc[:, 2] + 0.18)
        x_mm[start:stop] = xc
        y_mm[start:stop] = np.where(score >= 0.0, 1.0, -1.0)
    x_mm.flush()
    y_mm.flush()
    # The GLOBAL MANIFEST (multi-host resume, DESIGN.md §13): everything a
    # host needs to derive its own local row ranges without seeing any
    # other host's pages — sizes, file names, and the generation recipe.
    manifest = {"version": 1, "n": int(n), "d": int(d), "dtype": "float32",
                "x_file": os.path.basename(x_path),
                "y_file": os.path.basename(y_path),
                "seed": int(seed), "granule": int(granule)}
    tmp = os.path.join(directory, "manifest.json.tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, os.path.join(directory, "manifest.json"))
    return open_memmap_dataset(directory, n, d)


def open_memmap_dataset(directory: str, n: Optional[int] = None,
                        d: Optional[int] = None) -> HostSource:
    """Re-open a dataset written by ``make_memmap_dataset`` read-only.
    ``n``/``d`` may be omitted when the directory has a ``manifest.json``
    (datasets written since the manifest landed always do)."""
    if n is None or d is None:
        meta = read_manifest(directory)
        n, d = meta["n"], meta["d"]
    x = np.memmap(os.path.join(directory, f"x_{n}x{d}.f32"), np.float32,
                  mode="r", shape=(n, d))
    y = np.memmap(os.path.join(directory, f"y_{n}.f32"), np.float32,
                  mode="r", shape=(n,))
    return HostSource(x, y)


def read_manifest(directory: str) -> dict:
    """Load and validate ``manifest.json`` (written atomically by
    ``make_memmap_dataset``)."""
    path = os.path.join(directory, "manifest.json")
    with open(path) as f:
        meta = json.load(f)
    for k in ("n", "d", "x_file", "y_file"):
        if k not in meta:
            raise ValueError(f"manifest {path} is missing {k!r}")
    if meta.get("dtype", "float32") != "float32":
        raise ValueError(f"manifest dtype {meta['dtype']!r} unsupported")
    return meta


class ManifestSource(HostSource):
    """A dataset addressed through its GLOBAL MANIFEST, mapped per range.

    The object itself holds only ``manifest.json`` metadata — no file is
    mapped at construction.  ``local(offset, length)`` (and therefore
    ``split(n_shards)``) returns further ``ManifestSource`` views, and a
    view opens its backing ``np.memmap`` lazily, ON FIRST GATHER, with
    ``offset=`` into the global file covering ONLY its own row range.
    That is the multi-host contract (DESIGN.md §13): every host derives
    identical shard ranges from the shared manifest, then maps just its
    local rows — a 1 TB dataset resumes across 16 hosts with each host
    touching 1/16th of the file.

    The per-shard views a mesh fit uses (``source.split``) therefore map
    per-shard ranges even in single-host runs; the root view maps the
    whole file only if gathered through directly.
    """

    def __init__(self, directory: str, *, offset: int = 0,
                 length: Optional[int] = None, _meta: Optional[dict] = None):
        meta = read_manifest(directory) if _meta is None else _meta
        n, d = int(meta["n"]), int(meta["d"])
        length = n - offset if length is None else int(length)
        if offset < 0 or offset + length > n:
            raise ValueError(
                f"row range [{offset}, {offset + length}) outside 0..{n}")
        self._directory = directory
        self._meta = meta
        self._global_offset = int(offset)   # rows into the GLOBAL file
        self._n = int(length)               # HostSource.split reads this
        self._d = d
        self._offset = 0                    # view-local (post-mapping)
        self._mapped = False

    @property
    def d(self) -> int:
        return self._d

    @property
    def mapped(self) -> bool:
        """Whether this view has opened its backing memmap (tests assert
        shard views map lazily and the root stays unmapped)."""
        return self._mapped

    @property
    def global_offset(self) -> int:
        """First global row this view covers."""
        return self._global_offset

    def _ensure_mapped(self) -> None:
        if self._mapped:
            return
        meta, r0, rows = self._meta, self._global_offset, self._n
        x = np.memmap(os.path.join(self._directory, meta["x_file"]),
                      np.float32, mode="r", shape=(rows, self._d),
                      offset=4 * r0 * self._d)
        y = np.memmap(os.path.join(self._directory, meta["y_file"]),
                      np.float32, mode="r", shape=(rows,), offset=4 * r0)
        HostSource.__init__(self, x, y)
        self._mapped = True

    def gather(self, idx: Index,
               out_x: Optional[np.ndarray] = None,
               out_y: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        self._ensure_mapped()
        return super().gather(idx, out_x=out_x, out_y=out_y)

    def gather_x(self, idx: Index,
                 out: Optional[np.ndarray] = None) -> np.ndarray:
        self._ensure_mapped()
        return super().gather_x(idx, out=out)

    def local(self, offset: int, length: int) -> "ManifestSource":
        if offset < 0 or offset + length > self._n:
            raise ValueError(
                f"row range [{offset}, {offset + length}) outside the "
                f"view's [0, {self._n})")
        return ManifestSource(self._directory,
                              offset=self._global_offset + offset,
                              length=length, _meta=self._meta)
