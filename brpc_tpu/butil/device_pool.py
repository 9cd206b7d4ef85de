"""DeviceRecvPool: size-classed admission control over device (HBM)
receive memory — the tpu-native analog of the RDMA registered-memory
block pool (reference: rdma/block_pool.cpp:52 size classes 8KB/64KB/2MB,
:271-340 per-bucket freelists + region extend).

Honest delta from the reference, documented: PjRt owns physical buffer
placement and XLA arrays cannot be constructed into a caller-supplied
region from Python, so this pool governs *budget*, not placement — every
inbound device batch must reserve its (size-class-rounded) bytes before
the pull DMA is issued, and the reservation is released when the
application drops the arrays (tracked with weakref finalizers, the
moral equivalent of the rbuf block being returned to the pool when the
parsing IOBuf releases it, rdma_endpoint.h:145). Each connection
advertises a per-connection byte budget (window x largest block class,
capped by this pool) in its hello and the sender gates on bytes in
flight, so a single peer's in-flight bytes are bounded exactly like
RDMA's per-QP pre-posted rbufs; AGGREGATE pressure from many senders
lands on this pool's blocking reserve() — the same way rbuf posting
blocks when the shared block pool runs dry.
"""

from __future__ import annotations

import threading
from typing import List, Optional

# size classes mirror the reference's 8KB / 64KB / 2MB buckets
BLOCK_CLASSES = (8 << 10, 64 << 10, 2 << 20)


def round_to_class(nbytes: int) -> int:
    """Round a payload size up to its block-class footprint: payloads
    above the largest class take whole 2MB blocks (region extend)."""
    if nbytes <= 0:
        return BLOCK_CLASSES[0]
    for c in BLOCK_CLASSES:
        if nbytes <= c:
            return c
    big = BLOCK_CLASSES[-1]
    return ((nbytes + big - 1) // big) * big


class DeviceRecvPool:
    """Byte-budget admission for inbound device payloads.

    reserve() blocks (with timeout) when the budget is exhausted — the
    out-of-credit state a too-small window would otherwise hide.
    """

    def __init__(self, capacity_bytes: int = 256 << 20):
        self.capacity = capacity_bytes
        self._used = 0
        # reentrant: release() is a weakref finalizer, and the garbage
        # collector can fire it on ANY thread at a call boundary —
        # including inside reserve() on the thread that already holds
        # this lock (seen: the first smoke run from a clean checkout
        # hung in _class_index -> finalizer -> release)
        self._lock = threading.RLock()
        self._freed = threading.Condition(self._lock)
        # stats per class index (len+1 = oversized bucket)
        self.reserved_blocks: List[int] = [0] * (len(BLOCK_CLASSES) + 1)

    def _class_index(self, footprint: int) -> int:
        for i, c in enumerate(BLOCK_CLASSES):
            if footprint <= c:
                return i
        return len(BLOCK_CLASSES)

    @property
    def used(self) -> int:
        with self._lock:
            return self._used

    @property
    def available(self) -> int:
        with self._lock:
            return self.capacity - self._used

    def reserve(self, nbytes: int, timeout_s: Optional[float] = 10.0) -> int:
        """Reserve budget for one payload; returns the rounded footprint
        (pass it to release). Raises MemoryError on timeout — the
        connection-level error, not a silent stall.

        On pressure it runs gc.collect() OUTSIDE the lock (finalizers
        re-enter release()): reservations are freed when the app drops
        the pulled arrays, and arrays caught in reference cycles (a
        Controller holding its arrays and callbacks is one) would
        otherwise hold budget until an arbitrary future collection."""
        return self._reserve_footprint(round_to_class(nbytes), timeout_s)

    def reserve_group(self, footprint: int,
                      timeout_s: Optional[float] = 10.0) -> int:
        """ONE admission for a coalesced batch group: ``footprint`` is
        the pre-rounded sum of the group's per-array size classes (the
        sender and receiver compute it identically), so N tiny arrays
        pay one blocking reserve instead of N. Release with release()
        — or let GroupReservation do it when the last array dies."""
        return self._reserve_footprint(footprint, timeout_s)

    def _reserve_footprint(self, footprint: int,
                           timeout_s: Optional[float]) -> int:
        import time as _time

        if footprint > self.capacity:
            raise MemoryError(
                f"device payload footprint of {footprint}B exceeds "
                f"pool capacity {self.capacity}B")
        deadline = (None if timeout_s is None
                    else _time.monotonic() + timeout_s)
        gc_at = 0.0
        while True:
            with self._freed:
                if self.capacity - self._used >= footprint:
                    self._used += footprint
                    self.reserved_blocks[self._class_index(footprint)] += 1
                    return footprint
                if deadline is not None and _time.monotonic() >= deadline:
                    raise MemoryError(
                        f"device recv pool exhausted ({self._used}/"
                        f"{self.capacity}B used, need {footprint}B)")
                if _time.monotonic() >= gc_at:
                    collect = True
                else:
                    collect = False
                    self._freed.wait(0.05)
            if collect:
                import gc
                gc.collect()
                gc_at = _time.monotonic() + 1.0

    def try_reserve(self, nbytes: int) -> Optional[int]:
        """Non-blocking reserve; None when out of budget."""
        footprint = round_to_class(nbytes)
        with self._lock:
            if self.capacity - self._used < footprint:
                return None
            self._used += footprint
            self.reserved_blocks[self._class_index(footprint)] += 1
        return footprint

    def release(self, footprint: int) -> None:
        with self._freed:
            self._used -= footprint
            if self._used < 0:           # double-release guard
                self._used = 0
            self.reserved_blocks[self._class_index(footprint)] -= 1
            self._freed.notify_all()

    def attach_finalizer(self, obj, footprint: int) -> None:
        """Release the reservation when ``obj`` is garbage-collected —
        the app dropping the pulled arrays is the block returning to the
        pool."""
        import weakref
        try:
            weakref.finalize(obj, self.release, footprint)
        except TypeError:
            # object doesn't support weakrefs: release immediately rather
            # than leak budget forever
            self.release(footprint)

    def attach_group_finalizer(self, obj, group: "GroupReservation") -> None:
        """Coalesced-batch variant: every array of the group carries a
        finalizer into the SAME GroupReservation; the single group
        footprint releases when the last one dies."""
        import weakref
        try:
            weakref.finalize(obj, group.release_one)
        except TypeError:
            group.release_one()


class DevicePinnedStager:
    """Stage recv-side H2D copies through the native pinned (mlock'd)
    arena: the host bytes are copied into a pinned block, device_put
    reads from locked pages (no kernel bounce on a real DMA engine),
    and the block recycles when the device array is ready — a fiber
    parks on the PjRt future via DeviceEventPoller.watch instead of
    anyone blocking.

    Active when the native pinned arena can serve blocks (the native
    library loaded and ``mlock`` was granted). Otherwise ``land()`` is
    exactly ``jax.device_put`` and counts the call in
    ``fallback_count`` — same signature, and visible on /device and in
    ``chip_smoke.py``'s summary, which fails when the arena is missing.
    """

    def __init__(self):
        self._active: Optional[bool] = None
        self.staged_count = 0
        self.fallback_count = 0

    @property
    def active(self) -> bool:
        if self._active is None:
            from brpc_tpu import native
            self._active = native.alloc_pinned_block(1) is not None
        return self._active

    def land(self, host_arr, device=None, sharding=None):
        """device_put ``host_arr`` (a numpy array), staging through a
        pinned block when active. Returns the jax array; the pinned
        block is released when the device buffer signals ready."""
        import jax

        dst = sharding if sharding is not None else device
        if not self.active:
            self.fallback_count += 1
            return (jax.device_put(host_arr, dst) if dst is not None
                    else jax.device_put(host_arr))
        import numpy as np
        from brpc_tpu.butil.iobuf import pinned_staging_block
        staging = pinned_staging_block(host_arr.nbytes)
        if not staging.pinned:
            self.fallback_count += 1
            return (jax.device_put(host_arr, dst) if dst is not None
                    else jax.device_put(host_arr))
        flat = np.frombuffer(staging.view, dtype=np.uint8,
                             count=host_arr.nbytes)
        flat[:] = host_arr.reshape(-1).view(np.uint8)
        pinned_arr = flat.view(host_arr.dtype).reshape(host_arr.shape)
        arr = (jax.device_put(pinned_arr, dst) if dst is not None
               else jax.device_put(pinned_arr))
        self.staged_count += 1
        if next(iter(arr.devices())).platform == "cpu":
            # the CPU client does not copy an aligned host buffer, it
            # aliases it: the array IS the block, so the block goes
            # back only when the array dies (recycling it on readiness
            # let the next payload overwrite this one)
            import weakref
            weakref.finalize(arr, staging.release)
        else:
            # a real H2D copy: park on the PjRt future, the block goes
            # back to the pinned freelist once the copy has consumed it
            from brpc_tpu.fiber.device_poller import global_poller
            global_poller().watch(arr, staging.release)
        return arr


_stager: Optional[DevicePinnedStager] = None
_stager_lock = threading.Lock()


def global_pinned_stager() -> DevicePinnedStager:
    global _stager
    with _stager_lock:
        if _stager is None:
            _stager = DevicePinnedStager()
        return _stager


class GroupReservation:
    """Release-once holder shared by every array of a coalesced batch
    group: the pool footprint was reserved ONCE (reserve_group) and
    goes back when the last array is dropped."""

    __slots__ = ("_pool", "_footprint", "_count", "_lock")

    def __init__(self, pool: DeviceRecvPool, footprint: int, count: int):
        self._pool = pool
        self._footprint = footprint
        self._count = max(1, count)
        self._lock = threading.Lock()

    def release_one(self) -> None:
        with self._lock:
            self._count -= 1
            if self._count > 0:
                return
        self._pool.release(self._footprint)


def _postfork_reset_stager() -> None:
    # child gets a fresh stager (parent's watched futures/poller thread
    # are gone) and a fresh lock in case fork landed mid-acquire
    global _stager, _stager_lock
    _stager_lock = threading.Lock()
    _stager = None


from brpc_tpu.butil import postfork as _postfork  # noqa: E402

_postfork.register("butil.device_pool.stager", _postfork_reset_stager)
