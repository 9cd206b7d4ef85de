"""IOBuf: zero-copy chained buffer whose blocks may live on host or device.

TPU-native redesign of the reference's IOBuf (butil/iobuf.h:64, iobuf.cpp).
The reference chains refcounted 8KB heap blocks and cuts/appends without
memcpy; ours does the same for host bytes, and additionally supports
*device blocks* — jax.Array payload segments that stay in HBM. Cutting or
appending a device block is metadata-only (offset/length on the BlockRef);
materialization (a device slice or D2H copy) happens only when a consumer
explicitly asks for bytes, mirroring how the reference's RDMA path points
scatter-gather entries into registered blocks instead of copying
(rdma/rdma_endpoint.h:82).

Block recycling replaces the reference's TLS block cache (iobuf.cpp:318-430):
host block buffers return to a per-thread freelist when their Block becomes
unreachable (GC-driven via weakref.finalize — no manual refcounting races).
"""

from __future__ import annotations

import threading
import weakref
from typing import Callable, Iterator, List, Optional, Tuple

DEFAULT_BLOCK_SIZE = 8192  # same default payload-block size as the reference
_MAX_CACHED_BLOCKS_PER_THREAD = 64
# bytes payloads at/above this size are wrapped zero-copy by append()
# instead of being copied into 8KB blocks
_APPEND_ZEROCOPY_MIN = 16384


# large read blocks (adaptive drain hint) are recycled too, with a
# byte-budgeted per-thread cache (16MB default); sized so a full
# window of 1MB-payload messages in flight stays inside the cache,
# because a cache miss is a fresh large allocation whose page-fault
# cost dominates the recv syscall itself (see malloc_tune.py for the
# measurement). Block size tunable: bigger blocks mean fewer recv
# syscalls per bulk transfer but coarser recycling granularity.
import os as _os


def _big_block_size_from_env() -> int:
    try:
        v = int(_os.environ.get("BRPC_TPU_BIG_BLOCK", 262144))
    except ValueError:
        return 262144
    # clamp instead of crash/disable: below 64KB the "big" tier stops
    # paying for itself; above 8MB recycling granularity is useless
    return min(max(v, 65536), 8 << 20)


_BIG_BLOCK_SIZE = _big_block_size_from_env()
_MAX_CACHED_BIG_BLOCKS = max(1, (16 << 20) // _BIG_BLOCK_SIZE)

# debug poisoning: recycled buffers are filled with _POISON_BYTE and
# sentinel windows are verified intact at reuse — a consumer that held
# a memoryview/BlockRef past the recycle point reads 0xDD garbage
# (loud) instead of another call's payload (silent corruption), and a
# stale WRITER trips the sentinel check at the next acquire
_POISON_BYTE = 0xDD
_POISON_SENTINEL = 32


class BlockPool:
    """PROCESS-GLOBAL size-classed block freelists (list append/pop are
    GIL-atomic). The reference caches per-thread to dodge a lock on
    multicore (iobuf.cpp:318-430); under the GIL a global pool costs
    the same as a TLS lookup and — decisively — keeps recycling working
    when blocks are freed on a different thread than the one reading
    (server reads on the dispatcher, frees after the response on a
    worker: per-thread caches never hit there, and every miss is a
    fresh ZEROED bytearray whose page-fault cost dominates the recv
    syscall itself; see malloc_tune.py for the measurement).

    Every recycle bumps the pool generation and tags the buffer with
    it: a Block records the generation it was born under, so debug
    tooling (and the use-after-recycle tests) can prove a view predates
    the buffer's latest recycle. ``BRPC_TPU_IOBUF_POOL=0`` disables
    pooling entirely (every miss allocates, every recycle drops);
    ``BRPC_TPU_IOBUF_DEBUG=1`` turns on poisoning + exact outstanding
    accounting (a lock per acquire/recycle — debug only)."""

    __slots__ = ("enabled", "debug", "classes", "caps",
                 "hits", "misses", "recycled", "dropped",
                 "generation", "_debug_lock", "outstanding")

    def __init__(self, enabled: bool, debug: bool):
        self.enabled = enabled
        self.debug = debug
        # each freelist entry is ONE (buffer, generation) tuple so the
        # pop and the append each stay a single GIL-atomic list op —
        # parallel buffer/gen lists would let concurrent threads pair
        # a buffer with another recycle's tag (or IndexError between
        # the two pops and silently drop a cached buffer)
        self.classes = {DEFAULT_BLOCK_SIZE: [], _BIG_BLOCK_SIZE: []}
        self.caps = {DEFAULT_BLOCK_SIZE: _MAX_CACHED_BLOCKS_PER_THREAD,
                     _BIG_BLOCK_SIZE: _MAX_CACHED_BIG_BLOCKS}
        # approximate under races (stats, not invariants): exact
        # accounting costs a lock, paid only in debug mode
        self.hits = 0
        self.misses = 0
        self.recycled = 0
        self.dropped = 0
        self.generation = 0
        self._debug_lock = threading.Lock()
        self.outstanding = 0          # debug-exact pooled buffers out

    # ------------------------------------------------------------ acquire
    def acquire(self, capacity: int):
        """(buffer, generation) for a pooled size class — reused when
        cached, freshly allocated otherwise. None for foreign sizes."""
        lst = self.classes.get(capacity)
        if lst is None:
            return None
        if self.debug:
            return self._acquire_debug(capacity, lst)
        # pop inside try: the truthiness check and the pop are two
        # bytecodes — another thread can empty a one-element list
        # between them
        try:
            buf, gen = lst.pop()
            self.hits += 1
            return buf, gen
        except IndexError:
            self.misses += 1
            return bytearray(capacity), self.generation

    def _acquire_debug(self, capacity: int, lst):
        with self._debug_lock:
            self.outstanding += 1
            if lst:
                buf, gen = lst.pop()
                self.hits += 1
                sent = bytes((_POISON_BYTE,)) * _POISON_SENTINEL
                if (bytes(buf[:_POISON_SENTINEL]) != sent
                        or bytes(buf[-_POISON_SENTINEL:]) != sent):
                    raise RuntimeError(
                        "iobuf pool: poisoned block was written after "
                        "its recycle point (use-after-recycle)")
                return buf, gen
            self.misses += 1
            return bytearray(capacity), self.generation

    # ------------------------------------------------------------ recycle
    def recycle(self, buf: bytearray) -> None:
        """Return a buffer to its size class (called by the Block
        finalizer once no BlockRef/memoryview can reach it — THE
        recycle point every held view must not outlive)."""
        if not self.enabled:
            return
        cap = len(buf)
        lst = self.classes.get(cap)
        if lst is None:
            return
        if self.debug:
            with self._debug_lock:
                self.outstanding -= 1
                self.generation += 1
                if len(lst) >= self.caps[cap]:
                    self.dropped += 1
                    return
                buf[:] = bytes((_POISON_BYTE,)) * cap
                lst.append((buf, self.generation))
                self.recycled += 1
            return
        if len(lst) >= self.caps[cap]:
            self.dropped += 1
            return
        self.generation += 1
        lst.append((buf, self.generation))
        self.recycled += 1

    def clear(self) -> None:
        """Drop every cached buffer (tests / memory pressure hooks)."""
        for cap in self.classes:
            self.classes[cap].clear()

    def postfork_reset(self) -> None:
        """Fork hygiene (butil.postfork): reset IN PLACE — other
        modules hold `from iobuf import pool` references, so rebinding
        the module global would fork the state in two. Cached buffers
        are dropped (they are shared COW pages; writing into one from
        the child forces a copy anyway, and debug-mode generation tags
        would collide with the parent's), stats restart, and the debug
        lock — possibly held by a parent thread mid-recycle at fork
        time — is replaced. Outstanding blocks from the parent's
        in-flight calls are forgotten, not leaked-tracked."""
        for lst in self.classes.values():
            lst.clear()
        self.hits = self.misses = self.recycled = self.dropped = 0
        self.generation = 0
        self._debug_lock = threading.Lock()
        self.outstanding = 0

    # -------------------------------------------------------------- stats
    def hit_ratio(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def cached_bytes(self) -> int:
        return sum(cap * len(lst) for cap, lst in self.classes.items())

    def snapshot(self) -> dict:
        return {
            "enabled": self.enabled,
            "hits": self.hits,
            "misses": self.misses,
            "hit_ratio": round(self.hit_ratio(), 4),
            "recycled": self.recycled,
            "dropped": self.dropped,
            "cached_bytes": self.cached_bytes(),
            "cached_blocks": {str(c): len(l)
                              for c, l in self.classes.items()},
            "generation": self.generation,
        }


pool = BlockPool(
    enabled=_os.environ.get("BRPC_TPU_IOBUF_POOL", "1") != "0",
    debug=_os.environ.get("BRPC_TPU_IOBUF_DEBUG", "") not in ("", "0"))

from brpc_tpu.butil import postfork as _postfork  # noqa: E402
#   (registration ships with the pool it resets)

_postfork.register("butil.iobuf", pool.postfork_reset)

from brpc_tpu.butil import resource_census as _census  # noqa: E402
#   (census registration ships with the pool it measures)

_census.register("iobuf_pool", lambda: {
    "bytes": pool.cached_bytes(),
    "count": sum(len(l) for l in pool.classes.values()),
    "hit_ratio": round(pool.hit_ratio(), 4),
    "outstanding": pool.outstanding,
})


def _recycle_buffer(buf: bytearray) -> None:
    pool.recycle(buf)


class PinnedStaging:
    """A host staging buffer for H2D transfers, backed by an mlock'd
    block from the native pinned arena when one is available and by a
    plain bytearray otherwise. ``view`` is writable; ``release()``
    returns the pinned block to its freelist (no-op for the fallback)
    and is safe to call from a poller callback after the device copy
    lands."""

    __slots__ = ("view", "pinned", "_block", "__weakref__")

    def __init__(self, view: memoryview, block=None):
        self.view = view
        self.pinned = block is not None
        self._block = block

    def release(self) -> None:
        blk, self._block = self._block, None
        if blk is not None:
            blk.release()


def pinned_staging_block(nbytes: int) -> PinnedStaging:
    """Acquire staging memory for an H2D copy of ``nbytes``: an
    mlock'd pinned block when the native arena can serve it (the DMA
    engine reads straight from locked pages, the RDMA-registered-rbuf
    analog), else pageable memory — same interface either way, so
    callers never branch on availability."""
    from brpc_tpu import native
    blk = native.alloc_pinned_block(nbytes)
    if blk is not None:
        return PinnedStaging(blk.view[:nbytes], blk)
    return PinnedStaging(memoryview(bytearray(nbytes)))


class Block:
    """A contiguous host buffer; append-only region shared by BlockRefs.

    ``size`` is the high-water mark of valid bytes; an IOBuf may keep
    appending into the spare capacity as long as it owns the tail ref.
    """

    __slots__ = ("data", "size", "capacity", "user_meta", "gen",
                 "__weakref__")

    def __init__(self, capacity: int = DEFAULT_BLOCK_SIZE, _recycle: bool = True):
        got = pool.acquire(capacity) if (_recycle and pool.enabled) else None
        if got is not None:
            self.data, self.gen = got
            weakref.finalize(self, _recycle_buffer, self.data)
        else:
            self.data = bytearray(capacity)
            self.gen = 0
        self.size = 0
        self.capacity = len(self.data)
        self.user_meta = None

    def left_space(self) -> int:
        return self.capacity - self.size

    @classmethod
    def from_user_data(cls, data, deleter: Optional[Callable] = None, meta=None) -> "Block":
        """Wrap external bytes-like data zero-copy (iobuf.h:263
        append_user_data_with_meta). ``meta`` carries transport hints the way
        the reference carries an RDMA lkey."""
        blk = cls.__new__(cls)
        mv = memoryview(data)
        blk.data = mv
        blk.size = len(mv)
        blk.capacity = len(mv)
        blk.user_meta = meta
        blk.gen = 0
        if deleter is not None:
            weakref.finalize(blk, deleter, data)
        return blk


class DeviceBlock:
    """A payload segment resident on an accelerator: wraps a 1-D uint8
    jax.Array (or any object exposing __len__ + device semantics).

    Slicing is metadata-only; ``materialize`` produces host bytes (D2H) and
    ``device_slice`` produces an on-device slice, both lazily.
    """

    __slots__ = ("array", "size", "user_meta", "__weakref__")

    def __init__(self, array, meta=None):
        self.array = array
        self.size = int(array.shape[0]) if hasattr(array, "shape") else len(array)
        self.user_meta = meta

    @property
    def capacity(self) -> int:
        return self.size

    def left_space(self) -> int:
        return 0


class BlockRef:
    """A view (offset, length) into a Block or DeviceBlock."""

    __slots__ = ("block", "offset", "length")

    def __init__(self, block, offset: int, length: int):
        self.block = block
        self.offset = offset
        self.length = length

    @property
    def is_device(self) -> bool:
        return isinstance(self.block, DeviceBlock)

    def memoryview(self) -> memoryview:
        if self.is_device:
            raise TypeError("device BlockRef has no host memoryview; materialize first")
        return memoryview(self.block.data)[self.offset:self.offset + self.length]

    def to_bytes(self) -> bytes:
        if self.is_device:
            arr = self.device_array()
            import numpy as np
            return np.asarray(arr).tobytes()
        blk = self.block
        d = blk.data
        if self.offset == 0 and self.length == blk.size \
                and type(d) is memoryview and type(d.obj) is bytes \
                and d.nbytes == len(d.obj) and d.contiguous:
            # zero-copy: the ref covers a whole wrapped immutable
            # payload (append_user_data / the zero-copy append path) —
            # hand the original bytes back instead of copying it.
            # The nbytes+contiguous guard rejects views that are a
            # slice/recast of a larger object (mv.obj is the BASE
            # object, not the slice).
            return d.obj
        return bytes(self.memoryview())

    def device_array(self):
        """On-device slice covering exactly this ref (lazy, no D2H)."""
        arr = self.block.array
        if self.offset == 0 and self.length == self.block.size:
            return arr
        return arr[self.offset:self.offset + self.length]


class IOBuf:
    """Chained buffer of BlockRefs. append/cut are O(1) per touched ref and
    never copy payload bytes (iobuf.h:64)."""

    __slots__ = ("_refs",)

    def __init__(self):
        self._refs: List[BlockRef] = []

    # ------------------------------------------------------------ inspect
    @property
    def size(self) -> int:
        return sum(r.length for r in self._refs)

    def __len__(self) -> int:
        return self.size

    def __bool__(self) -> bool:
        return bool(self._refs)

    @property
    def backing_block_count(self) -> int:
        return len(self._refs)

    def empty(self) -> bool:
        return not self._refs

    def has_device_blocks(self) -> bool:
        return any(r.is_device for r in self._refs)

    def refs(self) -> Tuple[BlockRef, ...]:
        return tuple(self._refs)

    # ------------------------------------------------------------- append
    def append(self, data) -> None:
        """Append host bytes. Small payloads copy into pooled blocks (the
        only copy in the system — at the producer edge, like the
        reference); large immutable ``bytes`` are wrapped zero-copy (the
        append_user_data fast path — a 1MB payload must not be chopped
        into 128 block copies)."""
        if isinstance(data, IOBuf):
            self.append_buf(data)
            return
        if isinstance(data, bytes) and len(data) >= _APPEND_ZEROCOPY_MIN:
            # graftlint: disable=guarded-by -- IOBuf is single-owner
            # (bRPC's buffer contract): concurrent mutation is a caller
            # bug; ownership moves whole through locked queues, so the
            # next owner reads behind the publishing lock's barrier.
            self._refs.append(
                BlockRef(Block.from_user_data(data), 0, len(data)))
            return
        mv = memoryview(data)
        if mv.nbytes == 0:
            return
        pos = 0
        n = mv.nbytes
        # extend into tail block's spare capacity if we own its high-water mark
        while pos < n:
            tail = self._writable_tail()
            if tail is None:
                blk = Block(max(DEFAULT_BLOCK_SIZE, 0))
                take = min(n - pos, blk.left_space())
                blk.data[0:take] = mv[pos:pos + take]
                blk.size = take
                self._refs.append(BlockRef(blk, 0, take))
            else:
                ref, blk = tail
                take = min(n - pos, blk.left_space())
                blk.data[blk.size:blk.size + take] = mv[pos:pos + take]
                blk.size += take
                ref.length += take
            pos += take

    def _writable_tail(self) -> Optional[Tuple[BlockRef, Block]]:
        if not self._refs:
            return None
        ref = self._refs[-1]
        blk = ref.block
        if ref.is_device or not isinstance(blk.data, bytearray):
            return None
        # we may extend only if our ref ends exactly at the block's used size
        if ref.offset + ref.length != blk.size or blk.left_space() == 0:
            return None
        return ref, blk

    def append_buf(self, other: "IOBuf") -> None:
        """O(1)-per-ref zero-copy append of another IOBuf's refs."""
        for r in other._refs:
            self._refs.append(BlockRef(r.block, r.offset, r.length))

    def append_user_data(self, data, deleter: Optional[Callable] = None, meta=None) -> None:
        blk = Block.from_user_data(data, deleter, meta)
        if blk.size:
            self._refs.append(BlockRef(blk, 0, blk.size))

    def append_device_array(self, array, meta=None) -> None:
        """Append an HBM-resident payload segment zero-copy."""
        blk = DeviceBlock(array, meta)
        if blk.size:
            self._refs.append(BlockRef(blk, 0, blk.size))

    # ---------------------------------------------------------------- cut
    def cut(self, n: int) -> "IOBuf":
        """Move the first n bytes into a new IOBuf. Metadata-only: at most
        one boundary ref is split (iobuf.h cutn)."""
        out = IOBuf()
        self.cut_into(out, n)
        return out

    def cut_into(self, out: "IOBuf", n: int) -> int:
        """Move up to n bytes into ``out``; returns bytes moved."""
        moved = 0
        while n > 0 and self._refs:
            r = self._refs[0]
            if r.length <= n:
                out._refs.append(r)
                self._refs.pop(0)
                n -= r.length
                moved += r.length
            else:
                out._refs.append(BlockRef(r.block, r.offset, n))
                r.offset += n
                r.length -= n
                moved += n
                n = 0
        return moved

    def cut_all(self) -> "IOBuf":
        out = IOBuf()
        out._refs = self._refs
        self._refs = []
        return out

    def pop_front(self, n: int) -> int:
        """Drop the first n bytes (metadata-only). Returns bytes dropped."""
        dropped = 0
        while n > 0 and self._refs:
            r = self._refs[0]
            if r.length <= n:
                self._refs.pop(0)
                n -= r.length
                dropped += r.length
            else:
                r.offset += n
                r.length -= n
                dropped += n
                n = 0
        return dropped

    def clear(self) -> None:
        self._refs.clear()

    # ------------------------------------------------------------ consume
    def to_bytes(self) -> bytes:
        if len(self._refs) == 1:
            return self._refs[0].to_bytes()
        return b"".join(r.to_bytes() for r in self._refs)

    def first_host_view(self) -> Optional[memoryview]:
        """Memoryview over the first (host) ref — the contiguous head
        window batch parsers scan without copying. None when empty or
        the head is a device ref."""
        if self._refs and not self._refs[0].is_device:
            return self._refs[0].memoryview()
        return None

    def peek_bytes(self, n: int) -> bytes:
        """First n bytes without consuming. Single-block fast path: no
        chunk list, no join — and zero-copy outright when the head ref
        is exactly a wrapped immutable payload of n bytes."""
        refs = self._refs
        if refs and not refs[0].is_device and refs[0].length >= n:
            r = refs[0]
            if r.length == n:
                return r.to_bytes()          # zero-copy when wrapped
            return bytes(r.memoryview()[:n])
        chunks = []
        need = n
        for r in self._refs:
            if need <= 0:
                break
            take = min(need, r.length)
            if r.is_device:
                chunks.append(r.to_bytes()[:take])
            else:
                chunks.append(bytes(r.memoryview()[:take]))
            need -= take
        return b"".join(chunks)

    def iter_memoryviews(self) -> Iterator[memoryview]:
        """Host-side scatter list (the writev iovec list, iobuf.h:177
        prepare_iovecs). Device refs are materialized."""
        for r in self._refs:
            if r.is_device:
                yield memoryview(r.to_bytes())
            else:
                yield r.memoryview()

    def device_arrays(self) -> List:
        """All device segments in order (for device-native transports)."""
        return [r.device_array() for r in self._refs if r.is_device]

    # ----------------------------------------------------------------- io
    def cut_into_gather_writer(self, writev: Callable, max_iov: int = 32) -> int:
        """Feed the whole ref chain to a gather-write callable (sendmsg)
        — one syscall per iovec batch instead of one per ref
        (iobuf.h:177 prepare_iovecs). Consumes what was written; returns
        total. BlockingIOError stops with the remainder intact."""
        total = 0
        while self._refs:
            views = []
            offered = 0
            for r in self._refs[:max_iov]:
                mv = memoryview(r.to_bytes()) if r.is_device else r.memoryview()
                views.append(mv)
                offered += len(mv)
            try:
                nw = writev(views)
            except BlockingIOError:
                break
            if nw is None or nw <= 0:
                break
            self.pop_front(nw)
            total += nw
            if nw < offered:
                break
        return total

    def cut_into_writer(self, write: Callable[[memoryview], int], max_bytes: Optional[int] = None) -> int:
        """Feed refs to a write callable (socket.send-like; may write short).
        Consumes what was written; returns total written. The analogue of
        cut_into_file_descriptor (iobuf.h:163)."""
        total = 0
        budget = max_bytes if max_bytes is not None else float("inf")
        while self._refs and budget > 0:
            r = self._refs[0]
            mv = memoryview(r.to_bytes()) if r.is_device else r.memoryview()
            if budget < len(mv):
                mv = mv[:int(budget)]
            try:
                nw = write(mv)
            except BlockingIOError:
                break
            if nw is None or nw <= 0:
                break
            self.pop_front(nw)
            total += nw
            budget -= nw
            if nw < len(mv):
                break
        return total


class IOPortal(IOBuf):
    """IOBuf that can suck bytes from a non-blocking reader (iobuf.h:457)."""

    def append_from_reader(self, recv_into: Callable[[memoryview], int], hint: int = 65536) -> int:
        """Read once into spare tail capacity (allocating blocks as needed).
        Returns bytes read; 0 means EOF; raises BlockingIOError if the
        reader would block.

        ``hint`` sizes freshly-allocated read blocks: bulk drains want
        few large recv syscalls (the reference gets the same effect by
        readv'ing into an iovec of many 8KB blocks,
        iobuf.h:469 append_from_file_descriptor)."""
        tail = self._writable_tail()
        if tail is not None:
            ref, blk = tail
            # a nearly-full tail would cap this read at a few bytes;
            # prefer a fresh block over a tiny syscall
            if blk.left_space() >= 4096:
                mv = memoryview(blk.data)[blk.size:blk.capacity]
                nr = recv_into(mv)
                if nr and nr > 0:
                    blk.size += nr
                    ref.length += nr
                    return nr
                return 0
        blk = Block(max(hint, DEFAULT_BLOCK_SIZE))
        mv = memoryview(blk.data)[0:blk.capacity]
        nr = recv_into(mv)
        if nr and nr > 0:
            blk.size = nr
            # graftlint: disable=guarded-by -- a Socket's input portal
            # is single-owner like every IOBuf: only the context that
            # holds the input (Socket._nevent's 0->1 winner, or the
            # plucker that claimed it) reads into it or cuts from it.
            self._refs.append(BlockRef(blk, 0, nr))
            return nr
        return 0

    def append_from_reader_v(self, recv_into_v: Callable, hint: int = 65536,
                             nbufs: int = 4) -> int:
        """Scatter-read into several fresh blocks in ONE syscall
        (iobuf.h:469's readv discipline) — bulk bursts land without a
        syscall per block. Returns bytes read; 0 = EOF; raises
        BlockingIOError when the reader would block. Unused blocks go
        straight back to the freelist via their finalizer."""
        blocks = []
        views = []
        tail = self._writable_tail()
        if tail is not None and tail[1].left_space() >= 4096:
            ref, blk = tail
            views.append(memoryview(blk.data)[blk.size:blk.capacity])
            blocks.append((ref, blk))
        for _ in range(nbufs):
            blk = Block(max(hint, DEFAULT_BLOCK_SIZE))
            views.append(memoryview(blk.data)[0:blk.capacity])
            blocks.append((None, blk))
        nr = recv_into_v(views)
        if not nr or nr <= 0:
            return 0
        left = nr
        for (ref, blk), v in zip(blocks, views):
            take = min(left, len(v))
            if take <= 0:
                break
            if ref is not None:              # tail extension
                blk.size += take
                ref.length += take
            else:
                blk.size = take
                self._refs.append(BlockRef(blk, 0, take))
            left -= take
        return nr
