"""Native (C++) core of brpc_tpu, loaded via ctypes.

The reference implements its data plane in C++ (butil/iobuf, bthread's
work-stealing queues, socket write queue, resource pools); this package is
our native counterpart: a shared library built from ``src/*.cc`` exposing
a C ABI.

What is wired where today:
  hash.cc        crc32c (HW-accelerated) + murmur3_x64_128 — consumed by
                 butil.hash and the c_murmurhash load balancer, with
                 bit-identical pure-Python fallbacks.
  framing.cc     TRPC frame scanner/probe — `trpc_scan` for batch frame
                 cutting of pipelined bursts.
  block_pool.cc  size-classed refcounted block pool (rdma/block_pool
  nbuf.cc        design) and the chained zero-copy buffer over it — the
                 native data-plane substrate (C++-side counterpart of
                 butil.iobuf; parity-tested against it).
  queues.cc      Chase-Lev WSQ + wait-free MPSC write queue — the native
                 scheduler/socket-queue primitives (Python's fiber
                 scheduler keeps its own implementation; these carry the
                 reference semantics incl. the UNCONNECTED-sentinel
                 write-queue contract, concurrency-tested).
  respool.cc     versioned id resource pool (socket versioned-ref trick).

Use ``lib()`` to get the loaded ctypes library or None (no compiler /
build failure — callers must fall back to pure Python).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
# BRPC_TPU_SANITIZE value the cache was latched under: a change after
# latching must raise, not silently serve the mismatched artifact
_latched_san: Optional[str] = None

c_u8p = ctypes.POINTER(ctypes.c_uint8)
c_u32 = ctypes.c_uint32
c_u64 = ctypes.c_uint64
c_size = ctypes.c_size_t


def _declare(lib: ctypes.CDLL) -> None:
    L = lib
    # hash
    L.bt_crc32c.restype = c_u32
    L.bt_crc32c.argtypes = [ctypes.c_char_p, c_size, c_u32]
    L.bt_murmur3_x64_128.restype = None
    L.bt_murmur3_x64_128.argtypes = [ctypes.c_char_p, c_size, c_u32,
                                     ctypes.POINTER(c_u64)]
    # block pool
    L.bt_block_alloc.restype = ctypes.c_void_p
    L.bt_block_alloc.argtypes = [ctypes.c_int]
    L.bt_block_alloc_pinned.restype = ctypes.c_void_p
    L.bt_block_alloc_pinned.argtypes = [ctypes.c_int]
    L.bt_block_is_pinned.restype = ctypes.c_int
    L.bt_block_is_pinned.argtypes = [ctypes.c_void_p]
    L.bt_block_ref.argtypes = [ctypes.c_void_p]
    L.bt_block_unref.argtypes = [ctypes.c_void_p]
    L.bt_block_refcount.restype = c_u32
    L.bt_block_refcount.argtypes = [ctypes.c_void_p]
    L.bt_block_size.restype = c_size
    L.bt_block_size.argtypes = [ctypes.c_int]
    L.bt_block_class_for.restype = ctypes.c_int
    L.bt_block_class_for.argtypes = [c_size]
    L.bt_block_pool_stats.restype = c_u64
    L.bt_block_pool_stats.argtypes = [ctypes.c_int, ctypes.c_int]
    # nbuf
    L.bt_nbuf_create.restype = ctypes.c_void_p
    L.bt_nbuf_destroy.argtypes = [ctypes.c_void_p]
    L.bt_nbuf_clear.argtypes = [ctypes.c_void_p]
    L.bt_nbuf_size.restype = c_size
    L.bt_nbuf_size.argtypes = [ctypes.c_void_p]
    L.bt_nbuf_block_count.restype = c_size
    L.bt_nbuf_block_count.argtypes = [ctypes.c_void_p]
    L.bt_nbuf_append.restype = c_size
    L.bt_nbuf_append.argtypes = [ctypes.c_void_p, ctypes.c_char_p, c_size]
    L.bt_nbuf_append_nbuf.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    L.bt_nbuf_cut.restype = ctypes.c_void_p
    L.bt_nbuf_cut.argtypes = [ctypes.c_void_p, c_size]
    L.bt_nbuf_pop_front.restype = c_size
    L.bt_nbuf_pop_front.argtypes = [ctypes.c_void_p, c_size]
    L.bt_nbuf_copy_to.restype = c_size
    L.bt_nbuf_copy_to.argtypes = [ctypes.c_void_p, ctypes.c_char_p, c_size, c_size]
    L.bt_nbuf_ref_at.restype = ctypes.c_int
    L.bt_nbuf_ref_at.argtypes = [ctypes.c_void_p, c_size,
                                 ctypes.POINTER(ctypes.c_void_p),
                                 ctypes.POINTER(c_size)]
    # framing
    L.bt_trpc_scan.restype = ctypes.c_long
    L.bt_trpc_scan.argtypes = [ctypes.c_char_p, c_size, ctypes.POINTER(c_u64),
                               c_size, ctypes.POINTER(c_size),
                               ctypes.POINTER(c_size)]
    L.bt_trpc_probe.restype = ctypes.c_int
    L.bt_trpc_probe.argtypes = [ctypes.c_char_p, c_size,
                                ctypes.POINTER(c_u32), ctypes.POINTER(c_u32)]
    # snappy
    L.bt_snappy_max_compressed.restype = c_size
    L.bt_snappy_max_compressed.argtypes = [c_size]
    L.bt_snappy_compress.restype = c_size
    L.bt_snappy_compress.argtypes = [ctypes.c_char_p, c_size,
                                     ctypes.c_char_p, c_size]
    L.bt_snappy_decompress.restype = ctypes.c_int64
    L.bt_snappy_decompress.argtypes = [ctypes.c_char_p, c_size,
                                       ctypes.c_char_p, c_size]
    # wsq
    L.bt_wsq_create.restype = ctypes.c_void_p
    L.bt_wsq_create.argtypes = [c_size]
    L.bt_wsq_destroy.argtypes = [ctypes.c_void_p]
    L.bt_wsq_size.restype = c_size
    L.bt_wsq_size.argtypes = [ctypes.c_void_p]
    L.bt_wsq_push.restype = ctypes.c_bool
    L.bt_wsq_push.argtypes = [ctypes.c_void_p, c_u64]
    L.bt_wsq_pop.restype = ctypes.c_bool
    L.bt_wsq_pop.argtypes = [ctypes.c_void_p, ctypes.POINTER(c_u64)]
    L.bt_wsq_steal.restype = ctypes.c_bool
    L.bt_wsq_steal.argtypes = [ctypes.c_void_p, ctypes.POINTER(c_u64)]
    # mpsc
    L.bt_mpsc_create.restype = ctypes.c_void_p
    L.bt_mpsc_destroy.argtypes = [ctypes.c_void_p]
    L.bt_mpsc_push.restype = ctypes.c_bool
    L.bt_mpsc_push.argtypes = [ctypes.c_void_p, c_u64]
    L.bt_mpsc_drain.restype = c_size
    L.bt_mpsc_drain.argtypes = [ctypes.c_void_p, ctypes.POINTER(c_u64), c_size]
    L.bt_mpsc_pushed.restype = c_u64
    L.bt_mpsc_pushed.argtypes = [ctypes.c_void_p]
    # respool
    L.bt_respool_create.restype = ctypes.c_void_p
    L.bt_respool_create.argtypes = [c_size]
    L.bt_respool_destroy.argtypes = [ctypes.c_void_p]
    L.bt_respool_acquire.restype = c_u64
    L.bt_respool_acquire.argtypes = [ctypes.c_void_p, c_u64]
    L.bt_respool_get.restype = ctypes.c_bool
    L.bt_respool_get.argtypes = [ctypes.c_void_p, c_u64, ctypes.POINTER(c_u64)]
    L.bt_respool_release.restype = ctypes.c_bool
    L.bt_respool_release.argtypes = [ctypes.c_void_p, c_u64]
    L.bt_respool_live.restype = c_u64
    L.bt_respool_live.argtypes = [ctypes.c_void_p]


def lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first call. None if unavailable
    (no compiler / build failure) — callers fall back to pure Python."""
    global _lib, _tried, _latched_san
    if _lib is not None or _tried:
        if os.environ.get("BRPC_TPU_SANITIZE", "") != _latched_san:
            from brpc_tpu.native.build import sanitize_changed_error
            raise sanitize_changed_error(_latched_san)
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        # validate BRPC_TPU_SANITIZE before latching _tried, before the
        # broad except, and before the BRPC_TPU_NO_NATIVE short-circuit:
        # a typo must raise — on EVERY call, not just the first — never
        # silently run the uninstrumented pure-Python fallback while
        # claiming sanitizer coverage
        from brpc_tpu.native.build import (build, check_no_native_conflict,
                                           sanitize_mode,
                                           sanitized_load_failure)
        san = sanitize_mode()
        if os.environ.get("BRPC_TPU_NO_NATIVE"):
            check_no_native_conflict(san)
            _latched_san = ""
            _tried = True
            return None
        try:
            path = build()
            L = ctypes.CDLL(path)
            _declare(L)
            _lib = L
        except Exception as e:
            _lib = None
            if san:
                # a VALID sanitize mode whose artifact fails to
                # build/load must be just as loud as a typo, and must
                # not latch _tried: proceeding on pure Python would
                # pass the run off as sanitized with zero coverage
                raise sanitized_load_failure(
                    san, "native library") from e
            import logging
            logging.getLogger("brpc_tpu.native").warning(
                "native library unavailable, running pure Python: %s",
                str(e)[-400:])
        _latched_san = os.environ.get("BRPC_TPU_SANITIZE", "")
        _tried = True
    return _lib


def available() -> bool:
    return lib() is not None


# ------------------------------------------------------ high-level wraps


def crc32c(data: bytes, init: int = 0) -> Optional[int]:
    L = lib()
    if L is None:
        return None
    return L.bt_crc32c(bytes(data), len(data), init)


def murmur3_x64_128(data: bytes, seed: int = 0) -> Optional[int]:
    L = lib()
    if L is None:
        return None
    out = (c_u64 * 2)()
    L.bt_murmur3_x64_128(bytes(data), len(data), seed, out)
    return (int(out[1]) << 64) | int(out[0])


def trpc_scan(data, max_frames: int = 256):
    """Scan a contiguous window (bytes or memoryview) for complete TRPC
    frames.

    Returns (frames, consumed, need) where frames is a list of
    (offset, total_len), or None when the native lib is unavailable.
    Raises ValueError on bad magic.
    """
    L = lib()
    if L is None:
        return None
    size = len(data)
    if isinstance(data, memoryview):
        try:
            # zero-copy view into the portal's read block
            data = (ctypes.c_char * size).from_buffer(data)
        except TypeError:          # read-only buffer
            data = bytes(data)
    out = (c_u64 * (2 * max_frames))()
    consumed = c_size()
    need = c_size()
    n = L.bt_trpc_scan(data, size, out, max_frames,
                       ctypes.byref(consumed), ctypes.byref(need))
    if n < 0:
        raise ValueError("not a TRPC stream")
    frames = [(int(out[2 * i]), int(out[2 * i + 1])) for i in range(n)]
    return frames, int(consumed.value), int(need.value)


def snappy_compress(data: bytes) -> Optional[bytes]:
    L = lib()
    if L is None:
        return None
    data = bytes(data)
    cap = int(L.bt_snappy_max_compressed(len(data)))
    dst = ctypes.create_string_buffer(cap)
    n = int(L.bt_snappy_compress(data, len(data), dst, cap))
    if n == 0 and data:
        return None
    return dst.raw[:n]


def _unref_block(ptr: int) -> None:
    L = lib()
    if L is not None:
        L.bt_block_unref(ctypes.c_void_p(ptr))


class PinnedBlock:
    """One mlock'd block from the native pinned arena, exposed as a
    writable memoryview (``view``). The block returns to the pinned
    freelist on release() — or, safety net, when this wrapper dies
    (weakref.finalize fires its callback at most once, so the pair
    cannot double-unref)."""

    __slots__ = ("ptr", "size", "view", "_buf", "_fin", "__weakref__")

    def __init__(self, ptr: int, size: int):
        self.ptr = ptr
        self.size = size
        self._buf = (ctypes.c_char * size).from_address(ptr)
        self.view = memoryview(self._buf).cast("B")
        import weakref
        self._fin = weakref.finalize(self, _unref_block, ptr)

    def release(self) -> None:
        """Return the block to the pinned freelist. The view must not
        be written after this — the block may already be re-owned."""
        self._fin()


def alloc_pinned_block(nbytes: int) -> Optional[PinnedBlock]:
    """A pinned (mlock'd, DMA-capable) staging block of at least
    ``nbytes``; None when the native lib is absent, the size exceeds
    the largest class, the pinned cap is reached, or mlock is refused
    (RLIMIT_MEMLOCK) — callers fall back to pageable memory."""
    L = lib()
    if L is None:
        return None
    cls = int(L.bt_block_class_for(nbytes))
    if cls < 0:
        return None
    ptr = L.bt_block_alloc_pinned(cls)
    if not ptr:
        return None
    return PinnedBlock(int(ptr), int(L.bt_block_size(cls)))


def pinned_pool_stats() -> Optional[dict]:
    """Pinned-arena counters for /vars and the /device page."""
    L = lib()
    if L is None:
        return None
    per_class = []
    for cls in range(3):
        per_class.append({
            "total": int(L.bt_block_pool_stats(cls, 3)),
            "live": int(L.bt_block_pool_stats(cls, 4)),
            "free": int(L.bt_block_pool_stats(cls, 5)),
        })
    return {"classes": per_class,
            "pinned_bytes": int(L.bt_block_pool_stats(0, 6))}


def snappy_decompress(data: bytes) -> Optional[bytes]:
    """None when the native lib is absent; raises ValueError on corrupt
    input (mirrors snappy_codec.SnappyError)."""
    L = lib()
    if L is None:
        return None
    data = bytes(data)
    want = int(L.bt_snappy_decompress(data, len(data), None, 0))
    # the preamble is attacker-controlled (up to 2^35-1): cap it against
    # the format's maximum expansion (a copy2 turns 3 input bytes into
    # 64 output bytes, <22x) BEFORE allocating, or a 5-byte bomb
    # requests a 32GB buffer
    if want < 0 or want > 32 + 22 * len(data):
        raise ValueError("corrupt snappy stream")
    dst = ctypes.create_string_buffer(max(want, 1))
    n = int(L.bt_snappy_decompress(data, len(data), dst, want))
    if n < 0:
        raise ValueError("corrupt snappy stream")
    return dst.raw[:n]
