"""ici:// — the real device-fabric data plane (the RDMA slot).

Where the reference grafts ibverbs onto Socket — TCP handshake exchanging
GID/QPN then RC queue-pair bring-up (rdma/rdma_endpoint.h:64 state
machine, :95-109), zero-copy sends from registered blocks
(CutFromIOBufList :82), sliding-window flow control with piggybacked
ACKs (:138,:235-241), and a registered-memory block pool
(rdma/block_pool.cpp:52) — this transport grafts the PjRt fabric:

* **Bootstrap/control stream**: TCP (the reference's handshake +
  FALLBACK_TCP lane). Carries 13-byte-framed control/app frames.
* **Hello handshake** (the GID/QPN exchange): each side sends its
  process uuid, PjRt transfer-server address, advertised recv window,
  and recv-device ordinal before anything else.
* **Device lane**: sender registers the batch with its process-global
  PjRt transfer server (``jax.experimental.transfer``) and sends a
  small descriptor frame; the RECEIVER pulls the arrays directly onto
  its own device via PjRt DMA — receiver-driven placement, the moral
  twin of RDMA's pre-posted recv buffers. No numpy round-trip is on the
  data path. Same-process peers short-circuit through an in-process
  registry + ``jax.device_put`` (a device-to-device copy, ICI on real
  multi-chip hardware).
* **Flow control**: at most ``peer_window`` un-ACKed device batches in
  flight per connection; every frame header piggybacks the cumulative
  consumed count, and a bare ACK frame is pushed once half the window
  is unacknowledged with no reverse traffic (RdmaEndpoint::SendAck +
  imm-carried ack counts). A window-stalled sender parks exactly like a
  TCP-blocked one: BlockingIOError -> KeepWrite fiber waits for the
  writable event that ACK arrival fires.
* **Recv budget**: inbound batches reserve size-classed bytes from a
  DeviceRecvPool (butil/device_pool.py — block_pool.cpp's size classes
  as HBM admission control) before the pull is issued; the reservation
  releases when the app drops the arrays.

Frame format (all big-endian):
    type:u8  ack:u64  len:u32  payload[len]
    type 0 app bytes
    type 1 pull descriptor: uuid:u64, count:u16, then per array
           {dtype_len:u8, dtype, rank:u8, dims:i64*rank, nbytes:u64}
    type 2 hello (json)
    type 3 bare ack (payload empty, or a u32 adaptive window grant —
           header ack is the message, the grant is the receiver
           resizing the sender's pipeline from its admission headroom)
    type 4 staged batch (numpy fallback when either side lacks a
           transfer server — the old tpud lane, clearly second-class)
    type 5 coalesced group: mode:u8 (0 descriptor / 1 staged),
           count:u16, then mode 0: uid:u64 + per sub-batch
           {count:u16, array specs as type 1}; mode 1: per sub-batch
           {len:u32, staged blob}. One registration / one receiver
           reservation for N small batches; window + ack accounting
           stays per sub-batch.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import struct
import threading
import time
import uuid as uuidlib
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

from brpc_tpu.butil.device_pool import (BLOCK_CLASSES, DeviceRecvPool,
                                        round_to_class)

logger = logging.getLogger("brpc_tpu.ici")
from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.butil.jax_runtime import local_device
from brpc_tpu.transport import device_stats as _dev_stats
from brpc_tpu.transport import syscall_stats as _syscall_stats
from brpc_tpu.transport.base import Conn, Listener, Transport
from brpc_tpu.transport.event_dispatcher import (global_dispatcher,
                                                 peek_dispatcher)
from brpc_tpu.transport.tcp import TcpConn, TcpTransport
from brpc_tpu.transport.tpud import (_decode_device_batch,
                                     _encode_device_batch, _np_dtype)

F_BYTES = 0
F_DESCRIPTOR = 1
F_HELLO = 2
F_ACK = 3
F_STAGED = 4
F_COALESCED = 5
_HDR = struct.Struct(">BQI")
_MAX_FRAME = 256 << 20
_MAX_OUT = 64 << 20
DEFAULT_WINDOW = 32
# cap on framed-but-unwritten bytes per flush pass: one gather pass
# frames every sendable queue item up to this, then pays ONE TCP write
_FLUSH_CHUNK = 1 << 20
# the pump's read size; a conn keeps one buffer of it (pages no frame
# ever reached are never touched)
_PUMP_READ = 256 << 10

_jax_mod = None


def _jax():
    """Module-cached jax import: the take path runs per batch and the
    `import jax` statement is a sys.modules dict hit + attr dance we
    don't need to repeat there."""
    global _jax_mod
    if _jax_mod is None:
        import jax
        _jax_mod = jax
    return _jax_mod


_d2d_put_fn = False      # unresolved; None: the public call copies


def _d2d_put(probe_from, probe_to):
    """The copy of one single-device jax.Array onto another device with
    jax.device_put's Python layers taken off: for such an array
    ``jax.device_put(x, device)`` comes down to
    ``pxla.batched_device_put(x.aval, SingleDeviceSharding(device), [x],
    [device])`` (jax/_src/dispatch.py, _device_put_sharding_impl), and
    what lies above it (the pytree flatten, the primitive's bind, the
    abstractify, a new sharding object a call) costs more on a slow
    host than the launch of the copy itself: the take runs once a
    frame on the thread that cuts it, on four chips the one event
    thread (PERF.md section 6, PR 36). The name is private to jax, so
    it is resolved once and PROVED before it is trusted: a few numbers
    go from ``probe_from`` to ``probe_to`` (the first real copy's own
    two devices) through either call, and unless the private call gives what the
    public one gives (values, dtype, committed sharding, device) this
    returns None for good and every take makes the public call. Which
    of the two copied is counted: ``ici_d2d_copies_direct|public``."""
    global _d2d_put_fn
    if _d2d_put_fn is False:
        _d2d_put_fn = _proved_d2d_put(probe_from, probe_to)
    return _d2d_put_fn


def _private_batched_put():
    from jax._src.interpreters.pxla import batched_device_put
    return batched_device_put


def _proved_d2d_put(src, dst):
    import numpy as np
    jax = _jax()
    try:
        batched_device_put = _private_batched_put()
        x = jax.device_put(np.arange(8, dtype=np.float32), src)
        want = jax.device_put(x, dst)
        got = batched_device_put(
            x.aval, jax.sharding.SingleDeviceSharding(dst), [x], [dst])
        same = (type(got) is type(want) and got.committed
                and got.sharding == want.sharding
                and got.devices() == want.devices() == {dst}
                and got.dtype == want.dtype and got.shape == want.shape
                and np.array_equal(np.asarray(got), np.asarray(want)))
    except Exception:
        same = False
    if not same:
        logging.getLogger("brpc_tpu.transport").warning(
            "ici: jax %s has no usable pxla.batched_device_put; device "
            "batches are copied through jax.device_put", jax.__version__)
        return None
    return batched_device_put


def _stager():
    """The process-wide pinned H2D stager (plain device_put when the
    native pinned arena or jax transfer runtime is absent)."""
    from brpc_tpu.butil.device_pool import global_pinned_stager
    return global_pinned_stager()

_PROC_UUID = uuidlib.uuid4().hex

# sender-side registry for same-process peers: uuid -> arrays
_local_exchange: Dict[int, list] = {}
_local_lock = threading.Lock()

_uuid_base = int.from_bytes(os.urandom(4), "big")
_uuid_counter = itertools.count(1)


def _next_uuid() -> int:
    return (_uuid_base << 32) | (next(_uuid_counter) & 0xFFFFFFFF)


# ------------------------------------------------------------------ PjRt
_server_lock = threading.Lock()
_transfer_server = None
_transfer_failed = False
_transfer_error: Optional[str] = None
_conn_cache: Dict[str, object] = {}
_lane_status_var = None


def _postfork_reset() -> None:
    """Fork hygiene: the PjRt transfer server and its connection cache
    are device-runtime handles owned by the parent — a forked shard
    must re-probe the lane itself (or, the normal case, never touch
    the device at all). The handles are abandoned, not dropped: the
    child must not run their destructors (postfork.abandon)."""
    global _transfer_server, _transfer_failed, _transfer_error
    global _conn_cache, _lane_status_var, _server_lock
    _postfork.abandon(_transfer_server)
    for pconn in _conn_cache.values():
        _postfork.abandon(pconn)
    _transfer_server = None
    _transfer_failed = False
    _transfer_error = None
    _conn_cache = {}
    _lane_status_var = None
    _server_lock = threading.Lock()


from brpc_tpu.butil import postfork as _postfork  # noqa: E402
#   (registration ships with the lane state it resets)

_postfork.register("transport.ici", _postfork_reset)


def _publish_lane_status() -> None:
    """Expose transfer-server state as a bvar (/vars ici_transfer_lane)
    so lane degradation is observable, not a silent latch."""
    global _lane_status_var
    try:
        from brpc_tpu.bvar import Status
        if _lane_status_var is None:
            _lane_status_var = Status("init").expose("ici_transfer_lane")
        _lane_status_var.set_value(
            "up" if _transfer_server is not None
            else f"down: {_transfer_error or 'not started'}")
    except Exception:
        pass


def transfer_lane_status() -> str:
    """'up' | 'down: <reason>' | 'not started' — the startup-probe hook
    (rdma_helper.cpp's global-init + fallback story made queryable)."""
    if _transfer_server is not None:
        return "up"
    if _transfer_failed:
        return f"down: {_transfer_error}"
    return "not started"


def _get_transfer_server():
    """Process-global PjRt transfer server (the rdma_helper.cpp global
    init slot). None when jax/the backend doesn't support it — the
    staged lane takes over (loudly: warning log + bvar, and
    BRPC_TPU_ICI_REQUIRE_PULL=1 turns degradation into an error)."""
    global _transfer_server, _transfer_failed, _transfer_error
    if os.environ.get("BRPC_TPU_ICI_FORCE_STAGED"):
        return None       # test/ops knob: exercise the degraded lane
    if _transfer_server is not None or _transfer_failed:
        return _transfer_server
    with _server_lock:
        if _transfer_server is not None or _transfer_failed:
            return _transfer_server
        try:
            import jax
            from jax.experimental import transfer

            from brpc_tpu.butil.jax_runtime import ensure_compile_cache
            ensure_compile_cache()
            client = jax.devices()[0].client
            # explicit socket transport addresses: the default local bulk
            # transport only moves bytes within one process (aborts on a
            # cross-process pull); binding sockets gives the DCN lane
            host = os.environ.get("BRPC_TPU_TRANSFER_HOST", "0.0.0.0")
            _transfer_server = transfer.start_transfer_server(
                client, f"{host}:0", [f"{host}:0"])
            logger.info("ici: PjRt transfer server up at %s",
                        _transfer_server.address())
        except Exception as e:
            _transfer_failed = True
            _transfer_server = None
            _transfer_error = f"{type(e).__name__}: {e}"
            if os.environ.get("BRPC_TPU_ICI_REQUIRE_PULL"):
                raise ConnectionError(
                    f"ici: PjRt transfer server unavailable and "
                    f"BRPC_TPU_ICI_REQUIRE_PULL is set: {_transfer_error}")
            logger.warning(
                "ici: PjRt transfer server unavailable — device payloads "
                "DEGRADE to the host-staged lane (%s)", _transfer_error)
        _publish_lane_status()
    return _transfer_server


def _get_pull_conn(address: str):
    """Cached TransferConnection to a peer's transfer server."""
    srv = _get_transfer_server()
    if srv is None:
        raise ConnectionError("no local transfer server to pull with")
    conn = _conn_cache.get(address)
    if conn is None:
        with _server_lock:
            conn = _conn_cache.get(address)
            if conn is None:
                conn = srv.connect(address)
                _conn_cache[address] = conn
    return conn


def _canonical_addr(addr: str, peer_host: str) -> str:
    """The transfer server binds [::]:port; rewrite the wildcard host to
    the address we already reach the peer at (the TCP bootstrap host)."""
    host, _, port = addr.rpartition(":")
    if host in ("[::]", "0.0.0.0", ""):
        return f"{peer_host}:{port}"
    return addr


# shared default pool: one budget per process, like the reference's one
# block pool per NIC (rdma/block_pool.cpp global region registry)
_default_pool = DeviceRecvPool()


_lazy_adders: List["_LazyAdder"] = []


class _LazyAdder:
    """Counter that only materializes its bvar on first use. Instances
    register themselves so ``expose_ici_vars`` (called at Server.start)
    can RE-expose a materialized counter a test fixture's
    unexpose_all() stripped — without the re-expose, a server restart
    silently dropped every ici_* counter from /vars."""

    def __init__(self, name: str):
        self._name = name
        self._var = None
        _lazy_adders.append(self)

    def add(self, n: int) -> None:
        try:
            if self._var is None:
                from brpc_tpu.bvar import Adder
                self._var = Adder().expose(self._name)
            self._var.add(n)
        except Exception:
            pass

    def get_value(self) -> int:
        var = self._var
        try:
            return int(var.get_value()) if var is not None else 0
        except Exception:
            return 0

    def reexpose_counter(self) -> None:
        try:
            if self._var is not None:
                self._var.expose(self._name)
        except Exception:
            pass


# await_pull registrations whose peer died before pulling: the transfer
# API has no cancel, so these stay pinned until process exit — counted
# here so the leak is observable (/vars ici_unpulled_registrations).
# UPPER BOUND: un-ACKed pull-registered batches at close; a batch the
# peer pulled but had not yet acknowledged is counted too.
_unpulled_registrations = _LazyAdder("ici_unpulled_registrations")

# the HBM those leaked registrations pin, and the circuit breaker that
# BOUNDS it — attributed PER PEER EPOCH so one flapping peer degrades
# only itself (block_pool.cpp:271-340 freelist hygiene, adapted to an
# API with no cancel). The epoch is the peer's per-process uuid from
# the hello: a restarted peer arrives under a fresh epoch with a zero
# count, so the breaker recovers on reconnect. The GLOBAL cap stays —
# the leaked registrations of dead epochs remain pinned (the transfer
# API has no cancel), so the process-wide bound cannot honestly decay;
# past it every peer degrades to the host-staged lane.
# /vars ici_unpulled_bytes tracks the global estimate.
_unpulled_bytes = _LazyAdder("ici_unpulled_bytes")
# the real leaked/reclaimed counter PAIR the /device page surfaces:
# leaked = bytes a closing conn abandoned (un-ACKed pull registrations
# plus same-process exchange entries handed to the grace queue),
# reclaimed = bytes the grace sweep actually dropped. leaked - reclaimed
# is the live pinned estimate an operator watches.
_leaked_bytes_counter = _LazyAdder("ici_leaked_bytes")
_reclaimed_bytes_counter = _LazyAdder("ici_reclaimed_bytes")
_leaked_pull_bytes = [0]                    # global, all epochs
_leaked_by_epoch: Dict[str, int] = {}       # peer proc uuid -> bytes
_LEAK_CAP_BYTES = int(os.environ.get(
    "BRPC_TPU_ICI_PULL_LEAK_CAP", 256 << 20))          # per peer epoch
# process-wide hard bound. When an operator set PULL_LEAK_CAP as a
# strict HBM bound (its pre-per-epoch meaning) and no global cap, that
# value stays the global bound too — per-epoch attribution must not
# silently multiply a configured footprint limit.
_LEAK_GLOBAL_CAP_BYTES = int(
    os.environ.get("BRPC_TPU_ICI_PULL_LEAK_GLOBAL_CAP")
    or os.environ.get("BRPC_TPU_ICI_PULL_LEAK_CAP")
    or (1 << 30))
_epoch_trips_logged: set = set()


_leak_breaker_logged = [False]


def _note_leaked(peer_epoch: Optional[str], nbytes: int) -> None:
    """Attribute un-pulled registration bytes to the peer epoch that
    abandoned them (called under _local_lock by close paths)."""
    _leaked_pull_bytes[0] += nbytes
    if peer_epoch:
        _leaked_by_epoch[peer_epoch] = \
            _leaked_by_epoch.get(peer_epoch, 0) + nbytes
        if len(_leaked_by_epoch) > 4096:    # bound dead-epoch bookkeeping
            # keep the heaviest offenders; the global counter still
            # carries every byte
            for k in sorted(_leaked_by_epoch,
                            key=_leaked_by_epoch.get)[:2048]:
                del _leaked_by_epoch[k]


def _pull_lane_allowed(peer_epoch: Optional[str] = None) -> bool:
    if _leaked_pull_bytes[0] >= _LEAK_GLOBAL_CAP_BYTES:
        if not _leak_breaker_logged[0]:
            # once, on the open->tripped transition (runs per batch)
            _leak_breaker_logged[0] = True
            logger.warning(
                "ici: leaked pull registrations estimated at ~%d MB "
                "process-wide (global cap %d MB, an UPPER BOUND — "
                "pulled-but-unacked batches count too) — ALL lane "
                "batches use the host-staged path. Raise "
                "BRPC_TPU_ICI_PULL_LEAK_GLOBAL_CAP to re-enable.",
                _leaked_pull_bytes[0] >> 20, _LEAK_GLOBAL_CAP_BYTES >> 20)
        return False
    if peer_epoch and \
            _leaked_by_epoch.get(peer_epoch, 0) >= _LEAK_CAP_BYTES:
        if peer_epoch not in _epoch_trips_logged:
            _epoch_trips_logged.add(peer_epoch)
            logger.warning(
                "ici: peer epoch %s abandoned ~%d MB of pull "
                "registrations (per-epoch cap %d MB) — its lane "
                "batches degrade to the host-staged path until it "
                "reconnects under a fresh epoch",
                peer_epoch[:16], _leaked_by_epoch[peer_epoch] >> 20,
                _LEAK_CAP_BYTES >> 20)
        return False    # this epoch's own abandonment record gates it
    return True


# same-process exchange entries from closed connections are reclaimed on
# a grace timer, not immediately: close() flushes queued descriptor
# frames, so the peer may legitimately still take them — an instant pop
# would turn that take into an error. Tunable so soak tests can cycle
# quickly (flag ici_reclaim_grace_s).
from brpc_tpu.butil.flags import define_flag as _define_flag, flag as _flag

_define_flag("ici_reclaim_grace_s", 30.0,
             "seconds a closed connection's same-process exchange "
             "entries linger before reclaim (peer may still take them)")

# --- device-lane speed-run knobs (docs/performance.md "Device lane
# tuning"): the idle ACK closes the "cells only balance after
# close" gap, coalescing collapses bursts of tiny batches into one
# frame/registration/reservation, and the adaptive grant lets a
# receiver with headroom deepen the sender's pipeline.
_define_flag("ici_idle_ack_ms", 2.0,
             "idle ACK: a conn that consumed batches but has no "
             "reverse traffic sends a bare ACK after this many ms so "
             "the sender's window reopens (and its /device cells "
             "balance) without waiting for close; <=0 disables")
_define_flag("ici_coalesce_bytes", 16 << 10,
             "lane batches whose arrays total at most this many bytes "
             "are eligible to coalesce into one descriptor frame / one "
             "pull registration / one receiver reservation; <=0 "
             "disables coalescing")
_define_flag("ici_coalesce_max", 16,
             "max lane batches per coalesced frame (the flush-on-"
             "window-or-bytes cap)")
_define_flag("ici_adaptive_window", True,
             "receivers ride a window grant on bare ACKs sized from "
             "pool headroom: free pool -> grant 2x the hello window "
             "(deeper pipelining), pool under pressure -> window/4")


def _reclaim_grace_s() -> float:
    return float(_flag("ici_reclaim_grace_s"))


_reclaim_queue: Deque[Tuple[float, int]] = deque()
# uids on the grace queue, with the byte footprint their close charged
# to ici_leaked_bytes: whichever way the entry leaves — swept after the
# grace, or legitimately TAKEN by the peer mid-grace — the same bytes
# credit ici_reclaimed_bytes exactly once, so the /device pinned
# estimate (leaked - reclaimed) cannot drift upward on delivered
# batches (guarded by _local_lock like the exchange itself)
_grace_uid_bytes: Dict[int, int] = {}


def _sweep_reclaim(now: Optional[float] = None) -> None:
    """Drop expired same-process exchange entries (called
    opportunistically from lane activity and close). Reclaimed bytes
    are counted (ici_reclaimed_bytes) so /device can show how much of
    the leaked estimate actually came back."""
    now = time.monotonic() if now is None else now
    freed = 0
    with _local_lock:
        while _reclaim_queue and _reclaim_queue[0][0] <= now:
            _, uid = _reclaim_queue.popleft()
            _local_exchange.pop(uid, None)
            # credit what close charged — even when the peer already
            # took the entry (its take credited it, pop above is a
            # no-op and the uid is gone from the ledger)
            freed += _grace_uid_bytes.pop(uid, 0)
    if freed:
        _reclaimed_bytes_counter.add(freed)


def leak_snapshot() -> dict:
    """The /device leak pane: what the lane has abandoned, what came
    back, and where the circuit breaker stands."""
    with _local_lock:
        by_epoch = len(_leaked_by_epoch)
        grace_queued = len(_reclaim_queue)
    leaked = _leaked_bytes_counter.get_value()
    reclaimed = _reclaimed_bytes_counter.get_value()
    return {
        "leaked_bytes": leaked,
        "reclaimed_bytes": reclaimed,
        "pinned_bytes_estimate": max(0, leaked - reclaimed),
        "leaked_pull_bytes": _leaked_pull_bytes[0],
        "unpulled_registrations": _unpulled_registrations.get_value(),
        "epochs_tracked": by_epoch,
        "grace_queue": grace_queued,
        "leak_cap_bytes": _LEAK_CAP_BYTES,
        "leak_global_cap_bytes": _LEAK_GLOBAL_CAP_BYTES,
        "pull_lane_tripped":
            _leaked_pull_bytes[0] >= _LEAK_GLOBAL_CAP_BYTES,
    }


def expose_ici_vars() -> None:
    """(Re-)expose the lane's bvars — called from Server.start like the
    socket counters (the PR 2 unexpose_all survival rule): a restarted
    server must not silently drop ici_* from /vars."""
    global _lane_status_var
    if _lane_status_var is not None:
        try:
            _lane_status_var.expose("ici_transfer_lane")
        except Exception:
            pass
    else:
        _publish_lane_status()
    for adder in _lazy_adders:
        adder.reexpose_counter()


def _encode_spec(a) -> bytes:
    dt = str(a.dtype).encode()
    parts = [struct.pack(">B", len(dt)), dt, struct.pack(">B", a.ndim)]
    if a.ndim:
        parts.append(struct.pack(f">{a.ndim}q", *a.shape))
    parts.append(struct.pack(">Q", a.nbytes))
    return b"".join(parts)


def _decode_spec(data: bytes, pos: int) -> Tuple[dict, int]:
    (dtlen,) = struct.unpack_from(">B", data, pos)
    pos += 1
    dtype = data[pos:pos + dtlen].decode()
    pos += dtlen
    (rank,) = struct.unpack_from(">B", data, pos)
    pos += 1
    shape = struct.unpack_from(f">{rank}q", data, pos) if rank else ()
    pos += 8 * rank
    (nbytes,) = struct.unpack_from(">Q", data, pos)
    pos += 8
    return {"dtype": dtype, "shape": tuple(shape), "nbytes": nbytes}, pos


def _encode_descriptor(uid: int, arrays) -> bytes:
    parts = [struct.pack(">QH", uid, len(arrays))]
    for a in arrays:
        parts.append(_encode_spec(a))
    return b"".join(parts)


def _decode_descriptor(data: bytes) -> Tuple[int, List[dict]]:
    uid, count = struct.unpack_from(">QH", data, 0)
    pos = 10
    specs = []
    for _ in range(count):
        spec, pos = _decode_spec(data, pos)
        specs.append(spec)
    return uid, specs


def _encode_coalesced(uid: Optional[int], batches) -> bytes:
    """F_COALESCED payload: N sub-batches in one frame. ``uid`` is the
    group's single registration (descriptor mode); None means staged
    mode (each sub-batch's numpy blob rides inline)."""
    if uid is None:
        parts = [struct.pack(">BH", 1, len(batches))]
        for arrays in batches:
            blob = _encode_device_batch(arrays)
            parts.append(struct.pack(">I", len(blob)))
            parts.append(blob)
    else:
        parts = [struct.pack(">BH", 0, len(batches)),
                 struct.pack(">Q", uid)]
        for arrays in batches:
            parts.append(struct.pack(">H", len(arrays)))
            for a in arrays:
                parts.append(_encode_spec(a))
    return b"".join(parts)


def _decode_coalesced(data: bytes):
    """-> ("staged", None, [blob, ...]) |
          ("pull", uid, [[spec, ...] per sub-batch])"""
    mode, count = struct.unpack_from(">BH", data, 0)
    pos = 3
    if mode == 1:
        blobs = []
        for _ in range(count):
            (ln,) = struct.unpack_from(">I", data, pos)
            pos += 4
            blobs.append(data[pos:pos + ln])
            pos += ln
        return "staged", None, blobs
    (uid,) = struct.unpack_from(">Q", data, pos)
    pos += 8
    groups = []
    for _ in range(count):
        (narr,) = struct.unpack_from(">H", data, pos)
        pos += 2
        specs = []
        for _ in range(narr):
            spec, pos = _decode_spec(data, pos)
            specs.append(spec)
        groups.append(specs)
    return "pull", uid, groups


class IciConn(Conn):
    """One ici:// connection: RdmaEndpoint's state machine re-expressed.

    Outbound items queue in FIFO (`_outq`) so a device-batch descriptor
    can never overtake — or be overtaken by — the app bytes of the RPC
    that references it; the window check happens at flush time on the
    queue head, so a stalled lane stalls everything behind it, exactly
    like the RDMA endpoint's window_size gate on the whole send queue
    (rdma_endpoint.h:235-241)."""

    supports_device_lane = True
    # the socket's writer hands a queued batch's stage tracker through
    # to the flush/ack machinery (transport/device_stats.BatchTracker)
    supports_device_tracker = True
    # write() and write_device_payload() only enqueue and flush; they
    # never park the caller, so the context whose push claims a
    # socket's writership sends in place (TcpConn's write-once
    # discipline). Both take flush=False: the socket's single writer
    # queues a device batch, its envelope and whatever other callers
    # queued behind them, then calls flush() once — one TCP write.
    inline_write_ok = True

    def __init__(self, inner: TcpConn, local: EndPoint, remote: EndPoint,
                 recv_device_ordinal: int = 0,
                 window: int = DEFAULT_WINDOW,
                 pool: Optional[DeviceRecvPool] = None):
        self._inner = inner
        # The inner conn's fd is this conn's fd: what it can do as an
        # event source, this conn can, and says so by handing the inner
        # conn's own answer over (one it lacks, this conn lacks). The
        # Socket learns it once, at birth, and runs the read cycle TCP
        # has: a busy period with data pending pauses read interest
        # once and resumes once, and a sync joiner polls pluck_fd and
        # reads its own reply through read_into. short_read_drained is
        # not among them (PERF.md section 6, PR 27, has why), nor is
        # stream_fd: what arrives on the fd are lane frames, not the
        # application's bytes, so no native loop and no raw write may
        # use it.
        self.level_triggered = inner.level_triggered
        self.pause_read_events = inner.pause_read_events
        self.resume_read_events = inner.resume_read_events
        self.pluck_fd = inner.pluck_fd
        self._local = local
        self._remote = remote
        self._recv_device_ordinal = recv_device_ordinal
        self._window = window                    # credits we grant the peer
        self._pool = pool or _default_pool
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()
        # _pump is reached from the input-drain fiber (read_into) AND
        # from processing fibers (take_device_payload); the ingest state
        # (_inbuf/_appbuf/_lane/ack counters) needs one owner at a time
        self._pump_lock = threading.Lock()
        # outbound: FIFO of ("bytes", payload) | ("ctrl", ftype, payload)
        # | ("lane", arrays, tracker) — the tracker (device_stats stage
        # timeline, or None) rides the queue item so the flush/ack legs
        # never look anything up
        self._outq: Deque[Tuple] = deque()
        self._out_bytes = 0                      # backpressure accounting
        self._wirebuf = bytearray()              # framed, partially written
        # flush-stamp bookkeeping (all under _flush_lock): cumulative
        # bytes pushed into TCP, and (target_offset, tracker) marks —
        # a lane frame's tracker stamps lane_flushed when the wire
        # counter passes its frame's end
        self._wire_written = 0
        self._wire_marks: Deque[Tuple[int, object]] = deque()
        # the pump's read buffer, kept for the conn's life (under
        # _pump_lock) in place of a fresh 256 KB a pump
        self._rbuf = memoryview(bytearray(_PUMP_READ))
        self._inbuf = bytearray()
        self._appbuf = bytearray()
        self._lane: Deque[Tuple] = deque()       # inbound batch descriptors
        self._closed_read = False
        self._closed = False
        # set when an unsendable batch is detected at flush time: every
        # later write/flush refuses, so no frame can follow the popped
        # poison item and the lane/envelope FIFO pairing stays intact
        # even if a channel catches the error and retries
        self._poisoned: Optional[str] = None
        # flow-control state (sender side)
        # flow-control state below is touched from the flush path (under
        # _flush_lock) AND the pump path (under _pump_lock) — it needs
        # its own lock, not either of those
        self._fc_lock = threading.Lock()
        self._sent = 0                           # device batches sent
        self._peer_acked = 0                     # cumulative acks from peer
        # byte budget: footprints of un-ACKed batches, FIFO (the peer
        # consumes lane batches in order), so bytes-in-flight is
        # derivable from the cumulative ack count; each entry is
        # (footprint, is_pull, tracker-or-None)
        self._inflight_footprints: Deque[Tuple[int, bool, object]] = \
            deque()
        self._inflight_bytes = 0
        # uids this connection registered for peer pull; reclaimed (or at
        # least counted) on close/failure
        self._issued_uids: List[int] = []
        self._pull_registered = 0                # await_pull count (no cancel)
        # flow-control state (receiver side)
        self._consumed = 0                       # batches we pulled
        self._acked_sent = 0                     # last consumed count sent
        # adaptive window: last grant the peer rode on a bare ACK
        # (0 = none yet; effective window stays the hello window)
        self._peer_grant = 0
        # idle-ACK duty pending (under _fc_lock) + lane counters
        self._idle_ack_armed = False
        self._idle_acks = 0
        self._coalesced_frames = 0
        self._coalesced_batches = 0
        # take-path caches: the recv device is fixed per conn (the
        # ordinal came from the endpoint), so jax.devices() and the
        # SingleDeviceSharding need resolving once, not per batch
        self._recv_dev = None
        self._recv_sharding = None
        # handshake
        self.peer_info: Optional[dict] = None
        self._hello_evt = threading.Event()
        self._want_writable = False
        self._on_writable_cb: Optional[Callable[[], None]] = None
        srv = _get_transfer_server()
        hello = {
            "proc": _PROC_UUID,
            "transfer_addr": srv.address() if srv is not None else None,
            "window": self._window,
            # advertised recv byte budget: the sender derives its
            # effective window from this. Like RDMA's per-connection
            # pre-posted rbufs (rdma_endpoint.h:235-241) it is a
            # PER-CONNECTION bound — window × the largest block class —
            # capped by the pool; aggregate pressure from many senders
            # still lands on the pool's blocking admission, exactly as
            # rbuf posting does when the block pool runs dry.
            # max_batch is the pool capacity: the largest single batch
            # the receiver could EVER admit (bigger ones are unsendable;
            # batches between budget and max_batch go out alone)
            "budget": min(self._pool.capacity,
                          self._window * BLOCK_CLASSES[-1]),
            "max_batch": self._pool.capacity,
            "device": recv_device_ordinal,
            "can_pull": srv is not None,
        }
        _dev_stats.global_device_stats().track_device_conn(self)
        self._enqueue(("ctrl", F_HELLO, json.dumps(hello).encode()))
        self._flush()

    # --------------------------------------------------------- outbound
    def _enqueue(self, item: Tuple) -> None:
        with self._lock:
            if self._closed:
                # close() flips this under the same lock BEFORE it
                # sweeps queued-batch trackers: an enqueue losing that
                # race must fail loudly, or its tracker would be in no
                # sweep list and the cell would never balance
                raise ConnectionError("ici conn closed")
            if self._out_bytes > _MAX_OUT:
                raise BlockingIOError("ici out-buffer full")
            self._outq.append(item)
            if item[0] == "bytes":
                self._out_bytes += len(item[1])

    def _frame(self, ftype: int, payload: bytes) -> bytes:
        # every frame piggybacks the cumulative consumed count — the
        # imm-data ACK of rdma_endpoint.h:138
        self._acked_sent = self._consumed
        return _HDR.pack(ftype, self._consumed, len(payload)) + payload

    @staticmethod
    def _batch_footprint(arrays) -> int:
        """The pool footprint the receiver will reserve for this batch
        (same size-class rounding as DeviceRecvPool)."""
        return sum(round_to_class(a.nbytes) for a in arrays)

    def _apply_peer_ack(self, ack: int) -> None:
        """Advance the cumulative-consumed count and retire the matching
        FIFO footprints (bytes-in-flight accounting). Retired batches'
        stage trackers settle AFTER _fc_lock drops — the settle touches
        the cell lock and submits the device span, neither of which
        belongs under flow-control state."""
        acked_trackers = []
        with self._fc_lock:
            while self._peer_acked < ack and self._inflight_footprints:
                fp, _, tracker = self._inflight_footprints.popleft()
                self._inflight_bytes -= fp
                self._peer_acked += 1
                if tracker is not None:
                    acked_trackers.append(tracker)
            self._peer_acked = max(self._peer_acked, ack)
        for tracker in acked_trackers:
            tracker.lane_acked()

    def _unsendable_reason(self, arrays) -> Optional[str]:
        """A batch no receiver state could ever admit (footprint over
        the peer's pool capacity — pool.reserve rejects those outright)
        must fail at the source, not wedge the lane. Returns the error
        text, or None when sendable / peer unknown."""
        max_batch = int((self.peer_info or {}).get("max_batch") or 0)
        if max_batch:
            need = self._batch_footprint(arrays)
            if need > max_batch:
                return (f"ici: device batch footprint {need}B exceeds "
                        f"the peer's pool capacity {max_batch}B — "
                        f"unsendable (split the batch or raise the "
                        f"peer's DeviceRecvPool capacity)")
        return None

    def _effective_window(self, info: dict) -> int:
        """The batch window actually gating sends: the peer's hello
        window, overridden by the adaptive grant it rode on a bare ACK
        (bounded to 4x the hello window so a corrupt grant can't blow
        the pipeline open)."""
        base = int(info.get("window", 1))
        grant = self._peer_grant
        if grant > 0:
            return max(1, min(grant, base * 4))
        return base

    def _lane_ready(self) -> bool:
        """May the queue-head device batch go out? Gates: hello received
        (QP up), batch window (adaptive — see _effective_window), and
        the peer's advertised byte budget — bytes in flight plus this
        batch must fit, so per-connection in-flight bytes can never
        exceed what the receiver advertised. A batch larger than the
        budget (but within the peer's pool capacity) goes out ALONE
        once the lane drains."""
        info = self.peer_info
        if info is None:
            return False                     # QP not up yet
        budget = int(info.get("budget") or 0)
        need = self._batch_footprint(self._outq[0][1])
        window = self._effective_window(info)
        with self._fc_lock:
            if (self._sent - self._peer_acked) >= window:
                return False
            if (budget and self._inflight_bytes + need > budget
                    and self._inflight_bytes > 0):
                return False
        return True

    def _stage_lane_frame(self, arrays, tracker=None) -> bytes:
        """Turn a lane batch into its wire frame, registering the arrays
        for peer pull (or falling back to the staged lane). The tracker
        stamps descriptor-encode done here (host-stage boundary) and
        rides the in-flight footprint FIFO to its ack."""
        info = self.peer_info or {}
        footprint = self._batch_footprint(arrays)
        if info.get("proc") == _PROC_UUID:
            # same process: in-memory registry; take() device_puts (D2D)
            uid = _next_uuid()
            with _local_lock:
                _local_exchange[uid] = list(arrays)
            self._issued_uids.append(uid)
            frame = self._frame(F_DESCRIPTOR, _encode_descriptor(uid, arrays))
            is_pull = False
            staged = False
        else:
            srv = _get_transfer_server()
            if srv is not None and info.get("can_pull") \
                    and _pull_lane_allowed(info.get("proc")):
                uid = _next_uuid()
                srv.await_pull(uid, list(arrays))
                self._issued_uids.append(uid)
                with self._fc_lock:
                    self._pull_registered += 1
                frame = self._frame(F_DESCRIPTOR,
                                    _encode_descriptor(uid, arrays))
                is_pull = True
                staged = False
            else:
                # degraded lane: host-staged numpy over the control stream
                frame = self._frame(F_STAGED, _encode_device_batch(arrays))
                is_pull = False
                staged = True
        if tracker is not None:
            tracker.lane_encoded(staged=staged)
        with self._fc_lock:
            self._inflight_footprints.append((footprint, is_pull, tracker))
            self._inflight_bytes += footprint
            self._sent += 1
        # NOTE: no _sweep_reclaim() here — the grace sweep runs on the
        # timer close() schedules, not on every staged frame (it was a
        # lock + clock read on the hottest path in the lane)
        return frame

    def _collect_coalesce(self, head: Tuple) -> Optional[List[Tuple]]:
        """Called under _lock with ``head`` (a lane item) just popped:
        pull additional SMALL lane batches out of _outq so the group
        rides ONE coalesced frame — one descriptor, one registration,
        one receiver-side reservation. Hoisting a later lane batch over
        interleaved byte frames is safe (a descriptor only has to
        precede its OWN envelope; the receiver matches batches to
        envelopes FIFO in descriptor order) — which is also why an
        INELIGIBLE lane batch stops the scan: lane batches must keep
        their relative order. Returns the extra items (already removed
        from _outq), or None."""
        limit = int(_flag("ici_coalesce_bytes"))
        nmax = int(_flag("ici_coalesce_max"))
        if limit <= 0 or nmax <= 1 or not self._outq:
            return None
        if sum(a.nbytes for a in head[1]) > limit:
            return None
        info = self.peer_info or {}
        budget = int(info.get("budget") or 0)
        window = self._effective_window(info)
        with self._fc_lock:
            slots = window - (self._sent - self._peer_acked) - 1
            room = (budget - self._inflight_bytes
                    - self._batch_footprint(head[1])) if budget else None
        if slots <= 0:
            return None
        extras: List[Tuple] = []
        keep: Deque[Tuple] = deque()
        while self._outq and len(extras) < nmax - 1 and slots > 0:
            it = self._outq.popleft()
            if it[0] != "lane":
                keep.append(it)
                continue
            fp = self._batch_footprint(it[1])
            if sum(a.nbytes for a in it[1]) > limit \
                    or (room is not None and fp > room) \
                    or self._unsendable_reason(it[1]) is not None:
                keep.append(it)
                break
            extras.append(it)
            slots -= 1
            if room is not None:
                room -= fp
        while self._outq:
            keep.append(self._outq.popleft())
        self._outq = keep
        return extras or None

    def _stage_coalesced_frame(self, items: List[Tuple]) -> bytes:
        """One F_COALESCED frame for N small lane batches: one uid /
        one pull registration / one receiver reservation for the whole
        group, while window, budget, and stage-tracker accounting stay
        per sub-batch (each still consumes one window slot, one ack)."""
        info = self.peer_info or {}
        batches = [it[1] for it in items]
        flat = [a for b in batches for a in b]
        staged = False
        is_pull = False
        if info.get("proc") == _PROC_UUID:
            uid = _next_uuid()
            with _local_lock:
                _local_exchange[uid] = flat
            self._issued_uids.append(uid)
            payload = _encode_coalesced(uid, batches)
        else:
            srv = _get_transfer_server()
            if srv is not None and info.get("can_pull") \
                    and _pull_lane_allowed(info.get("proc")):
                uid = _next_uuid()
                srv.await_pull(uid, flat)
                self._issued_uids.append(uid)
                with self._fc_lock:
                    self._pull_registered += 1
                payload = _encode_coalesced(uid, batches)
                is_pull = True
            else:
                staged = True
                payload = _encode_coalesced(None, batches)
        frame = self._frame(F_COALESCED, payload)
        for it in items:
            if it[2] is not None:
                it[2].lane_encoded(staged=staged)
        with self._fc_lock:
            for it in items:
                fp = self._batch_footprint(it[1])
                self._inflight_footprints.append((fp, is_pull, it[2]))
                self._inflight_bytes += fp
                self._sent += 1
            self._coalesced_frames += 1
            self._coalesced_batches += len(items)
        return frame

    def _flush(self) -> bool:
        """Drain wirebuf + eligible queue items into TCP. Single-flight
        (two flushers would interleave framed bytes). True = all
        drained. Framing is a GATHER pass: every currently-sendable
        queue item is framed before each TCP write, so a burst pays one
        syscall, not one per item."""
        if self._poisoned is not None:
            raise ConnectionError(self._poisoned)
        with self._flush_lock:
            while True:
                # re-check INSIDE the lock: a writer that passed the
                # outer check while another flusher was poisoning must
                # not drain its frame past the popped batch
                if self._poisoned is not None:
                    raise ConnectionError(self._poisoned)
                stalled = self._frame_ready_items()
                if not self._wirebuf:
                    return not stalled
                while self._wirebuf:
                    # the memoryview is released EXPLICITLY before the
                    # resize below: callee frames keep the view object
                    # alive in their locals, and a frame-walking sampler
                    # (the flight recorder) can briefly pin those frames
                    # — a refcount-implicit release would then race the
                    # `del` into "BufferError: Existing exports of data"
                    mv = memoryview(self._wirebuf)
                    try:
                        n = self._inner.write(mv)
                    except BlockingIOError:
                        self._inner.request_writable_event()
                        return False
                    finally:
                        mv.release()
                    del self._wirebuf[:n]
                    self._wire_written += n
                    while self._wire_marks and \
                            self._wire_marks[0][0] <= self._wire_written:
                        # this lane frame's bytes fully left for TCP:
                        # pump-flush waypoint (wire_us starts here)
                        self._wire_marks.popleft()[1].lane_flushed()
                if stalled:
                    return False

    def _frame_ready_items(self) -> bool:
        """Pop every currently-sendable _outq item and frame it into
        _wirebuf (the caller pays one TCP write for the lot — PR 4's
        gather-write idea applied to the lane). Adjacent small lane
        batches coalesce into one F_COALESCED frame. Returns True when
        the queue head is a credit-gated lane batch (caller parks for
        the ACK edge). Runs under _flush_lock."""
        while len(self._wirebuf) < _FLUSH_CHUNK:
            poison = None
            extras = None
            gated = False
            with self._lock:
                if not self._outq:
                    return False
                item = self._outq[0]
                if item[0] == "lane":
                    poison = self._unsendable_reason(item[1])
                    if poison is not None:
                        # poison the whole connection, not just the
                        # item: later writes must not slip past the
                        # popped batch or the receiver would FIFO-
                        # match some other RPC's arrays to this
                        # RPC's envelope
                        self._outq.popleft()
                        self._poisoned = poison
                    elif not self._lane_ready():
                        # out of credit: park until an ACK arrives
                        self._want_writable = True
                        gated = True
                    else:
                        self._outq.popleft()
                        extras = self._collect_coalesce(item)
                else:
                    self._outq.popleft()
                    if item[0] == "bytes":
                        self._out_bytes -= len(item[1])
            if gated:
                # what this side has consumed still has to reach the
                # peer, whose own window may be closed on those very
                # batches: a bare ACK queued behind the gated head would
                # wait for an ACK that waits for it, both ways. It goes
                # ahead of the head (every frame carries the count, so
                # its place in the order means nothing)
                if self._consumed > self._acked_sent:
                    self._wirebuf += self._frame(F_ACK,
                                                 self._ack_grant_payload())
                return True
            if poison is not None:
                if len(item) > 2 and item[2] is not None:
                    # the popped batch's tracker settles as failed
                    # (the span carries the unsendable reason)
                    item[2].lane_failed(poison)
                raise ConnectionError(poison)
            if item[0] == "bytes":
                self._wirebuf += self._frame(F_BYTES, item[1])
            elif item[0] == "ctrl":
                self._wirebuf += self._frame(item[1], item[2])
            elif extras:
                group = [item] + extras
                self._wirebuf += self._stage_coalesced_frame(group)
                end = self._wire_written + len(self._wirebuf)
                for it in group:
                    if it[2] is not None:
                        self._wire_marks.append((end, it[2]))
            else:                             # lone lane batch
                tracker = item[2]
                self._wirebuf += self._stage_lane_frame(item[1],
                                                        tracker)
                if tracker is not None:
                    self._wire_marks.append(
                        (self._wire_written + len(self._wirebuf),
                         tracker))
        return False

    def write(self, mv: memoryview, flush: bool = True) -> int:
        if self._poisoned is not None:
            raise ConnectionError(self._poisoned)
        data = bytes(mv)
        self._enqueue(("bytes", data))
        if flush:
            self._flush()
        return len(data)

    def flush(self) -> bool:
        """Frame everything queued and hand it to TCP in one write
        (True = all drained). The closing half of a run of flush=False
        writes; a flush that stalls (TCP full, window closed) resumes
        from the writable event or the ACK edge on its own."""
        return self._flush()

    def write_device_payload(self, arrays, tracker=None,
                             flush: bool = True) -> bool:
        """Stage jax arrays on our device and queue the batch. Host
        inputs are device_put once here (H2D staging); from then on the
        payload moves device-to-device only. ``tracker``: the
        device_stats stage timeline riding this batch (or None).
        ``flush=False``: the envelope follows and its flush carries
        both. A full out-buffer raises BlockingIOError with the tracker
        still open: the caller parks the batch and hands it again."""
        jax = _jax()
        staged = []
        for a in arrays:
            if not isinstance(a, jax.Array):
                a = jax.device_put(a)
            staged.append(a)
        if self._poisoned is not None:
            if tracker is not None:
                tracker.lane_failed(self._poisoned)
            raise ConnectionError(self._poisoned)
        # fail-fast at the call site when the peer is already known
        # (otherwise flush-time detection poisons the connection)
        reason = self._unsendable_reason(staged)
        if reason is not None:
            if tracker is not None:
                tracker.lane_failed(reason)
            raise ConnectionError(reason)
        try:
            self._enqueue(("lane", staged, tracker))
        except ConnectionError as e:
            # closed-conn refusal: settle here — the batch never
            # entered a queue any sweep covers
            if tracker is not None:
                tracker.lane_failed(str(e))
            raise
        if flush:
            self._flush()
        return True

    # ---------------------------------------------------------- inbound
    def _pump(self) -> None:
        with self._pump_lock:
            fire = self._pump_locked()
        # the writable callback re-enters the write path (and a write
        # completion can pump again through read_into) — it must run
        # AFTER _pump_lock is released, never under it
        if fire is not None:
            fire()

    def _pump_locked(self) -> Optional[Callable[[], None]]:
        """Drain + decode inbound frames; returns the writable callback
        to fire once the caller has dropped _pump_lock (or None)."""
        buf = self._rbuf
        while True:
            try:
                n = self._inner.read_into(buf)
            except BlockingIOError:
                break
            if n == 0:
                self._closed_read = True
                break
            self._inbuf += buf[:n]
        window_opened = False
        while len(self._inbuf) >= _HDR.size:
            ftype, ack, length = _HDR.unpack_from(self._inbuf, 0)
            if length > _MAX_FRAME:
                raise ConnectionError(f"ici frame of {length}B exceeds max")
            if len(self._inbuf) < _HDR.size + length:
                break
            payload = bytes(self._inbuf[_HDR.size:_HDR.size + length])
            del self._inbuf[:_HDR.size + length]
            if ack > self._peer_acked:
                self._apply_peer_ack(ack)
                window_opened = True
            if ftype == F_BYTES:
                self._appbuf += payload
            elif ftype == F_DESCRIPTOR:
                uid, specs = _decode_descriptor(payload)
                self._lane.append(("pull", uid, specs))
            elif ftype == F_STAGED:
                self._lane.append(("staged", payload, None))
            elif ftype == F_COALESCED:
                mode, uid, subs = _decode_coalesced(payload)
                # one group dict shared by all sub-entries: the FIRST
                # take materializes the whole group (one pull / one
                # reservation), later takes just index into it
                group = {"mode": mode, "uid": uid, "subs": subs,
                         "out": None, "error": None}
                for i in range(len(subs)):
                    self._lane.append(("coal", group, i))
            elif ftype == F_HELLO:
                try:
                    self.peer_info = json.loads(payload.decode())
                except ValueError:
                    raise ConnectionError("ici: bad hello")
                self._hello_evt.set()
                window_opened = True          # lane may be gated on hello
            elif ftype == F_ACK:
                # header ack already applied; payload may carry the
                # receiver's adaptive window grant
                if len(payload) >= 4:
                    (grant,) = struct.unpack_from(">I", payload, 0)
                    self._peer_grant = grant
                    window_opened = True      # a wider grant may unpark
            else:
                raise ConnectionError(f"ici: unknown frame type {ftype}")
        if window_opened:
            drained = self._flush()
            if drained and self._want_writable:
                self._want_writable = False
                return self._on_writable_cb
        return None

    def read_into(self, mv: memoryview) -> int:
        # A read under 4096 bytes handed over all of _appbuf (the
        # Socket never offers less: a fresh block of 8 KB or more, or a
        # tail gap of 4096 and up) after a pump that read to EAGAIN: a
        # plucking joiner ends its drain there and polls. The event-
        # driven drain reads on to EAGAIN (this conn does not say
        # short_read_drained)
        self._pump()
        if self._appbuf:
            n = min(len(mv), len(self._appbuf))
            mv[:n] = self._appbuf[:n]
            del self._appbuf[:n]
            return n
        if self._closed_read:
            return 0
        raise BlockingIOError

    def _recv_device(self):
        """Resolved ONCE per conn: jax.devices() re-enumerates the
        client's device list per call, which the take path used to pay
        per batch."""
        dev = self._recv_dev
        if dev is None:
            dev = self._recv_dev = local_device(
                self._recv_device_ordinal, f"ici:// conn to {self._remote}")
        return dev

    def _ack_grant_payload(self) -> bytes:
        """Adaptive window grant riding the bare-ACK payload: the
        receiver sizes the sender's pipeline from its own admission
        headroom (the input the sender's ack-stage reservoir reflects —
        ack latency is set by how deep the pipeline runs vs how fast
        takes drain it). Plenty of pool headroom -> grant 2x the hello
        window (deeper pipelining); pool under pressure -> shrink
        toward window/4 so the blocking admission gate, not the wire,
        is what backs off."""
        if not _flag("ici_adaptive_window"):
            return b""
        cap = self._pool.capacity or 1
        try:
            frac = self._pool.available / cap
        except Exception:
            frac = 1.0
        if frac >= 0.5:
            grant = self._window * 2
        elif frac >= 0.25:
            grant = self._window
        else:
            grant = max(1, self._window // 4)
        return struct.pack(">I", grant)

    def _maybe_send_ack(self) -> None:
        """Bare ACK once half the window is unacknowledged and no
        reverse-direction frame has carried it (SendAck,
        rdma_endpoint.h:138)."""
        if self._consumed - self._acked_sent >= max(1, self._window // 2):
            try:
                self._enqueue(("ctrl", F_ACK, self._ack_grant_payload()))
            except BlockingIOError:
                return      # out-buffer full: the ack piggybacks later
            except ConnectionError:
                # conn closed under us (a racing close flips _closed
                # before tearing down): a courtesy ack on a dying conn
                # is worthless — it must not error the batch the
                # caller already took successfully
                return
            self._flush()

    def _arm_idle_ack(self) -> None:
        """Eager ACK: a quiescent conn must not leave its last consumed
        batches un-ACKed until close (acks normally piggyback on reverse
        traffic or fire at half-window). Armed from the take path as a
        quiet duty of the event dispatcher that owns this conn's fd: it
        comes due once, ici_idle_ack_ms later, and the next take re-arms.
        A take on the event thread (a server's request, the reply of a
        done= call) pays a dict store and wakes nobody; the event thread
        looks at what is due at the end of every tick and sleeps no
        longer than the nearest deadline. This is what lets the sender's
        /device cells balance WITHOUT a close(), and what reopens a
        ping-pong sender's window inside the same RTT."""
        if self._closed or self._consumed <= self._acked_sent:
            return
        delay = float(_flag("ici_idle_ack_ms")) / 1000.0
        if delay <= 0:
            return
        with self._fc_lock:
            if self._idle_ack_armed:
                return
            self._idle_ack_armed = True
        _syscall_stats.idle_ack_armed.add(1)
        global_dispatcher().arm_quiet_duty(
            self, time.monotonic() + delay, self._idle_ack_due)

    def _idle_ack_due(self) -> None:
        """The duty, on the event thread: nothing if a reverse frame
        carried the ACK meanwhile, else the bare ACK with its grant."""
        with self._fc_lock:
            self._idle_ack_armed = False
        if self._closed:
            return
        if self._consumed <= self._acked_sent:
            _syscall_stats.idle_ack_carried.add(1)
            return
        try:
            self._enqueue(("ctrl", F_ACK, self._ack_grant_payload()))
        except (BlockingIOError, ConnectionError):
            return
        self._idle_acks += 1
        _syscall_stats.idle_ack_sent.add(1)
        try:
            self._flush()
        except Exception:
            pass            # conn poisoned/torn down under the duty

    def _sharding_for(self, target):
        if self._recv_sharding is None:
            self._recv_sharding = \
                _jax().sharding.SingleDeviceSharding(target)
        return self._recv_sharding

    def _take_local(self, uid: int, target) -> list:
        """Same-process take: pop the exchange entry, credit a grace-
        queued uid as DELIVERED, and copy what is not on ``target`` yet
        onto it (the D2D/ICI hop)."""
        with _local_lock:
            arrays = _local_exchange.pop(uid, None)
            # a grace-queued entry (sender closed) that the peer
            # legitimately takes is DELIVERED, not leaked: credit the
            # bytes its close charged
            grace_credit = _grace_uid_bytes.pop(uid, 0) \
                if arrays is not None else 0
        if grace_credit:
            _reclaimed_bytes_counter.add(grace_credit)
        if arrays is None:
            raise ConnectionError(
                "ici: same-process batch no longer available "
                "(sender closed and its registration was "
                "reclaimed)")
        out = []
        for a in arrays:
            devs = a.devices() if hasattr(a, "devices") else ()
            out.append(a if target in devs
                       else self._copy_onto(a, devs, target))
        return out

    def _copy_onto(self, a, devs, target):
        """One array, now on ``devs``, onto ``target``: ``_d2d_put`` for
        a single-device jax.Array, the public call for anything else."""
        if len(devs) == 1:
            put = _d2d_put(next(iter(devs)), target)
            if put is not None:
                _syscall_stats.d2d_copies_direct.add(1)
                return put(a.aval, self._sharding_for(target), [a],
                           [target])
        _syscall_stats.d2d_copies_public.add(1)
        return _jax().device_put(a, target)

    def _pull_arrays(self, uid: int, specs: List[dict], target) -> list:
        """Cross-process take: PjRt pull straight onto our device."""
        jax = _jax()
        info = self.peer_info or {}
        addr = _canonical_addr(info["transfer_addr"],
                               self._remote.host or "127.0.0.1")
        pconn = _get_pull_conn(addr)
        sharding = self._sharding_for(target)
        sds = [jax.ShapeDtypeStruct(
            s["shape"], _np_dtype(s["dtype"]),
            sharding=sharding) for s in specs]
        try:
            return pconn.pull(uid, sds)
        except BaseException:
            # a failed pull poisons the cached connection
            # (peer restart leaves a half-dead channel):
            # drop it so the next pull redials
            with _server_lock:
                if _conn_cache.get(addr) is pconn:
                    del _conn_cache[addr]
            raise

    def _materialize_coalesced(self, group: dict, target) -> List[list]:
        """First take of a coalesced group: ONE pool reservation for
        the whole group's footprint, one pull (or one exchange pop /
        one staged decode), then split back into per-sub-batch lists.
        The reservation is released when the LAST array of the group
        dies (GroupReservation refcount)."""
        jax = _jax()
        info = self.peer_info or {}
        if group["mode"] == "staged":
            subs = [_decode_device_batch(blob) for blob in group["subs"]]
            footprint = sum(round_to_class(x.nbytes)
                            for b in subs for x in b)
            res = self._pool.reserve_group(footprint)
            stager = _stager()
            try:
                outs = [[stager.land(x, device=target) for x in b]
                        for b in subs]
            except BaseException:
                self._pool.release(res)
                raise
        else:
            spec_groups = group["subs"]
            flat_specs = [s for g in spec_groups for s in g]
            footprint = sum(round_to_class(s["nbytes"])
                            for s in flat_specs)
            res = self._pool.reserve_group(footprint)
            try:
                if info.get("proc") == _PROC_UUID:
                    flat = self._take_local(group["uid"], target)
                else:
                    flat = self._pull_arrays(group["uid"], flat_specs,
                                             target)
                outs = []
                pos = 0
                for g in spec_groups:
                    outs.append(list(flat[pos:pos + len(g)]))
                    pos += len(g)
            except BaseException:
                self._pool.release(res)
                raise
        from brpc_tpu.butil.device_pool import GroupReservation
        holder = GroupReservation(self._pool, res,
                                  sum(len(o) for o in outs))
        for sub in outs:
            for arr in sub:
                self._pool.attach_group_finalizer(arr, holder)
        return outs

    def _take_coalesced(self, group: dict, idx: int, target) -> list:
        err = group.get("error")
        if err is not None:
            # a sibling's materialization failed: every sub-batch of
            # the group fails the same way (one registration, one fate)
            raise ConnectionError(err)
        outs = group.get("out")
        if outs is None:
            try:
                outs = self._materialize_coalesced(group, target)
            except BaseException as e:
                group["error"] = \
                    f"ici: coalesced group materialization failed: {e}"
                raise
            group["out"] = outs
        return outs[idx]

    def take_device_payload(self):
        # NO TCP pump here: a descriptor frame always precedes its
        # message's byte frames on the wire, so by the time the parser
        # saw those bytes the descriptor was already de-enveloped into
        # _lane. Pumping TCP from the parse path would strand what it
        # read: bytes moved into _appbuf leave the kernel's buffer
        # empty, the level trigger stays silent, and no event sends the
        # input pass back for them.
        with self._pump_lock:
            if not self._lane:
                return None
            kind, a, b = self._lane.popleft()
        jax = _jax()
        target = self._recv_device()
        if kind == "coal":
            out = self._take_coalesced(a, b, target)
            with self._pump_lock:
                self._consumed += 1
            self._maybe_send_ack()
            self._arm_idle_ack()
            return out
        footprints: List[int] = []
        try:
            # reserve inside the try: a partial multi-array reservation
            # must be released when a later reserve raises. BOTH lanes
            # reserve — the staged fallback is subject to the same HBM
            # admission control as the pull path (a peer without a
            # transfer server must not escape the budget).
            if kind == "staged":
                batch = _decode_device_batch(a)
                stager = _stager()
                for x in batch:
                    footprints.append(self._pool.reserve(x.nbytes))
                out = [stager.land(x, device=target) for x in batch]
            else:
                uid, specs = a, b
                info = self.peer_info or {}
                for s in specs:
                    footprints.append(self._pool.reserve(s["nbytes"]))
                if info.get("proc") == _PROC_UUID:
                    # same-process: receiver-driven device_put = the D2D
                    # copy (ICI hop on real multi-chip hardware)
                    out = self._take_local(uid, target)
                else:
                    out = self._pull_arrays(uid, specs, target)
        except BaseException:
            # admission timeout (MemoryError after reserve's 10s wait)
            # or pull failure: the error escapes into the input path,
            # which drops the CONNECTION — the batch is lost with it and
            # the sender learns through the conn failure + RPC retry,
            # the same resolution RDMA reaches when rbufs can't be
            # posted and the QP tears down
            for f in footprints:
                self._pool.release(f)
            raise
        for arr, f in zip(out, footprints):
            self._pool.attach_finalizer(arr, f)
        with self._pump_lock:
            self._consumed += 1
        self._maybe_send_ack()
        self._arm_idle_ack()
        return out

    # --------------------------------------------------------- plumbing
    def close(self) -> None:
        with self._lock:
            # under _lock: _enqueue checks the flag under the same
            # hold, so no batch can slip into _outq after the queued-
            # tracker sweep below has run
            if self._closed:
                return
            self._closed = True
        d = peek_dispatcher()
        if d is not None:
            d.drop_quiet_duty(self)
        # best-effort flush: Socket's keep_write reported success for
        # frames that may still sit in _outq/_wirebuf behind a window
        # gate or TCP backpressure — don't silently drop them on close
        try:
            self._flush()
        except Exception:
            pass
        self._inner.close()
        # reclaim sender-side lane registrations. Same-process entries
        # go on a GRACE timer rather than being popped now: the flush
        # above may have just delivered their descriptors, and the peer
        # taking one after an instant pop would see a phantom error.
        # Cross-process await_pull registrations have no cancel API, so
        # the un-ACKed pull-registered batches are counted (an upper
        # bound: pulled-but-unacked ones are included) at
        # /vars ici_unpulled_registrations instead of pinning silently.
        grace = _reclaim_grace_s()
        deadline = time.monotonic() + grace
        queued = False
        grace_bytes = 0
        with _local_lock:
            for uid in self._issued_uids:
                arrays = _local_exchange.get(uid)
                if arrays is not None:
                    nb = sum(getattr(a, "nbytes", 0) or 0
                             for a in arrays)
                    _reclaim_queue.append((deadline, uid))
                    _grace_uid_bytes[uid] = nb
                    grace_bytes += nb
                    queued = True
        self._issued_uids.clear()
        if grace_bytes:
            # pinned until the grace sweep: counted leaked now, counted
            # reclaimed when the sweep drops them — the /device pane's
            # pinned estimate is the difference
            _leaked_bytes_counter.add(grace_bytes)
        if queued:
            # a timer guarantees the sweep even if no further lane
            # activity ever happens in this process (otherwise the
            # queued entries would pin device arrays until exit)
            try:
                from brpc_tpu.fiber.timer import global_timer
                global_timer().schedule_after(grace + 0.5,
                                              _sweep_reclaim)
            except Exception:
                pass
        # lane batches still QUEUED (window-gated, or stuck behind a
        # poisoned head) never reached _stage_lane_frame: no footprint
        # rides them, so the in-flight sweep below cannot see them —
        # their trackers settle here or the cell never balances and the
        # device span is stranded unsubmitted (collect under the lock,
        # settle after)
        with self._lock:
            queued_trackers = [item[2] for item in self._outq
                               if item[0] == "lane" and len(item) > 2
                               and item[2] is not None]
        for tracker in queued_trackers:
            tracker.lane_failed("connection closed before the batch "
                                "was flushed")
        with self._fc_lock:
            # every entry still in the deque is un-ACKed; only PULL-lane
            # batches pin peer-side registrations (staged/local bytes
            # attributed here would falsely trip the breaker)
            unacked = list(self._inflight_footprints)
            outstanding = sum(1 for _, p, _t in unacked if p)
            leaked_bytes = sum(fp for fp, p, _t in unacked if p)
        # un-ACKed batches' stage trackers settle as failures — a pull
        # registration the peer never drained is a LEAK and its device
        # span says so (leak-reclaim annotation + failed cell counter)
        peer_epoch = (self.peer_info or {}).get("proc")
        cross_proc = peer_epoch != _PROC_UUID
        for fp, is_pull, tracker in unacked:
            if tracker is not None:
                tracker.lane_failed(
                    "connection closed with batch un-ACKed"
                    + (" (pull registration pinned — no cancel API)"
                       if is_pull and cross_proc else ""),
                    leaked=is_pull and cross_proc)
        if outstanding > 0 and cross_proc:
            _unpulled_registrations.add(outstanding)
            _unpulled_bytes.add(leaked_bytes)
            _leaked_bytes_counter.add(leaked_bytes)
            with _local_lock:   # closes race from two threads' +=
                _note_leaked(peer_epoch, leaked_bytes)
        _sweep_reclaim()
        # drop any inbound descriptors never taken (their uids live in
        # the PEER's registry; our pool never reserved for them)
        with self._pump_lock:
            self._lane.clear()

    def start_events(self, on_readable: Callable[[], None],
                     on_writable: Callable[[], None]) -> None:
        self._on_writable_cb = on_writable

        def writable():
            if self._flush():
                on_writable()

        self._inner.start_events(on_readable, writable)

    def request_writable_event(self) -> None:
        # the stall may be TCP backpressure OR window credit; arm both
        # wake sources (whichever clears first fires on_writable once)
        self._want_writable = True
        self._inner.request_writable_event()

    def peek_closed(self) -> bool:
        """True only when the peer's FIN has arrived, the kernel holds
        no byte more, and nothing this conn has read is still to be
        delivered: frames pumped into _inbuf, _appbuf or _lane keep the
        connection alive until a drain has handed them on."""
        if not self._inner.peek_closed():
            return False
        # after the FIN nothing more can arrive; a pump in flight ends,
        # and what it read shows under its lock
        with self._pump_lock:
            return not (self._inbuf or self._appbuf or self._lane)

    def awaits_peer_frame(self) -> bool:
        """True while queued output waits for a frame of the peer's
        (hello, ACK, grant) or for TCP to take more. Read interest has
        to stay on then: the Socket leaves no sticky pause behind, or
        a bare ACK would wait in the kernel with nobody to read it."""
        return self._want_writable

    @property
    def local_endpoint(self):
        return self._local

    @property
    def remote_endpoint(self):
        return self._remote

    # introspection for /connections and tests
    @property
    def lane_kind(self) -> str:
        info = self.peer_info or {}
        if info.get("proc") == _PROC_UUID:
            return "local-d2d"
        if info.get("can_pull") and _get_transfer_server() is not None:
            return "pjrt-pull"
        return "staged"

    @property
    def outstanding_batches(self) -> int:
        with self._fc_lock:
            return self._sent - self._peer_acked

    def lane_introspection(self) -> dict:
        """One /device conn row: credit-window occupancy, queue depths,
        buffered bytes — the live lane state next to the cells."""
        info = self.peer_info or {}
        window = int(info.get("window") or self._window)
        with self._fc_lock:
            outstanding = self._sent - self._peer_acked
            inflight_bytes = self._inflight_bytes
            sent = self._sent
            coalesced_frames = self._coalesced_frames
            coalesced_batches = self._coalesced_batches
        with self._lock:
            outq_depth = len(self._outq)
            out_bytes = self._out_bytes
        effective = self._effective_window(info) if info else window
        buffered = len(self._wirebuf) + len(self._inbuf) \
            + len(self._appbuf) + out_bytes
        return {
            "remote": str(self._remote),
            "lane_kind": self.lane_kind,
            "window": window,
            "effective_window": effective,
            "peer_grant": self._peer_grant,
            "outstanding_batches": outstanding,
            "window_occupancy": round(outstanding / effective, 3)
            if effective else 0.0,
            "inflight_bytes": inflight_bytes,
            "budget": int(info.get("budget") or 0),
            "batches_sent": sent,
            "coalesced_frames": coalesced_frames,
            "coalesced_batches": coalesced_batches,
            "idle_acks": self._idle_acks,
            "enqueue_depth": outq_depth,
            "buffered_bytes": buffered,
            "want_writable": self._want_writable,
            "poisoned": self._poisoned,
            "closed": self._closed,
        }


class _IciListener(Listener):
    def __init__(self, inner: Listener, ep: EndPoint):
        self._inner = inner
        self._ep = ep

    def stop(self) -> None:
        self._inner.stop()

    @property
    def endpoint(self) -> EndPoint:
        return self._ep


class IciTransport(Transport):
    scheme = "ici"

    def __init__(self, window: int = DEFAULT_WINDOW,
                 pool: Optional[DeviceRecvPool] = None):
        self._tcp = TcpTransport()
        self._window = window
        self._pool = pool

    def listen(self, ep: EndPoint, on_new_conn) -> Listener:
        # warm the transfer server HERE (caller thread): accepted conns are
        # constructed on the event-dispatcher thread, and a lazy multi-
        # second PjRt bring-up there would stall every socket in the process
        _get_transfer_server()
        ordinal = ep.device or 0
        local_device(ordinal, f"{ep} #device")     # out of range: refuse
        tcp_ep = EndPoint("tcp", ep.host or "127.0.0.1", ep.port, ep.extras)
        ready = threading.Event()

        def wrap(conn: TcpConn):
            if not ready.wait(5):
                # listener bring-up stalled: fail the accepted conn
                # cleanly instead of NameError-ing on `bound` below
                conn.close()
                raise ConnectionError("ici: listener endpoint not bound "
                                      "within 5s; dropping accepted conn")
            on_new_conn(IciConn(conn, bound, conn.remote_endpoint,
                                recv_device_ordinal=ordinal,
                                window=self._window, pool=self._pool))

        inner = self._tcp.listen(tcp_ep, wrap)
        bound = EndPoint("ici", inner.endpoint.host, inner.endpoint.port,
                         ep.extras)
        ready.set()
        return _IciListener(inner, bound)

    def connect(self, ep: EndPoint) -> Conn:
        reply = ep.reply_device or 0
        local_device(reply, f"{ep} #reply_device")  # out of range: refuse
        tcp_ep = EndPoint("tcp", ep.host, ep.port, ep.extras)
        inner = self._tcp.connect(tcp_ep)
        return IciConn(inner, inner.local_endpoint, ep,
                       recv_device_ordinal=reply,
                       window=self._window, pool=self._pool)
