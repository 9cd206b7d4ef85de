"""Socket: THE connection object (brpc/socket.h, SURVEY.md §2.4).

Keeps the reference's load-bearing properties, re-expressed for the fiber
runtime:

- **Versioned refs**: sockets live in a global ResourcePool; a SocketId
  goes stale atomically on SetFailed (socket.cpp:776-800's _versioned_ref
  race-freedom between address() and SetFailed()).
- **Serialized wait-free-ish writes**: producers append to an MPSC queue
  and return; a single KeepWrite fiber drains it (socket.cpp:1924-2160's
  _write_head exchange + KeepWrite bthread). On EAGAIN it parks on a
  butex armed by the transport's one-shot writable event.
- **Edge-triggered input**: readiness events bump an atomic counter; only
  the 0->1 transition spawns the processing fiber (StartInputEvent's
  _nevent dance, socket.cpp:2527), which drains input until EAGAIN.
- **Device payload lane**: device arrays ride next to the byte stream on
  transports that support it (the HBM zero-copy slot where the reference
  has RDMA SGEs).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import deque
from typing import Callable, Optional

from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.butil.flags import define_flag, flag
from brpc_tpu.butil.iobuf import (DEFAULT_BLOCK_SIZE, IOBuf, IOPortal,
                                  _BIG_BLOCK_SIZE)
from brpc_tpu.butil.resource_pool import INVALID_ID, ResourcePool, VersionedId
from brpc_tpu.bvar.reducer import Adder, Maxer, PassiveStatus
from brpc_tpu.fiber import TaskControl, global_control
from brpc_tpu.fiber.butex import Butex
from brpc_tpu.transport.base import Conn, get_transport
from brpc_tpu.transport import device_stats as _device_stats
from brpc_tpu.transport import event_dispatcher as _event_dispatcher
from brpc_tpu.transport import syscall_stats as _syscall_stats

define_flag("socket_inline_process", True,
            "process socket input inline on the event-raising thread "
            "until the handler first suspends (process-in-place, "
            "input_messenger.cpp:183); handlers that await park as "
            "normal fibers. Off = always spawn a fiber per busy period")

# Writes at/above this size claim writership through a keep_write fiber
# instead of sending inline from the submitting context: the kernel
# copy of a large frame (a sendmsg releases the GIL for its whole
# duration) then overlaps with whatever the submitter does next — on
# the event thread that means the NEXT frame's recv runs concurrently
# with this frame's send, which is the difference between one thread
# and two threads carrying the 1MB echo pipeline. 0 disables (single-
# core hosts: there is nothing to overlap with, and the fiber wake is
# pure cost). Applies only to fd transports (kernel-copy writes).
define_flag("socket_async_write_min",
            131072 if (os.cpu_count() or 1) > 1 else 0,
            "min frame bytes routed to a keep_write fiber instead of "
            "the inline send (0 = always inline); fd transports only")

# gather-write coalescing bounds: adjacent queued frames merge into one
# writev/sendmsg batch up to these caps (the iovec cap keeps a batch
# under IOV_MAX with headroom; the byte cap bounds how much one syscall
# pins while the queue drains)
_COALESCE_MAX_FRAMES = 32
_COALESCE_MAX_BYTES = 1 << 20


def _composite_cb(pending_cbs):
    """One done-callback firing a batch's unfired per-frame callbacks —
    the parked-remainder composite _write_coalesced hands to
    _park_handoff. None when there is nothing to fire."""
    if not pending_cbs:
        return None

    def comp(err, _cbs=pending_cbs):
        for c in _cbs:
            try:
                c(err)
            except Exception:
                pass
    return comp


def _close_pinned(cell) -> None:
    """Finalizer for a socket's pinned-fd cell (belt and braces: the
    normal close runs at set_failed once no native loop holds it)."""
    fd, cell[0] = cell[0], -1
    if fd is not None and fd >= 0:
        try:
            os.close(fd)
        except OSError:
            pass


class _PyMpsc:
    """Fallback for fastcore's Mpsc (queues.cc writer-retire MPSC) with
    the same contract: push() returns True when the caller became the
    writer; the writer drains FIFO and releases via try_retire(), which
    refuses while items remain (socket.cpp StartWrite/IsWriteComplete)."""

    __slots__ = ("_q", "_lock", "_writing")

    def __init__(self):
        self._q = deque()
        self._lock = threading.Lock()
        self._writing = False

    def push(self, item) -> bool:
        with self._lock:
            self._q.append(item)
            if self._writing:
                return False
            self._writing = True
            return True

    def drain_one(self):
        with self._lock:
            return self._q.popleft() if self._q else None

    def try_retire(self) -> bool:
        with self._lock:
            if self._q:
                return False
            self._writing = False
            return True

    def depth(self) -> int:
        return len(self._q)




# socket versioned-ref pool (socket.cpp:776-800): native respool.cc
# slots when available. Resolved on first use — fastcore.get() may
# compile the extension, and import must stay cheap.
_socket_pool = None
_socket_pool_lock = threading.Lock()


def _pool():
    p = _socket_pool
    if p is None:
        p = _make_pool()
    return p


def _make_pool():
    # locked: concurrent first-socket threads must agree on ONE pool
    # (a Socket registered in a discarded duplicate would be
    # unaddressable and set_failed would remove from the wrong pool)
    global _socket_pool
    with _socket_pool_lock:
        if _socket_pool is None:
            from brpc_tpu.native import fastcore as _fastcore
            fc = _fastcore.get()
            _socket_pool = fc.Pool(1 << 16) if fc is not None \
                else ResourcePool()
        return _socket_pool


def _new_mpsc():
    from brpc_tpu.native import fastcore as _fastcore
    fc = _fastcore.get()
    return fc.Mpsc() if fc is not None else _PyMpsc()


# fastcore module for the per-call fd loops (pluck_scan); resolved on
# first use for the same import-cost reason as the pools above
_fc_mod = False


def _fastcore():
    global _fc_mod
    if _fc_mod is False:
        from brpc_tpu.native import fastcore as _fastcore_loader
        _fc_mod = _fastcore_loader.get()
    return _fc_mod

# socket-level traffic + fast-lane health, visible at /vars (the
# reference self-instruments every subsystem the same way)
nwrites = Adder().expose("socket_writes")
# how each claim of writership sent: in place, in the context whose
# push claimed it, or through a keep_write fiber spawned for it (one
# scheduling hop before the conn sees the frame). Writes that queued
# behind an active writer are in neither. One Adder pair per conn
# family (tcp, ici, mem, ...: the conn class), made when the family's
# first socket is; /sockets shows each socket's own pair and
# syscall_stats.snapshot() carries the totals.
_write_mode: dict = {}       # family -> (in place, fiber spawns)
_write_mode_lock = threading.Lock()


def _write_mode_pair(family: str):
    pair = _write_mode.get(family)
    if pair is None:
        with _write_mode_lock:
            pair = _write_mode.get(family)
            if pair is None:
                pair = (Adder(), Adder())
                _write_mode[family] = pair
                _expose_write_mode(family, pair)
    return pair


def _expose_write_mode(family: str, pair) -> None:
    pair[0].expose(f"socket_write_inplace_{family}")
    pair[1].expose(f"socket_write_fiber_spawns_{family}")


def write_mode_totals():
    """(in-place claims, keep_write fiber spawns) over every family."""
    pairs = list(_write_mode.values())
    return (sum(p[0].get_value() or 0 for p in pairs),
            sum(p[1].get_value() or 0 for p in pairs))
nreads = Adder().expose("socket_read_bytes")
npluck_fast = Adder().expose("pluck_fast_responses")   # native-loop wins
npluck_defer = Adder().expose("pluck_defers")          # classic fallbacks
# write-queue saturation: bytes accepted by write() but not yet handed
# to the conn, across all sockets (a live gauge: +size at enqueue,
# -size at dequeue) — sustained growth means peers or the network can't
# absorb the response rate, which an rpcz timeline shows as write_us.
# The windowed peak catches bursts a point sample between drains misses.
nwqueue_bytes = Adder().expose("socket_wqueue_bytes")
_wqueue_peak = Maxer()
# frames that left in a merged gather-write batch beyond the first —
# each one is a send/sendmsg syscall the coalescer removed
ncoalesced = Adder().expose("socket_write_coalesced_frames")

# ---------------------------------------------------------------- census
# Every live Socket, regardless of owner (server conns, client channel
# sockets): the resource census measures per-connection cost across the
# whole process, not just one server's accept list. WeakSet so the
# registry itself can never pin a connection's memory. The lock
# serializes ADDs against census snapshots (a concurrent add during
# iteration raises "Set changed size"; GC-driven removals are already
# deferred by WeakSet's own _IterationGuard).
_live_sockets: "weakref.WeakSet" = weakref.WeakSet()
_live_sockets_lock = threading.Lock()

define_flag("census_idle_s", 10.0,
            "a connection with no read/write activity for this long "
            "counts as idle on /census, /connections and the "
            "idle_conn_count bvar")


_rows_memo = (0.0, [])     # (expires_monotonic, rows) — GIL-atomic swap


def socket_census_rows(max_age_s: float = 0.2):
    """One pass over every live, non-failed socket: (socket, resident
    bytes, idle seconds). THE shared accounting authority — the /census
    subsystem totals, the /connections per-conn rows and the idle/avg
    bvars all derive from this, so they cannot disagree on what a
    connection 'costs'. Resident bytes = parser-buffered input + queued
    unsent output (the two elastic per-conn buffers; fixed object
    overhead is what bytes_per_idle_conn measures via RSS).

    Memoized for ``max_age_s`` (0 forces fresh): one /vars scrape
    evaluates BOTH census gauges and a shard dump adds the census
    provider — without the memo that is three full walks over every
    live connection per scrape, which matters at the 100k-conn
    target."""
    global _rows_memo
    now_mono = time.monotonic()
    expires, rows = _rows_memo
    if max_age_s > 0 and now_mono < expires:
        return rows
    now = time.monotonic_ns()
    with _live_sockets_lock:
        socks = list(_live_sockets)
    rows = []
    for s in socks:
        if s is None or s.failed:
            continue
        rows.append((s, s.input_portal.size + s.wq_bytes,
                     (now - s.last_active_ns) / 1e9))
    _rows_memo = (now_mono + 0.2, rows)
    return rows


def _socket_census() -> dict:
    """Process-wide socket census, with the server-side subset broken
    out: ``bytes``/``count`` cover EVERY live socket (client channels
    included — they cost memory too), while ``server_bytes``/
    ``server_count`` cover only accepted server connections, the set
    /connections lists (a server conn carries user_data['server'])."""
    rows = socket_census_rows()
    idle_after = flag("census_idle_s")
    srv = [(s, b, i) for s, b, i in rows
           if s.user_data.get("server") is not None]
    return {
        "bytes": sum(b for _, b, _ in rows),
        "count": len(rows),
        "idle": sum(1 for _, _, i in rows if i >= idle_after),
        "server_bytes": sum(b for _, b, _ in srv),
        "server_count": len(srv),
    }


def idle_conn_count() -> int:
    idle_after = flag("census_idle_s")
    return sum(1 for _, _, i in socket_census_rows() if i >= idle_after)


def conn_resident_bytes_avg() -> float:
    rows = socket_census_rows()
    if not rows:
        return 0.0
    return round(sum(b for _, b, _ in rows) / len(rows), 1)


def expose_conn_census_vars() -> None:
    """(Re-)expose the connection-cost bvars and the write-mode pairs —
    called at import and again from Server.start, surviving a test
    fixture's unexpose_all like the other socket counters."""
    _idle_var.expose("idle_conn_count")
    _avg_var.expose("conn_resident_bytes_avg")
    for family, pair in list(_write_mode.items()):
        _expose_write_mode(family, pair)


_idle_var = PassiveStatus(idle_conn_count)
_avg_var = PassiveStatus(conn_resident_bytes_avg)
expose_conn_census_vars()



def _span_recording() -> bool:
    """rpc.span.recording, bound on first use (rpc imports this
    module)."""
    global _span_recording
    from brpc_tpu.rpc.span import recording
    _span_recording = recording
    return recording()


from brpc_tpu.butil import resource_census as _resource_census  # noqa: E402
#   (census registration ships with the socket registry it measures)

_resource_census.register("sockets", _socket_census)


def _wqueue_peak_window():
    """Windowed high-water mark of any single socket's queued bytes,
    created lazily (a Window starts the background sampler thread).
    Locked double-check: a losing racer's Window would stay registered
    with the sampler and drain the delta-mode Maxer via reset() each
    tick, zeroing the kept window's samples."""
    global _wq_peak_win
    if _wq_peak_win is None:
        with _wq_peak_win_lock:
            if _wq_peak_win is None:
                from brpc_tpu.bvar.window import Window
                _wq_peak_win = Window(_wqueue_peak, 10)
    return _wq_peak_win


_wq_peak_win = None
_wq_peak_win_lock = threading.Lock()


def _postfork_reset() -> None:
    """Fork hygiene: the versioned-ref socket pool addresses PARENT
    sockets (their fds are mere dup'd copies here, their event
    registrations live in the parent's dispatcher), and the peak
    window rides the parent's sampler. Fresh child, fresh pool."""
    global _socket_pool, _socket_pool_lock, _wq_peak_win
    global _live_sockets_lock, _rows_memo, _wq_peak_win_lock
    _socket_pool = None
    _socket_pool_lock = threading.Lock()
    _wq_peak_win = None
    _wq_peak_win_lock = threading.Lock()
    _rows_memo = (0.0, [])    # memoized rows describe parent sockets
    # census registry: the listed sockets are the PARENT's connections
    # (the child holds mere fd dups it will never serve), and the lock
    # may have been mid-hold at fork time
    _live_sockets_lock = threading.Lock()
    _live_sockets.clear()


from brpc_tpu.butil import postfork as _postfork  # noqa: E402
#   (registration ships with the singletons it resets)

_postfork.register("transport.socket", _postfork_reset)

# Installed by the RPC layer (brpc_tpu.rpc.channel): callable
# ``(socket, [controllers])`` that fails or re-issues the client calls
# still in flight on a socket that just failed — the transport layer
# defines the hook, the RPC layer provides the semantics (the
# reference's SetFailed -> bthread_id_error fan-out, socket.cpp).
inflight_failer = None


def pull_chunks(sock):
    """Shared front half of the chunk-handoff fast lanes (mem://): pull
    the writer's exact bytes objects off the conn, with the common
    eligibility/eof/accounting protocol in ONE place so the client and
    server lanes cannot diverge on it. Returns (data, handled):
    data=None means no scanning to do — handled tells the hook what to
    return (True: spurious wake or eof dealt with; False: not a chunk
    conn, and the hook was self-disabled)."""
    rc = sock.conn.read_chunks
    if rc is None:
        sock.fast_drain = None
        return None, False
    chunks, eof = rc()
    if eof:
        # the classic chunk drain's verdict (Socket._drain_readable)
        sock.set_failed(ConnectionResetError("peer closed"))
        return None, True
    if not chunks:
        return None, True
    data = chunks[0] if len(chunks) == 1 else b"".join(chunks)
    nreads.add(len(data))
    return data, False

SocketId = VersionedId


def address_socket(sid: SocketId) -> Optional["Socket"]:
    return _pool().address(sid)


class Socket:
    def __init__(self, conn: Conn, on_input: Optional[Callable] = None,
                 control: Optional[TaskControl] = None):
        """``on_input(socket)`` runs in a fiber when bytes arrive
        (InputMessenger.on_new_messages in the assembled stack)."""
        self.conn = conn
        self._control = control or global_control()
        self._on_input = on_input
        # sync twin of the input callback (InputMessenger's
        # on_new_messages_sync): lets the whole drain+parse+dispatch
        # cycle run without coroutine/fiber machinery when nothing
        # suspends — the client response path in particular
        self._on_input_sync = None
        if on_input is not None and \
                getattr(on_input, "__name__", "") == "on_new_messages":
            self._on_input_sync = getattr(
                getattr(on_input, "__self__", None),
                "on_new_messages_sync", None)
        self.input_portal = IOPortal()
        self.failed = False
        self.fail_reason: Optional[BaseException] = None
        # wait-free MPSC write queue with writer-retire arbitration
        # (native queues.cc via fastcore when available): items are
        # (bytes|IOBuf, done_cb|None); the producer whose push claims
        # writership drains — socket.cpp:1924-2005's _write_head protocol
        self._wq = _new_mpsc()
        # mid-frame leftover of a parked writer: (IOBuf, cb). INVARIANT:
        # non-None exactly while writership is parked awaiting a
        # writable event; consuming it (under _handoff_lock) IS taking
        # writership. Both the writable-event continuation and
        # set_failed's cleanup race for it — exactly one wins.
        self._handoff = None
        self._handoff_lock = threading.Lock()
        self._writable_butex = Butex(0)
        self._nevent = 0                          # edge-trigger input counter
        self._nevent_lock = threading.Lock()
        self._plucking = False       # a sync joiner owns input processing
        # dispatched requests whose response hasn't been written yet —
        # the cut-through gate: streaming a response in pieces is only
        # frame-safe while no other response can interleave
        self.pending_responses = 0
        self.pending_lock = threading.Lock()
        # client-side calls currently issued on this socket (balanced by
        # Controller._set_issue_socket) — the sync-pluck lazy-deadline
        # gate: with >1 in flight, another call's big response could
        # stall a plucker past its deadline, so those joiners keep the
        # real timer; _lazy_plucker is the controller currently plucking
        # WITH a lazy deadline, armed by a later issuer (both under
        # pending_lock)
        self.client_inflight = 0
        self.inflight_calls: set = set()   # their controllers (same lock)
        self._lazy_plucker = None
        self._busy_rearmed = False   # one probe re-arm per busy period
        self._busy_paused = False    # level-trigger: read interest paused
        # sticky pluck pause: after a sync-pluck settles with nothing in
        # flight, read interest STAYS paused (the next pluck_preclaim
        # consumes it for free — per-call epoll_ctl pair removed from
        # the sync-RPC path). Any non-pluck consumer of the socket
        # (async issue registration, a direct write, a stream binding)
        # must unstick first; _submit does. _sticky_since gates the
        # dead-peer probe (probe_unobserved) to genuinely idle reuse.
        self._pluck_sticky = False
        self._sticky_since = 0.0
        self._read_hint = 8192                    # adaptive read-block size
        self.preferred_protocol = -1              # InputMessenger cache
        # protocol hint: total portal bytes needed before the next parse
        # can succeed (a 1MB frame arrives in ~5 drain cycles; without
        # this each cycle re-probes header/meta just to learn "not yet")
        self.input_need = 0
        # server native drain hook (fastcore serve_drain): a callable
        # ``(socket) -> bool`` tried before the classic drain on the
        # sync input path; True = the pass was handled natively.
        # Installed by Server for eligible sockets, self-disabling.
        self.fast_drain: Optional[Callable] = None
        self.user_data: dict = {}                 # per-conn session state
        # last read-event/write stamp (monotonic ns): the idle-class
        # signal for /census, /connections and idle_conn_count — one
        # attr store per readable event / queued write
        self.last_active_ns = time.monotonic_ns()
        # bytes enqueued to _wq and not yet popped by a writer (owner
        # thread +=, writer -=; GIL-atomic enough for a gauge) — the
        # per-socket write-queue saturation signal (/sockets page)
        self.wq_bytes = 0
        # writership claims that sent in place / spawned a keep_write
        # fiber (the /sockets twins of socket_write_inplace and
        # socket_write_fiber_spawns)
        self.write_inplace = 0
        self.write_fiber_spawns = 0
        self.family = type(conn).__name__.removesuffix("Conn").lower()
        self._nwrite_inplace, self._nwrite_fiber = \
            _write_mode_pair(self.family)
        self._on_failed_cbs: list = []
        self._failed_cb_lock = threading.Lock()   # failed-flag/append race
        # captured once: /flags mutation applies to new sockets (a dict
        # lookup per readable event is measurable on the inline path)
        self._inline_process = flag("socket_inline_process")
        # what the conn says of itself, read once and turned into this
        # socket's switches (transport/base.py::Conn declares each
        # name, its default and what follows from it)
        self._inline_write = conn.inline_write_ok
        self._drain_all_reads = conn.drain_all_reads
        self._level_triggered = conn.level_triggered
        self._short_read_drained = self._level_triggered and \
            conn.short_read_drained
        self._writev = conn.writev
        self._conn_flush = conn.flush      # set: _write_gathered writes
        self._conn_awaits_peer = conn.awaits_peer_frame
        self._lane_tracked = conn.supports_device_tracker
        self._readv = conn.read_into_v
        self._read_chunks = conn.read_chunks
        # async big-write routing applies only to kernel-copy fd conns
        self._async_write_min = (flag("socket_async_write_min")
                                 if conn.stream_fd is not None else 0)
        # pinned-fd cache for the native fd loops (pluck_scan /
        # serve_drain): ONE dup per socket instead of one dup+close
        # per call/event. Refcounted so set_failed can close it the
        # moment no native loop holds it (a lingering dup would delay
        # the FIN a set_failed close is supposed to send).
        self._pin_lock = threading.Lock()
        self._pin_cell = [None]      # dup'd fd (None = not yet, -1 = closed)
        self._pin_refs = 0
        self._pin_closed = False
        weakref.finalize(self, _close_pinned, self._pin_cell)
        try:
            self.id: SocketId = _pool().insert(self)
        except RuntimeError:
            # bounded native pool (65536 live sockets): surface as a
            # connection error the RPC paths already handle — and close
            # the conn NOW (start_events never runs, so nothing else
            # will), or every rejected connect leaks an fd exactly when
            # the process is resource-exhausted
            try:
                conn.close()
            except Exception:
                pass
            raise ConnectionError("socket pool exhausted") from None
        with _live_sockets_lock:         # resource-census registry
            _live_sockets.add(self)
        conn.start_events(self._on_readable_event, self._on_writable_event)

    # ---------------------------------------------------------- pinned fd
    def pin_fd_acquire(self) -> int:
        """Acquire the cached dup of the conn's fd for a native loop
        (pluck_scan / serve_drain). The dup pins the kernel socket: a
        concurrent set_failed closes the conn's own fd while the C
        loop sits in poll/recv with the GIL released, and the OS could
        hand that fd NUMBER to a brand-new connection whose bytes the
        loop would then consume. Returns -1 when unavailable (no fd
        conn, already closed, dup failed). MUST be balanced by
        pin_fd_release()."""
        with self._pin_lock:
            if self._pin_closed:
                return -1
            fd = self._pin_cell[0]
            if fd is None:
                # stream_fd, not pluck_fd: a native loop reads the fd's
                # bytes as the application's (on ici:// they are lane
                # frames, and the joiner's poll is all the fd is for)
                pfd = self.conn.stream_fd
                if pfd is None:
                    return -1
                try:
                    fd = os.dup(pfd())
                except OSError:
                    return -1
                self._pin_cell[0] = fd
            self._pin_refs += 1
            return fd

    def pin_fd_release(self) -> None:
        with self._pin_lock:
            self._pin_refs -= 1
            if (self._pin_refs == 0 and self._pin_closed
                    and self._pin_cell[0] is not None
                    and self._pin_cell[0] >= 0):
                fd, self._pin_cell[0] = self._pin_cell[0], -1
                try:
                    os.close(fd)
                except OSError:
                    pass

    def _pin_fd_shutdown(self) -> None:
        """set_failed's half: close the pinned dup as soon as no native
        loop holds it (the loop in flight sees EOF/reset through its
        still-open dup and releases; the LAST releaser closes)."""
        with self._pin_lock:
            self._pin_closed = True
            if (self._pin_refs == 0 and self._pin_cell[0] is not None
                    and self._pin_cell[0] >= 0):
                fd, self._pin_cell[0] = self._pin_cell[0], -1
                try:
                    os.close(fd)
                except OSError:
                    pass

    # ----------------------------------------------------------- identity
    @property
    def remote_endpoint(self) -> Optional[EndPoint]:
        return self.conn.remote_endpoint

    @property
    def local_endpoint(self) -> Optional[EndPoint]:
        return self.conn.local_endpoint

    # -------------------------------------------------------------- write
    def write(self, data, on_done: Optional[Callable] = None,
              device_arrays=None, span=None) -> bool:
        """Enqueue an IOBuf or a ready-made bytes frame and return
        immediately; ordering is FIFO per socket. Bytes frames skip the
        IOBuf machinery unless the conn blocks mid-frame (the reference's
        write-once-in-place, socket.cpp:1960). On an already-failed
        socket the done callback still fires (with the failure) so
        callers' retry paths run — never a silent drop.

        ``device_arrays``: the out-of-band device batch this frame is
        the envelope of (device-lane conns only; host transports
        serialize instead). The receiver matches lane batches to
        envelopes in FIFO order, so the pair enters the write queue as
        ONE item and the single writer hands batch, then frame, to the
        conn back to back: no second caller can come between them and
        no lock is held while either is sent. A batch the conn refuses
        fails ``on_done`` and its envelope is not sent. ``span``: the
        owning RPC span — with device telemetry on, the transfer gets
        a stage tracker (and, with rpcz, a child device span)."""
        return self._submit(data, on_done, device_arrays, span)

    # bytes and IOBufs share one path; the old two-name split survives as
    # an alias so fast-path call sites read as what they are
    write_small = write

    def _submit(self, data, on_done, arrays=None, span=None) -> bool:
        """One write path for bytes and IOBufs: push onto the MPSC queue;
        the producer whose push CLAIMS writership sends — inline in this
        context when the conn allows it (write-once-then-KeepWrite,
        socket.cpp:1924-2050), via a keep_write fiber otherwise. FIFO
        holds because the queue is the only ordering authority."""
        if self.failed:
            if on_done is not None:
                try:
                    on_done(self.fail_reason)
                except Exception:
                    pass
            return False
        if self._pluck_sticky and not self._plucking:
            # a non-pluck writer is using a sticky-paused socket: the
            # response/peer data needs live read events again
            self.unstick_reads()
        nwrites.add(1)
        # graftlint: disable=guarded-by -- last_active_ns is a stamp,
        # not a count: each store is whole, the last writer wins, and
        # its one reader (the idle reaper's age) tolerates either. A
        # lock would sit on every submit and every read event.
        self.last_active_ns = time.monotonic_ns()
        sz = data.size if isinstance(data, IOBuf) else len(data)
        # graftlint: disable=guarded-by -- wq_bytes is approximate
        # accounting beside the wait-free write queue: a lock here
        # would sit on every submit of every thread, and drift only
        # skews an observability gauge, never the queue itself.
        self.wq_bytes += sz
        nwqueue_bytes.add(sz)
        _wqueue_peak.update(self.wq_bytes)
        lane = None
        if arrays is not None:
            lane = (arrays, self._open_lane_tracker(arrays, span))
        if not self._wq.push((data, on_done, lane)):
            return True          # the active writer drains it in order
        m = self._async_write_min
        if self._inline_write and not (m and sz >= m):
            self.write_inplace += 1
            self._nwrite_inplace.add(1)
            return self._drain_writes_inline()
        self.write_fiber_spawns += 1
        self._nwrite_fiber.add(1)
        self._control.spawn(self._keep_write, name="keep_write")
        return True

    def _wq_acct_pop(self, item) -> None:
        """Settle the write-queue gauge for one popped item (called at
        drain_one sites only — a handoff continuation was already
        settled when the item first left the queue)."""
        data = item[0]
        sz = data.size if isinstance(data, IOBuf) else len(data)
        self.wq_bytes -= sz
        nwqueue_bytes.add(-sz)

    def _write_data_once(self, data):
        """Single pass over one item; returns (err, leftover_iobuf|None).
        BlockingIOError is absorbed into a leftover (never an error)."""
        try:
            if isinstance(data, IOBuf):
                self._cut_buf(data)
                return None, (data if data else None)
            # whole-frame send first: the common small frame leaves in
            # one syscall with no memoryview/loop machinery
            try:
                n = self.conn.write(data) or 0
            except BlockingIOError:
                # fully blocked: don't pay a second guaranteed-EAGAIN
                # send — park the whole frame
                buf = IOBuf()
                buf.append(bytes(data))
                return None, buf
            if n == len(data):
                return None, None
            mv = memoryview(data)[n:]
            while mv:
                try:
                    n = self.conn.write(mv)
                except BlockingIOError:
                    break
                if n is None or n <= 0:
                    break
                mv = mv[n:]
            if mv:
                buf = IOBuf()
                buf.append(bytes(mv))
                return None, buf
            return None, None
        except (BrokenPipeError, ConnectionError, OSError) as e:
            return e, None

    def _drain_writes_inline(self, first_item=None) -> bool:
        """Writer loop in the claiming context (push claim, a writable-
        event continuation, or set_failed's cleanup). On EAGAIN the
        partial frame parks in _handoff with writership attached and a
        one-shot writable event re-enters this loop ON THE DISPATCHER —
        no fiber, no worker wake per blocked write (the reference pays a
        bthread park/wake here, which is ~1us for it and ~50us for us)."""
        ok = True
        item = first_item
        while True:
            if item is None:
                item = self._wq.drain_one()
                if item is not None:
                    self._wq_acct_pop(item)
            if item is None:
                if self._wq.try_retire():
                    return ok
                continue          # a racing push landed: keep draining
            err: Optional[BaseException] = None
            if not self.failed and self._conn_flush is not None:
                # the conn frames its own queue: hand it every item
                # queued so far, flush once
                status = self._write_gathered(item)
                item = None
                if status == 0:
                    continue
                if status == 1:
                    return ok
                if status == 3:
                    return False
                ok = False
                continue
            data, cb, lane = item
            item = None
            if not self.failed and self._writev is not None:
                # gather-write coalescing: if more frames already queued
                # behind this one, merge the run into one bounded
                # writev batch — one syscall instead of one per frame
                # (fd conns only: they carry no device lane)
                nxt = self._wq.drain_one()
                if nxt is not None:
                    self._wq_acct_pop(nxt)
                    status = self._write_coalesced(data, cb, nxt)
                    if status == 0:
                        continue      # batch fully sent: keep draining
                    if status == 1:
                        return ok     # parked on the writable event
                    if status == 3:
                        return False  # queue claimed by a concurrent
                                      # set_failed: stop draining
                    ok = False        # batch failed (socket now failed)
                    continue
            if self.failed:
                err = self.fail_reason
                self._fail_lane(lane, err)
            else:
                refused = self._hand_lane(lane) if lane is not None \
                    else None
                if refused is not None:
                    # the batch never reached the conn: fail this call
                    # and keep its envelope home; the conn stays usable
                    if cb is not None:
                        try:
                            cb(refused)
                        except Exception:
                            pass
                    continue
                err, leftover = self._write_data_once(data)
                if err is None and leftover is not None:
                    # blocked mid-frame: park writership on the
                    # writable event
                    st = self._park_handoff(leftover, cb)
                    if st == 1:
                        return ok
                    if st == -1:
                        return False
                    ok = False
                    continue
            if err is not None:
                ok = False
                self.set_failed(err)
            if cb is not None:
                try:
                    cb(err)
                except Exception:
                    pass

    def _open_lane_tracker(self, arrays, span):
        """The device_stats stage tracker of one queued batch, opened
        in the submitting context (t_submit is the hand-over to the
        socket); None with device telemetry off."""
        _ds = _device_stats
        if not _ds.enabled():
            return None
        conn = self.conn
        lane = conn.lane_kind or \
            getattr(conn.remote_endpoint, "scheme", "device")
        # (lane, peer, cell) cached on the socket — the PR 7
        # cells-cached-per-channel discipline; lane_kind can change
        # once the hello lands, so the cache keys on it
        cached = self.__dict__.get("_dev_send")
        if cached is None or cached[0] != lane:
            peer = _ds.peer_key(conn.remote_endpoint)
            cached = (lane, peer,
                      _ds.global_device_stats().device_cell(peer, lane))
            self._dev_send = cached
        nbytes = sum(getattr(a, "nbytes", 0) or 0 for a in arrays)
        return _ds.open_transfer(cached[1], lane, nbytes,
                                 parent_span=span, cell=cached[2])

    @staticmethod
    def _fail_lane(lane, err) -> None:
        """Settle the tracker of a queued batch that will not be sent
        (the settle latch makes a second report harmless)."""
        if lane is not None and lane[1] is not None:
            lane[1].lane_failed(f"{type(err).__name__}: {err}")

    def _hand_lane(self, lane, flush: bool = True):
        """Writer-side half of a paired write: hand the queued device
        batch to the conn, just before its envelope. Returns the
        refusal (the tracker is settled) or None. With ``flush`` False
        (gathering conns) a full out-buffer raises BlockingIOError and
        leaves the tracker open: the pair parks and is handed again."""
        arrays, tracker = lane
        try:
            if self._lane_tracked:
                # the conn's flush/ack legs stamp the tracker
                self.conn.write_device_payload(arrays, tracker=tracker,
                                               flush=flush)
                return None
            self.conn.write_device_payload(arrays)
        except BlockingIOError as e:
            if not flush:
                raise
            self._fail_lane(lane, e)
            return e
        except Exception as e:
            # the conn settles the refusals it detects (poison,
            # unsendable); a raise before those checks (device_put OOM,
            # bad dtype) must not strand an opened cell record
            self._fail_lane(lane, e)
            return e
        if tracker is not None:
            # loopback/staged conns deliver synchronously: the whole
            # timeline collapses into one settle (stage≈call, ack≈0)
            tracker.lane_encoded()
            tracker.lane_flushed()
            tracker.lane_acked()
        return None

    def _write_gathered(self, item) -> int:
        """Writer for a conn that queues what it is given and flushes
        on request (ici://): ``item`` and every item queued behind it,
        up to the coalescing caps, go to the conn unflushed — a device
        batch, then its envelope, pair after pair in queue order — and
        ONE flush carries the lot. A pair costs one TCP write, pairs of
        concurrent callers share it, and adjacent small batches reach
        the conn's own coalescer together. Callbacks fire after that
        flush, in this context with no lock held: write_done_us /
        flushed_us stamp bytes the conn has handed to TCP (or, behind a
        full TCP buffer or a closed window, holds to send on its own
        wake). A full out-buffer parks the rest — an unhanded batch
        with its envelope — through _park_handoff.

        Returns 0 = all handed and flushed (keep draining), 1 = parked
        on the writable event, 2 = failed (socket now failed, every
        callback fired), 3 = failed AND a concurrent set_failed claimed
        the queue (the caller must stop draining)."""
        conn = self.conn

        def write_unflushed(mv):
            return conn.write(mv, flush=False)

        done = []                    # (cb, refusal) in queue order
        parked = None
        fatal: Optional[BaseException] = None
        total = 0
        while True:
            data, cb, lane = item
            try:
                if lane is not None:
                    refused = self._hand_lane(lane, flush=False)
                    lane = None
                    if refused is not None:
                        done.append((cb, refused))
                        data = None
                if data is not None:
                    total += data.size if isinstance(data, IOBuf) \
                        else len(data)
                    if isinstance(data, IOBuf):
                        data.cut_into_writer(write_unflushed)
                        if data:
                            raise BlockingIOError
                    else:
                        write_unflushed(data)
                    done.append((cb, None))
            except BlockingIOError:
                if not isinstance(data, IOBuf):
                    buf = IOBuf()
                    buf.append(bytes(data))
                    data = buf
                parked = (data, cb, lane)
                break
            except (BrokenPipeError, ConnectionError, OSError) as e:
                fatal = e
                done.append((cb, None))
                break
            if total >= _COALESCE_MAX_BYTES or \
                    len(done) >= _COALESCE_MAX_FRAMES:
                break
            item = self._wq.drain_one()
            if item is None:
                break
            self._wq_acct_pop(item)
        if len(done) > 1:
            ncoalesced.add(len(done) - 1)
        if fatal is None:
            try:
                self._conn_flush()
            except (BrokenPipeError, ConnectionError, OSError) as e:
                fatal = e
        if fatal is not None:
            self.set_failed(fatal)
        for cb, refused in done:
            if cb is not None:
                try:
                    cb(refused or fatal)
                except Exception:
                    pass
        if parked is None:
            return 0 if fatal is None else 2
        if fatal is not None:
            self._fail_lane(parked[2], fatal)
            if parked[1] is not None:
                try:
                    parked[1](fatal)
                except Exception:
                    pass
            return 2
        st = self._park_handoff(*parked)
        if st == 1:
            return 1
        return 3 if st == -1 else 2

    def _take_handoff(self):
        with self._handoff_lock:
            item, self._handoff = self._handoff, None
            if item is not None:
                # every taker disposes of the item immediately (resumes
                # the write or fails its callback): settle the gauge
                sz = item[0].size
                self.wq_bytes -= sz
                nwqueue_bytes.add(-sz)
        return item

    def _park_handoff(self, leftover, comp, lane=None) -> int:
        """Park a blocked write remainder on the writable event (the
        continuation takes it via _take_handoff) — the ONE copy of the
        park protocol the single-frame, coalesced and gathered write
        paths all share; ``lane`` is a parked envelope's device batch the
        conn has not taken yet. The parked bytes re-enter the queue gauge: a
        stalled peer holding megabytes mid-frame is exactly what
        socket_wqueue_bytes exists to show (_take_handoff settles it
        when the park resolves).

        Returns 1 = parked; 0 = requesting the event failed (socket
        now failed, ``comp`` fired with the reason, writership still
        this context's — keep fail-draining); -1 = it failed AND a
        concurrent set_failed already claimed the handoff and
        writership (this context must NOT touch the queue again:
        draining here too would put two consumers on it)."""
        lsz = leftover.size
        with self._handoff_lock:
            self._handoff = (leftover, comp, lane)
            self.wq_bytes += lsz
            nwqueue_bytes.add(lsz)
        try:
            self.conn.request_writable_event()
            return 1
        except Exception as e:
            took = self._take_handoff()
            self.set_failed(e if isinstance(e, Exception)
                            else ConnectionError(str(e)))
            if took is None:
                return -1
            self._fail_lane(took[2], self.fail_reason)
            if took[1] is not None:
                try:
                    took[1](self.fail_reason)
                except Exception:
                    pass
            return 0

    def _write_coalesced(self, data, cb, nxt) -> int:
        """Send a run of queued frames as ONE gather-write batch:
        ``data``/``cb`` plus ``nxt`` plus whatever else sits in the
        queue, up to the coalescing caps. Per-frame callbacks fire as
        their bytes are fully accepted; a blocked batch parks its
        remainder (with the unfired callbacks composited) through the
        same handoff protocol as a single frame. Device-ref-bearing
        IOBufs keep their semantics: refs merge in FIFO frame order,
        so the lane-batch pairing (write_device_payload immediately
        before its wire frame) cannot interleave.

        Returns 0 = batch fully sent (keep draining), 1 = parked on
        the writable event (writership parked), 2 = failed (socket is
        now failed; every callback fired with the reason), 3 = failed
        AND a concurrent set_failed claimed the queue (the caller must
        stop draining — two consumers otherwise)."""
        agg = IOBuf()
        marks = []                    # (end_offset, cb) per frame
        total = 0

        def add(d, c):
            nonlocal total
            if isinstance(d, IOBuf):
                agg.append_buf(d)
                total += d.size
            elif len(d):
                agg.append_user_data(d)
                total += len(d)
            marks.append((total, c))

        add(data, cb)
        add(nxt[0], nxt[1])
        while total < _COALESCE_MAX_BYTES and len(marks) < _COALESCE_MAX_FRAMES:
            more = self._wq.drain_one()
            if more is None:
                break
            self._wq_acct_pop(more)
            add(more[0], more[1])
        ncoalesced.add(len(marks) - 1)
        try:
            self._cut_buf(agg)        # gather writev; absorbs EAGAIN
        except (BrokenPipeError, ConnectionError, OSError) as e:
            self.set_failed(e)
            for _, c in marks:
                if c is not None:
                    try:
                        c(e)
                    except Exception:
                        pass
            return 2
        sent = total - agg.size
        pending_cbs = []
        for end, c in marks:
            if end <= sent:
                if c is not None:
                    try:
                        c(None)
                    except Exception:
                        pass
            elif c is not None:
                pending_cbs.append(c)
        if not agg:
            return 0
        # blocked mid-batch: park the remainder with the unfired
        # callbacks composited into one done (same protocol as the
        # single-frame park in _drain_writes_inline)
        comp = _composite_cb(pending_cbs)
        st = self._park_handoff(agg, comp)
        if st == 1:
            return 1
        return 3 if st == -1 else 2

    def probe_unobserved(self) -> bool:
        """True when this socket is (now) failed. A sticky pluck pause
        leaves NOTHING watching the fd between sync calls, so a peer
        FIN lands unseen — callers about to REUSE a socket (channel
        single/pooled pick) probe here: one non-consuming MSG_PEEK
        (only when the socket is actually in the unobserved state)
        restores the dead-peer detection the dispatcher's read event
        used to provide, BEFORE a call is issued into the corpse."""
        if self.failed:
            return True
        if not self._pluck_sticky:
            return False          # reads armed: the dispatcher watches
        if time.monotonic() - self._sticky_since < 0.005:
            # back-to-back sync calls: skip the probe syscall — a peer
            # close in a <5ms window still surfaces through the pluck
            # read itself, this probe exists for IDLE reuse
            return False
        peek = self.conn.peek_closed
        if peek is not None:
            try:
                if peek():
                    self.set_failed(ConnectionResetError("peer closed"))
                    return True
            except Exception:
                pass
        return False

    def unstick_reads(self) -> None:
        """Re-arm read interest left sticky-paused by a settled pluck
        (see _pluck_sticky). Idempotent; never touches a socket whose
        pause is owned by a live plucker or busy period."""
        with self._nevent_lock:
            if not self._pluck_sticky:
                return
            self._pluck_sticky = False
            if self._busy_paused and not self._plucking:
                self._busy_paused = False
                if not self.failed:
                    try:
                        self.conn.resume_read_events()
                    except Exception:
                        pass

    def _cut_buf(self, buf: IOBuf) -> None:
        """Write as much of the chain as the conn accepts: gather-write
        (one sendmsg per iovec batch) when available and worthwhile,
        per-ref writes otherwise. BlockingIOError is absorbed, leaving
        the remainder in ``buf``."""
        if self._writev is not None and buf.backing_block_count > 1:
            buf.cut_into_gather_writer(self._writev)
        else:
            buf.cut_into_writer(self.conn.write)

    async def _write_buf_blocking(self, buf: IOBuf) -> Optional[BaseException]:
        while buf and not self.failed:
            try:
                self._cut_buf(buf)
            except (BrokenPipeError, ConnectionError, OSError) as e:
                return e
            if buf:
                # blocked: arm one-shot writable event, park on butex
                seq = self._writable_butex.value
                self.conn.request_writable_event()
                await self._writable_butex.wait(expected=seq, timeout_s=1.0)
        if buf and self.failed:
            return self.fail_reason   # failed mid-write: not a success
        return None

    async def _keep_write(self):
        """Background writer (owns writership until retire): finishes a
        handed-off partial frame, then drains the queue, parking on the
        writable butex when the conn blocks (KeepWrite bthread,
        socket.cpp:2066-2160). On failure every remaining item's callback
        fires with the reason — never a silent drop."""
        handoff = self._take_handoff()
        if handoff is not None:
            buf, cb = handoff[0], handoff[1]
            err = await self._write_buf_blocking(buf)
            if err is not None:
                self.set_failed(err)
            if cb is not None:
                try:
                    cb(err)
                except Exception:
                    pass
        while True:
            item = self._wq.drain_one()
            if item is None:
                if self._wq.try_retire():
                    return
                continue
            self._wq_acct_pop(item)
            data, cb, lane = item
            err: Optional[BaseException] = None
            if self.failed:
                err = self.fail_reason
                self._fail_lane(lane, err)
            elif lane is not None and \
                    (refused := self._hand_lane(lane)) is not None:
                # as in _drain_writes_inline: the call fails, its
                # envelope stays home, the conn stays usable
                if cb is not None:
                    try:
                        cb(refused)
                    except Exception:
                        pass
                continue
            else:
                if not isinstance(data, IOBuf):
                    b = IOBuf()
                    b.append(data)
                    data = b
                err = await self._write_buf_blocking(data)
                if err is not None:
                    self.set_failed(err)
            if cb is not None:
                try:
                    cb(err)
                except Exception:
                    pass

    def _on_writable_event(self):
        self._writable_butex.fetch_add(1)
        self._writable_butex.wake_all()
        if self._inline_write:
            item = self._take_handoff()
            if item is not None:
                # we now hold writership: resume the parked frame and
                # whatever queued behind it, right here
                self._drain_writes_inline(first_item=item)

    # -------------------------------------------------------------- input
    def _on_readable_event(self):
        """May fire from the dispatcher thread or a peer's fiber; only the
        0->1 transition starts a processing fiber."""
        self.last_active_ns = time.monotonic_ns()
        with self._nevent_lock:
            self._nevent += 1
            # a plucking joiner owns the input: events defer to it
            # exactly like a busy processing pass
            busy = self._nevent > 1 or self._plucking
        if not busy:
            if self._inline_process:
                if self._on_input_sync is not None:
                    # fully-sync fast path: no coroutine, no Fiber —
                    # escalates itself if a message's processing awaits
                    self._process_input_entry()
                else:
                    # zero-wake fast path: drain + parse + dispatch on
                    # THIS thread; suspension continues as a fiber
                    self._control.run_inline(self._process_input(),
                                             name="socket_input")
            else:
                self._control.spawn(self._process_input, name="socket_input")
            return
        # the input fiber is busy — possibly SUSPENDED awaiting a long
        # handler, in which case it cannot drain this event for a
        # while. A dead peer must still become visible NOW
        # (Controller::IsCanceled / NotifyOnCancel): cheap non-consuming
        # EOF probe from the dispatcher (the reference's event
        # dispatcher detects the hangup independently of message
        # processing for the same reason)
        peek = self.conn.peek_closed
        if peek is not None:
            try:
                if peek():
                    # NOT inline: set_failed runs user notify_on_cancel
                    # callbacks — a blocking one must not stall the
                    # process-wide dispatcher thread (the reference runs
                    # NotifyOnCancel in a fresh bthread)
                    self._control.spawn(
                        lambda: self.set_failed(
                            ConnectionResetError("peer closed")))
                elif self._level_triggered:
                    # data (not FIN) pending while the input context is
                    # busy: a LEVEL-triggered fd would re-fire this
                    # event in a hot loop — pause read interest for the
                    # rest of the busy period (the input loop re-drains
                    # via _nevent, and the busy-period end resumes).
                    # Flag AND fd state change under ONE _nevent_lock
                    # hold, and only while a processing pass is still
                    # owed (_nevent > 0): otherwise this pause could
                    # race the busy period ending and leave the fd
                    # deaf forever (no one left to resume). The
                    # matching resume in _finish_input_cycle also runs
                    # under the lock, so flag and fd state never
                    # disagree. This is the only read-interest syscall
                    # pair left on the TCP path; the idle/inline common
                    # case pays none
                    with self._nevent_lock:
                        if self._nevent > 0 and not self._busy_paused:
                            self._busy_paused = True
                            self.conn.pause_read_events()
                else:
                    # one-shot conns (ssl): this event consumed the read
                    # interest — re-arm so a later FIN during the same
                    # handler still produces an event. ONCE per busy
                    # period: unconditional re-arm with data pending
                    # would storm the dispatcher, and the input loop
                    # re-drains pending data anyway via _nevent. The
                    # flag is taken under the lock the busy period ends
                    # under, and only while it lasts: set after its end
                    # it would cost the NEXT period its re-arm
                    with self._nevent_lock:
                        rearm = not self._busy_rearmed
                        if rearm and self._nevent > 0:
                            self._busy_rearmed = True
                    resume = self.conn.resume_read_events
                    if rearm and resume is not None:
                        resume()
            except Exception:
                pass

    def _input_error(self, e: BaseException) -> None:
        # an escaping parse/process error must not wedge the socket (a
        # dead processing context would leave _nevent elevated and no
        # future event would restart it): drop the conn
        import logging
        logging.getLogger("brpc_tpu.transport").exception(
            "input processing failed; dropping connection")
        self.set_failed(e if isinstance(e, Exception)
                        else ConnectionError(str(e)))

    def _finish_input_cycle(self, pending: int) -> bool:
        """Settle one drain+dispatch cycle; True = more events arrived
        (caller loops)."""
        with self._nevent_lock:
            self._nevent -= pending
            if self._nevent > 0:
                return True
            self._busy_rearmed = False   # busy period over
            self._pluck_sticky = False   # a live busy period owns the
            #                              pause again: never leave the
            #                              flag claiming otherwise
            if self._busy_paused and not self._plucking:
                # paired with the pause in _on_readable_event: both run
                # under the lock so the paused flag always matches the
                # fd's read-interest state. While a plucker owns the fd
                # the pause STAYS (resuming here would reinstate the
                # per-message dispatcher wakes the claim-time pause
                # removed); the pluck exit path restores read interest.
                self._busy_paused = False
                if not self.failed:
                    try:
                        self.conn.resume_read_events()
                    except Exception:
                        pass
        return False

    def _pluck_process(self):
        """One drain+process pass in the pluck context. Returns True
        when the pass ESCALATED (a message's processing suspended — the
        cycle, including pending-event accounting, was handed back to
        the normal machinery and the caller must stop plucking)."""
        with self._nevent_lock:
            pending = self._nevent
        self._drain_readable()
        if self.input_portal or self.failed:
            r = None
            try:
                r = self._on_input_sync(self)
            except BaseException as e:
                self._input_error(e)
            if r is not None:
                # The extra _nevent keeps the busy invariant (>=1
                # through the handoff): with pending==0 a dispatcher
                # event in this window would otherwise start a
                # CONCURRENT processing pass against the same portal
                # as the escalated tail
                with self._nevent_lock:
                    self._nevent += 1
                    self._plucking = False
                self._control.run_inline(
                    self._input_async_tail(r, pending + 1),
                    name="socket_input")
                return True
        if pending:
            self._finish_input_cycle(pending)
        return False

    def pluck_preclaim(self) -> bool:
        """Claim the sync-pluck lane BEFORE the request is sent: pausing
        read interest pre-send closes the 1-core race where the kernel
        runs the server and then the dispatcher before the issuing
        thread resumes — the response would complete on the dispatcher
        (cross-thread wake, event-wait join) on roughly a coin flip.
        Returns True when claimed; the caller MUST hand the claim to
        pluck_until(preclaimed=True) or release via pluck_release()."""
        if self.conn.pluck_fd is None \
                or self._on_input_sync is None or self.failed:
            return False
        with self._nevent_lock:
            if self._nevent > 0 or self._plucking:
                return False
            self._plucking = True
            # a sticky pause from the previous settle is consumed here:
            # read interest is already off, so the claim pays NO
            # epoll_ctl (the steady sync-RPC state)
            self._pluck_sticky = False
            if self._level_triggered and not self._busy_paused:
                self._busy_paused = True
                try:
                    self.conn.pause_read_events()
                except Exception:
                    self._busy_paused = False
        return True

    def pluck_release(self) -> None:
        """THE pluck-claim settle protocol, shared by pluck_until's exit
        and every path that abandons a pluck_preclaim (retry moved the
        call to another socket, the joiner never arrived). Pause flag
        and fd read-interest change under the same lock as the claim,
        so they can never disagree; deferred events we didn't settle
        get one normal pass (its finish cycle restores read interest
        and balances the _nevent accounting)."""
        with self.pending_lock:
            # pending_lock FIRST (established order: pending -> nevent):
            # the sticky decision below reads client_inflight, and it
            # must serialize against _set_issue_socket registrations —
            # either the registration lands first (we see it and
            # resume) or we stick first (the issuer's write sees the
            # sticky flag and unsticks). No window hangs a response.
            with self._nevent_lock:
                if not self._plucking:
                    return
                self._plucking = False
                leftover = self._nevent > 0
                if self._busy_paused and not leftover:
                    awaits = self._conn_awaits_peer
                    if (not self.failed and self.client_inflight == 0
                            and not self.user_data.get("bound_streams")
                            and not (awaits is not None and awaits())):
                        # sticky pause: nothing in flight can produce
                        # input — leave reads off so the next sync call
                        # claims the lane for free (unstick_reads is
                        # every non-pluck consumer's entry)
                        self._pluck_sticky = True
                        self._sticky_since = time.monotonic()
                    else:
                        self._busy_paused = False
                        if not self.failed:
                            try:
                                self.conn.resume_read_events()
                            except Exception:
                                pass
        if leftover and not self.failed:
            self._process_input_entry()

    # graftlint: disable=judge-defer -- the defer exit here is
    # re-injection, not a return: frames the native loop can't judge are
    # appended back into input_portal and settled through the classic
    # machinery before pluck_until returns pred()
    def pluck_until(self, pred, deadline_s: float, fast=None,
                    preclaimed: bool = False) -> bool:
        """Sync-pluck lane: a joining (non-worker) thread adopts this
        socket's input processing until ``pred()`` or the deadline — the
        caller waiting for its response drives the connection itself,
        paying zero cross-thread wakes and no dispatcher round trip per
        message (the pthread analog of the reference's in-place bthread
        processing; gRPC core's completion-queue pluck is the same
        idea). Claims the socket only when no processing pass is in
        flight; for the duration, dispatcher events defer to the
        plucker (``_plucking`` reads as busy), and leftovers are
        settled through the normal machinery on exit. Returns pred().

        ``fast=(magic, cid, max_body, on_resp)`` arms the native receive
        loop
        (fastcore pluck_scan): poll+recv+frame-scan run in ONE C call
        per slice, and the sole expected response completes through
        ``on_resp(cid, ec, et, payload, att, sock)``. Anything only the
        classic path can judge (foreign frames, slow metas, pipelined
        tails) is re-injected into the portal and processed through the
        normal machinery — the lanes cannot diverge on semantics."""
        # ONE claim protocol (pluck_preclaim) and ONE settle protocol
        # (pluck_release) shared with the pre-send claim path — the
        # lock-sensitive pause/resume dance must not exist twice
        if not preclaimed and not self.pluck_preclaim():
            return pred()   # can't pluck / processing in flight
        pfd = self.conn.pluck_fd
        if pfd is None or self._on_input_sync is None:
            self.pluck_release()
            return pred()
        try:
            fd = pfd()
        except OSError:
            self.pluck_release()
            return pred()
        scan = None
        dup_fd = -1
        if fast is not None and not self.input_portal \
                and not self.input_need:
            fc = _fastcore()
            scan = getattr(fc, "pluck_scan", None) if fc is not None else None
            if scan is not None:
                # pinned fd: the refcounted cached dup (pin_fd_acquire)
                # pins the kernel socket for the loop's duration — same
                # fd-recycling protection as a per-call dup, without
                # the dup+close syscall pair on every sync RPC
                dup_fd = self.pin_fd_acquire()
                if dup_fd < 0:
                    scan = None
        poller = None
        escalated = False
        carry = b""
        try:
            while not pred() and not self.failed:
                remaining = deadline_s - time.monotonic()
                if remaining <= 0:
                    break
                # short slices: pred() can flip without fd traffic
                # (timeout timer, another thread completing the call)
                if scan is not None:
                    magic, cid, max_body, on_resp = fast
                    r = scan(dup_fd, magic, cid,
                             int(min(remaining, 0.2) * 1000) + 1,
                             max_body, carry)
                    tag = r[0]
                    nr = r[-1]            # bytes the C loop read this call
                    if nr:
                        nreads.add(nr)
                    if tag == 2:          # slice elapsed: keep the carry
                        carry = r[1]
                        continue
                    carry = b""
                    if tag == 0:          # the response for cid
                        npluck_fast.add(1)
                        # this completion bypasses record_dispatch_batch
                        # (the other denominator authority): count it
                        # here so syscalls_per_rpc stays honest on the
                        # sync-pluck lane
                        _syscall_stats.note_rpc_messages(1)
                        _, ec, et, payload, att, leftover, _nr = r
                        if leftover:
                            self.input_portal.append_user_data(leftover)
                        on_resp(cid, ec, et, payload, att, self)
                        if not self.input_portal:
                            continue      # pred() flips on the next check
                        # pipelined tail behind our response: classic
                        # machinery from here (retry may change cid)
                        scan = None
                        escalated = self._pluck_process()
                        if escalated:
                            break
                        continue
                    if tag == 1:          # defer: classic path judges
                        npluck_defer.add(1)
                        if r[1]:
                            self.input_portal.append_user_data(r[1])
                        scan = None
                        escalated = self._pluck_process()
                        if escalated:
                            break
                        continue
                    # tag == 3: EOF/socket error; complete frames that
                    # arrived before it still get processed, exactly as
                    # the classic drain would
                    scan = None
                    if r[2]:
                        self.input_portal.append_user_data(r[2])
                        escalated = self._pluck_process()
                        if escalated:
                            break
                    if not self.failed and not pred():
                        self.set_failed(ConnectionError(r[1]))
                    continue
                if poller is None:
                    import select
                    poller = self.__dict__.get("_pluck_poller")
                    if poller is None:
                        poller = self._pluck_poller = select.poll()
                        poller.register(
                            fd,
                            select.POLLIN | select.POLLHUP | select.POLLERR)
                if not poller.poll(min(remaining, 0.2) * 1000):
                    continue
                escalated = self._pluck_process()
                if escalated:
                    break
        finally:
            if dup_fd >= 0:
                self.pin_fd_release()
            if carry:
                # a partial frame read by the native loop: back into the
                # portal — more bytes must arrive for it to complete, and
                # their readable event restarts normal processing
                self.input_portal.append_user_data(carry)
            if not escalated:
                # the shared settle (escalation already handed the
                # claim + accounting to the normal machinery)
                self.pluck_release()
        return pred()

    def _process_input_entry(self) -> None:
        """Sync processing loop (no coroutine, no Fiber); when a
        message's processing turns out to be async, the remainder of
        the cycle escalates to a fiber via run_inline."""
        while True:
            with self._nevent_lock:
                pending = self._nevent
            fde = self.fast_drain
            if fde is not None and not self.failed:
                handled = False
                try:
                    handled = fde(self)
                except BaseException as e:
                    self._input_error(e)
                if handled:
                    if not self._finish_input_cycle(pending):
                        return
                    continue
            # the event loop while it fires callbacks and spans record:
            # what this pass takes of its awake time is summed there.
            # The read begins with the callback (no clock read then);
            # the input callback starts with a cut and marks where it
            # processes; the last phase runs to the callback's end
            loop = _event_dispatcher.stamping
            if loop is not None:
                loop.lap(_event_dispatcher.READ)
            self._drain_readable()
            if self.input_portal or self.failed:
                if loop is not None:
                    loop.lap(_event_dispatcher.CUT)
                r = None
                try:
                    r = self._on_input_sync(self)
                except BaseException as e:
                    self._input_error(e)
                if r is not None:
                    self._control.run_inline(
                        self._input_async_tail(r, pending),
                        name="socket_input")
                    return
            if not self._finish_input_cycle(pending):
                return

    async def _input_async_tail(self, r, pending: int):
        """Finish an escalated cycle: await the pending processing, then
        continue the event loop in async mode."""
        try:
            await r
        except BaseException as e:
            self._input_error(e)
        if self._finish_input_cycle(pending):
            await self._process_input()

    async def _process_input(self):
        while True:
            with self._nevent_lock:
                pending = self._nevent
            self._drain_readable()
            if self._on_input is not None and (self.input_portal or self.failed):
                try:
                    r = self._on_input(self)
                    if hasattr(r, "__await__"):
                        await r
                except BaseException as e:
                    self._input_error(e)
            if not self._finish_input_cycle(pending):
                return

    def _drain_readable(self) -> int:
        """Read until EAGAIN/EOF into the portal; returns bytes read.

        Read blocks are sized adaptively: full reads grow the next
        block (up to 256KB) so bulk transfers take few recv syscalls,
        small reads shrink it back so idle connections don't hold large
        buffers — the readv-into-many-blocks effect of
        iobuf.h:469 without the iovec."""
        rc = self._read_chunks
        if rc is not None:
            # zero-copy handoff (mem://): the writer's bytes objects
            # become user-data blocks directly — no read_into copy, no
            # block management
            chunks, eof = rc()
            if eof:
                self.set_failed(ConnectionResetError("peer closed"))
                return 0
            total = 0
            portal = self.input_portal
            for c in chunks:
                portal.append_user_data(c)
                total += len(c)
            if total:
                nreads.add(total)
            return total
        total = 0
        while not self.failed:
            hint = self._read_hint
            try:
                if self._readv is not None and hint >= _BIG_BLOCK_SIZE:
                    # bulk mode: scatter-read a whole burst per syscall
                    n = self.input_portal.append_from_reader_v(
                        self._readv, hint=hint, nbufs=4)
                else:
                    n = self.input_portal.append_from_reader(
                        self.conn.read_into, hint=hint)
            except BlockingIOError:
                # drained. One-shot conns re-arm here (the event consumed
                # their read interest). Level-triggered conns must NOT:
                # their arming is owned by the pause/resume busy protocol
                # — an EAGAIN rearm mid-pause would defeat the pause and
                # let the fd re-fire hot for the rest of the busy period
                if not self._level_triggered:
                    resume = self.conn.resume_read_events
                    if resume is not None:
                        resume()
                break
            except (ConnectionError, OSError) as e:
                self.set_failed(e)
                break
            if n == 0:  # EOF
                self.set_failed(ConnectionResetError("peer closed"))
                break
            if n >= hint:
                # jump straight to the big recyclable size: intermediate
                # sizes would allocate non-poolable buffers
                # graftlint: disable=guarded-by -- _read_hint belongs
                # to whoever holds the input (the _nevent 0->1 winner
                # or the plucker that claimed it): one drain at a time,
                # on whichever thread that context runs.
                self._read_hint = _BIG_BLOCK_SIZE
            elif n < 4096:
                self._read_hint = DEFAULT_BLOCK_SIZE
            total += n
            nreads.add(n)
            if self._drain_all_reads and self.conn.pending_bytes() == 0:
                # exact emptiness probe (a short read is NOT proof —
                # the read may have landed in a small tail-block gap):
                # stop without paying a raise/catch of BlockingIOError
                # per message. Safe only because such conns notify on
                # every write, so a refill re-triggers _process_input.
                break
            if n < 4096 and (self._short_read_drained or (
                    self._plucking and self._level_triggered)):
                # short read on a level-triggered fd: almost certainly
                # drained — skip the EAGAIN recv round trip. 4096 is
                # below every buffer this loop offers (fresh blocks are
                # >=8KB; tail gaps <4KB are never offered), so a short
                # read really was short. If the kernel does hold more,
                # the level trigger fires again — no stall possible.
                # A plucking joiner stops here on every level-triggered
                # conn: its next step is a poll of the fd, which says
                # exactly what an EAGAIN would.
                break
        return total

    def take_device_payload(self):
        take = self.conn.take_device_payload
        if take is None:
            return None
        _ds = _device_stats
        if not _ds.enabled():
            return take()
        t0 = time.monotonic_ns()
        lane = take()
        if lane is None:
            return None
        dur_us = (time.monotonic_ns() - t0) / 1e3
        conn = self.conn
        kind = conn.lane_kind or \
            getattr(conn.remote_endpoint, "scheme", "device")
        cached = self.__dict__.get("_dev_recv")
        if cached is None or cached[0] != kind:
            peer = _ds.peer_key(conn.remote_endpoint)
            cached = (kind, peer,
                      _ds.global_device_stats().device_cell(peer, kind))
            self._dev_recv = cached
        nbytes = sum(getattr(a, "nbytes", 0) or 0 for a in lane)
        cached[2].note_recv(dur_us, nbytes)
        if _span_recording():
            # parse-path handoff: the protocol attaches this to the
            # message so dispatch can hang a device-recv child span off
            # the server span it is about to create (parse per conn is
            # sequential — the slot cannot be clobbered before the
            # attach); only rpcz consumers read it, so only they pay
            # the dict
            self.last_device_take = {
                "peer": cached[1], "lane": kind,
                "recv_us": round(dur_us, 1),
                "nbytes": nbytes, "t_us": t0 // 1000}
        return lane

    def take_device_payload_with_recv(self):
        """(lane_arrays_or_None, recv_record_or_None) — the ONE parse-
        side consumer API: every protocol parse site uses this so the
        take + recv-record handoff cannot drift per protocol (the
        device-recv span's producing half)."""
        lane = self.take_device_payload()
        if lane is None:
            return None, None
        return lane, self.__dict__.pop("last_device_take", None)

    # ------------------------------------------------------------ failure
    def set_failed(self, reason: Optional[BaseException] = None) -> None:
        """Version-bump the id (outstanding SocketIds go stale), close the
        conn, fire failure callbacks (SetFailed, socket.cpp)."""
        with self._failed_cb_lock:
            if self.failed:
                return
            self.failed = True
            self.fail_reason = reason or ConnectionError("socket set_failed")
            cbs = list(self._on_failed_cbs)
        _pool().remove(self.id)
        try:
            self.conn.close()
        except Exception:
            pass
        # the pinned dup (native fd loops) must not outlive the close —
        # it would silently delay the FIN; closed now or by the last
        # pin_fd_release still in flight
        self._pin_fd_shutdown()
        self._writable_butex.fetch_add(1)
        self._writable_butex.wake_all()
        # a writer parked on a writable event will never be woken by the
        # closed conn: claim its handoff (the take IS the writership
        # transfer — the event continuation that loses the race no-ops)
        # and fail-drain it plus everything queued behind it
        item = self._take_handoff()
        if item is not None:
            self._drain_writes_inline(first_item=item)
        for cb in cbs:
            try:
                cb(self)
            except Exception:
                pass
        self._drain_inflight_calls()

    def _drain_inflight_calls(self) -> None:
        """Fail (or retry elsewhere) every client call still issued on
        this socket — the reference errors all correlation ids bound to
        a failed Socket immediately (SetFailed -> bthread_id_error, so
        waiters never sit out the full RPC deadline on a dead
        connection). The failer is installed by the RPC layer
        (inflight_failer); it runs on a fiber because retries may
        reconnect (blocking), which must not run on the event thread."""
        failer = inflight_failer
        if failer is None:
            return
        with self.pending_lock:
            if not self.inflight_calls:
                return
            # correlation id AND issue sequence captured NOW: the failer
            # fiber judges the attempt that was bound to THIS socket —
            # a controller recycled onto a new call (cid changes) or
            # re-issued by a faster failure path (seq changes; transport
            # retries keep the cid) cannot be spuriously judged
            calls = [(c, c.correlation_id, c.__dict__.get("_issue_seq"))
                     for c in self.inflight_calls]
            self.inflight_calls.clear()
        self._control.spawn((lambda s=self, cs=calls: failer(s, cs)),
                            name="inflight_fail")

    def on_failed(self, cb: Callable[["Socket"], None]) -> None:
        # flag-check and append under one lock: a registration racing
        # set_failed's snapshot would otherwise be lost forever
        # (notify_on_cancel waiters would never fire)
        with self._failed_cb_lock:
            if not self.failed:
                self._on_failed_cbs.append(cb)
                return
        cb(self)

    def off_failed(self, cb: Callable[["Socket"], None]) -> None:
        """Unsubscribe a failure callback (no-op if absent): long-lived
        multiplexed sockets must not accumulate dead subscribers."""
        with self._failed_cb_lock:
            try:
                self._on_failed_cbs.remove(cb)
            except ValueError:
                pass


def create_client_socket(ep: EndPoint, on_input: Optional[Callable] = None,
                         control: Optional[TaskControl] = None) -> Socket:
    conn = get_transport(ep.scheme).connect(ep)
    return Socket(conn, on_input=on_input, control=control)
