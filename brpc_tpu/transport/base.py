"""Transport plugin interface.

The reference hides three data planes behind one Socket (epoll TCP, verbs
RDMA, io_uring — SURVEY.md §2.4); we do the same behind ``Transport``:

  mem://  in-process loopback — the test fabric every layer above runs on
          (the reference's 127.0.0.1 fixture pattern, SURVEY.md §4)
  tcp://  real sockets via a selectors EventDispatcher (bootstrap + DCN)
  ici://  THE device data plane: TCP bootstrap handshake, PjRt pull-DMA
          device lane, windowed flow control (transport/ici.py — the
          RDMA slot)
  tpu://  in-process loopback variant of the device lane (test fabric)
  tpud:// staged (numpy-over-TCP) device lane — the degraded fallback
          ici:// uses when PjRt transfer is unavailable

A Conn is a non-blocking byte stream; BlockingIOError means "would block"
and the owning Socket parks until the dispatcher reports readiness.

**The contract between a Socket and its conn** is the ``Conn`` class
below, whole: five methods a conn must have, and the optional names it
may answer. Every optional name has its default written there (a flag
False, a method None: "this conn lacks it") with what the Socket, the
Channel or the server does when it is set. ``Socket.__init__`` reads the
conn once and turns what it says into its private switches: which
writer loop, whether a busy period pauses read interest, whether a sync
caller may read its own reply. Nothing reads a conn by string, so a
misspelt name is an AttributeError and not a silent slow path. A new
transport subclasses ``Conn`` and overrides what it can do; a wrapper
(``IciConn`` over its TCP conn, ``ChaosConn`` over anything) hands a
name over explicitly, because ``__getattr__`` never fires for a name
the base class declares.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

from brpc_tpu.butil.endpoint import EndPoint


class Conn:
    """One established byte-stream connection (non-blocking).

    Required: ``write``, ``read_into``, ``close``, ``start_events``,
    ``request_writable_event``. Everything else is optional and keeps
    the default declared here, which is what a conn that says nothing
    gets: the ``keep_write`` fiber for every claimed write, a drain to
    EAGAIN, no pause, no pluck."""

    # ------------------------------------------------------------ flags
    # True when the transport can move device arrays out of band (the
    # zero-copy lane); host-byte transports serialize payloads instead
    supports_device_lane: bool = False
    # write_device_payload takes tracker= and flush=: the Socket's
    # writer hands a queued batch's stage tracker through, and the
    # conn's flush/ack legs stamp it (otherwise the Socket settles the
    # tracker itself, right after the hand-over)
    supports_device_tracker: bool = False
    # write() never parks the caller: the context whose push claims a
    # socket's writership sends in place (write-once-then-KeepWrite);
    # otherwise every claim spawns a keep_write fiber
    inline_write_ok: bool = False
    # the conn notifies on every write and answers pending_bytes(): the
    # drain stops when that reads 0, without an EAGAIN round trip
    drain_all_reads: bool = False
    # read events are level-triggered and pause_read_events /
    # resume_read_events exist: a busy period with data pending, and a
    # plucking joiner, pause read interest once and resume once, and
    # an EAGAIN does not re-arm. False: one-shot events, re-armed at
    # EAGAIN through resume_read_events where the conn has one
    level_triggered: bool = False
    # read_into hands the kernel's bytes straight over, so a read
    # under 4096 bytes emptied it: the event-driven drain stops there
    # (only read with level_triggered; a plucking joiner stops at a
    # short read on every level-triggered conn)
    short_read_drained: bool = False
    # the /device cell label of this conn's lane; None: the scheme
    lane_kind: Optional[str] = None
    # the peer's hello, on conns that shake hands: None while it is in
    # flight (Channel.device_lane_kind waits for it). True: no hello
    peer_info = True

    # ------------------------------------------------- optional methods
    # (None where a conn lacks them; a conn overrides with a method)
    # writev(views) -> int: gather-send; the Socket coalesces queued
    # frames into one call (_write_coalesced, _cut_buf)
    writev: Optional[Callable] = None
    # read_into_v(views) -> int: scatter-read; bulk reads fill several
    # blocks a syscall
    read_into_v: Optional[Callable] = None
    # read_chunks() -> (chunks, eof): the writer's bytes objects handed
    # over whole; replaces read_into in the drain (mem://)
    read_chunks: Optional[Callable] = None
    # pending_bytes() -> int: unread bytes; drain_all_reads obliges it
    pending_bytes: Optional[Callable] = None
    # flush() with write(mv, flush=False): the conn frames its own
    # queue, so the Socket's writer hands it every queued item, a
    # device batch then its envelope, and flushes once
    # (_write_gathered)
    flush: Optional[Callable] = None
    # awaits_peer_frame() -> bool: queued output waits for a frame of
    # the peer's; while True a settled pluck leaves no sticky pause
    awaits_peer_frame: Optional[Callable] = None
    # peek_closed() -> bool: non-consuming FIN probe; lets a busy
    # socket see a dead peer, and a sticky-paused one before reuse
    peek_closed: Optional[Callable] = None
    # pluck_fd() -> int: a joining thread may poll this fd and drain
    # the conn through read_into (the sync-pluck lane); everything a
    # poll would miss must still be in the kernel
    pluck_fd: Optional[Callable] = None
    # stream_fd() -> int: the fd's bytes ARE the application's byte
    # stream: native loops on a pinned dup (pluck_scan, serve_drain)
    # and the async big-write routing may use it
    stream_fd: Optional[Callable] = None
    # pause_read_events() / resume_read_events(): read interest off and
    # on (see level_triggered)
    pause_read_events: Optional[Callable] = None
    resume_read_events: Optional[Callable] = None
    # take_device_payload() -> arrays | None: the next inbound device
    # batch, in the order its envelopes arrive
    take_device_payload: Optional[Callable] = None

    # --------------------------------------------------------- required
    def write(self, mv: memoryview) -> int:
        """Write some bytes; raises BlockingIOError if none can be taken."""
        raise NotImplementedError

    def read_into(self, mv: memoryview) -> int:
        """Read some bytes; 0 = peer closed; raises BlockingIOError."""
        raise NotImplementedError

    def close(self) -> None:
        raise NotImplementedError

    def start_events(self, on_readable: Callable[[], None],
                     on_writable: Callable[[], None]) -> None:
        """Begin edge-style readiness callbacks (may fire from any thread)."""
        raise NotImplementedError

    def request_writable_event(self) -> None:
        """Ask for one on_writable callback when the conn can take bytes
        again (epollout registration for a blocked writer)."""
        raise NotImplementedError

    # device-native transports may move jax arrays out of band; host-byte
    # transports leave this None
    def write_device_payload(self, arrays) -> Optional[object]:
        return None

    @property
    def local_endpoint(self) -> Optional[EndPoint]:
        return None

    @property
    def remote_endpoint(self) -> Optional[EndPoint]:
        return None


#: The optional names of the contract, read off the class that declares
#: them: a wrapper passes them in one loop (ChaosConn), and
#: tests/test_conn_contract.py holds every conn class to each
OPTIONAL_NAMES = frozenset(
    name for name, default in vars(Conn).items()
    if not name.startswith("_")
    and (default is None or isinstance(default, bool)))


class Listener:
    def stop(self) -> None:
        raise NotImplementedError

    @property
    def endpoint(self) -> EndPoint:
        raise NotImplementedError


class Transport:
    scheme: str = ""

    def connect(self, ep: EndPoint) -> Conn:
        raise NotImplementedError

    def listen(self, ep: EndPoint, on_new_conn: Callable[[Conn], None]) -> Listener:
        raise NotImplementedError


_transports: Dict[str, Transport] = {}
_lock = threading.Lock()


def register_transport(t: Transport) -> None:
    with _lock:
        _transports[t.scheme] = t


def get_transport(scheme: str) -> Transport:
    t = _transports.get(scheme)
    if t is None:
        # lazy-register builtins on first use
        _register_builtins()
        t = _transports.get(scheme)
    if t is None:
        raise ValueError(f"no transport registered for scheme {scheme!r}")
    return t


def _register_builtins() -> None:
    with _lock:
        if "mem" not in _transports:
            from brpc_tpu.transport.mem import MemTransport
            _transports["mem"] = MemTransport()
        if "tcp" not in _transports:
            from brpc_tpu.transport.tcp import TcpTransport
            _transports["tcp"] = TcpTransport()
        if "tpu" not in _transports:
            from brpc_tpu.transport.tpu import TpuTransport
            _transports["tpu"] = TpuTransport()
        if "tpud" not in _transports:
            from brpc_tpu.transport.tpud import TpudTransport
            _transports["tpud"] = TpudTransport()
        if "ici" not in _transports:
            from brpc_tpu.transport.ici import IciTransport
            _transports["ici"] = IciTransport()
        if "ssl" not in _transports:
            from brpc_tpu.transport.ssl import SslTransport
            _transports["ssl"] = SslTransport()
