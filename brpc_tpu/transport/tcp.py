"""TCP transport: non-blocking sockets driven by the EventDispatcher.

The reference's epoll-ET Socket/Acceptor path (brpc/socket.cpp,
acceptor.cpp) reduced to its essentials: non-blocking connect with
deferred writability, accept loop on the dispatcher, TCP_NODELAY on by
default (RPC latency over Nagle throughput).
"""

from __future__ import annotations

import errno
import socket as pysocket
import threading
from typing import Callable, Optional

from brpc_tpu.butil.endpoint import EndPoint, str2endpoint
from brpc_tpu.butil.flags import define_flag, flag
from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.transport.base import Conn, Listener, Transport
from brpc_tpu.transport.event_dispatcher import global_dispatcher
# conn-boundary syscall floor (ISSUE 15): every Python->libc socket
# crossing below stamps one of these, counted where it's paid
from brpc_tpu.transport.syscall_stats import (py_accept as _c_accept,
                                              py_recv as _c_recv,
                                              py_writev as _c_writev)

define_flag("acceptor_backoff_ms", 100,
            "pause accepting for this long after the accept loop hits "
            "fd exhaustion (EMFILE/ENFILE) — a level-triggered listener "
            "would otherwise spin the dispatcher at 100% while the "
            "process is out of descriptors",
            validator=lambda v: v > 0)

# accept-loop health: each pause is one fd-exhaustion incident the
# timer-driven resume absorbed instead of a dispatcher hot-loop
naccept_pauses = Adder().expose("acceptor_fd_exhausted_pauses")


class TcpConn(Conn):
    # first write attempt runs inline in the caller's context (the
    # reference writes once in place before handing leftovers to
    # KeepWrite, socket.cpp:1960-2050): a nonblocking send of a small
    # frame almost always completes immediately, and the inline path
    # saves two fiber wakeups per RPC round trip. Safe because
    # cut_into_writer absorbs EAGAIN (partial frames hand off to the
    # keep_write fiber with the writing flag held).
    inline_write_ok = True

    def __init__(self, sock: pysocket.socket, local: EndPoint, remote: EndPoint):
        sock.setblocking(False)
        try:
            sock.setsockopt(pysocket.IPPROTO_TCP, pysocket.TCP_NODELAY, 1)
        except OSError:
            pass
        try:
            # bulk-transfer buffers: default rmem/wmem mean ~64-128KB per
            # recv wakeup on a 1MB payload — each extra chunk costs a
            # syscall plus block bookkeeping on the drain path. 2MB (two
            # 1MB frames in flight per direction) keeps the pipe full
            # across a writable-event wake gap; 4MB measured no better
            # and grows the cache working set
            sock.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_RCVBUF, 2 << 20)
            sock.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_SNDBUF, 2 << 20)
        except OSError:
            pass
        self._sock = sock
        self._local = local
        self._remote = remote
        self._closed = False

    def write(self, mv: memoryview) -> int:
        _c_writev.add(1)
        try:
            return self._sock.send(mv)
        except BlockingIOError:
            raise
        except OSError as e:
            if e.errno in (errno.EAGAIN, errno.EWOULDBLOCK):
                raise BlockingIOError from e
            raise

    def writev(self, views) -> int:
        """Gather-send (sendmsg): one syscall for a whole ref chain —
        a chunked 1MB response is ~6 scattered blocks, and per-block
        send() syscalls were the server's dominant cost
        (iobuf.h:177 prepare_iovecs / writev discipline)."""
        _c_writev.add(1)
        try:
            return self._sock.sendmsg(views)
        except BlockingIOError:
            raise
        except OSError as e:
            if e.errno in (errno.EAGAIN, errno.EWOULDBLOCK):
                raise BlockingIOError from e
            raise

    def read_into_v(self, views) -> int:
        """Scatter-read (recvmsg_into): fill several blocks per syscall
        when a burst is pending (iobuf.h:469's readv-into-many-blocks)."""
        _c_recv.add(1)
        try:
            return self._sock.recvmsg_into(views)[0]
        except BlockingIOError:
            raise
        except OSError as e:
            if e.errno in (errno.EAGAIN, errno.EWOULDBLOCK):
                raise BlockingIOError from e
            raise

    def read_into(self, mv: memoryview) -> int:
        _c_recv.add(1)
        try:
            return self._sock.recv_into(mv)
        except BlockingIOError:
            raise
        except OSError as e:
            if e.errno in (errno.EAGAIN, errno.EWOULDBLOCK):
                raise BlockingIOError from e
            raise

    # level-triggered events (see start_events): a short read implies the
    # kernel buffer is (almost certainly) empty, and if not, the level
    # trigger fires again — Socket._drain_readable may stop early
    # without the EAGAIN recv round trip. Pause/resume move the
    # read-interest syscalls from per-message to per-busy-period.
    level_triggered = True
    # the first half of the comment above, said on its own: read_into
    # hands the kernel's bytes straight over, so a short read drained
    # it. (ici:// leaves it out; PERF.md section 6, PR 27, has why.)
    short_read_drained = True

    def pluck_fd(self) -> int:
        """fd for the sync-pluck lane (Socket.pluck_until): a joining
        thread may poll it and drain this conn through read_into. A
        conn offers it when everything a poll would miss is still in
        the kernel: plain TCP, and ici:// over it. SSL buffers
        decrypted bytes above the fd and mem:// has no fd."""
        return self._sock.fileno()

    # The same fd, for those that read or write the byte stream on it
    # directly: the native loops on a pinned dup (pluck_scan,
    # serve_drain) and the async big-write routing. Only this conn
    # says it: on every conn layered over one (ici://, tpud://) the
    # fd's bytes are that conn's frames.
    stream_fd = pluck_fd

    def peek_closed(self) -> bool:
        """Non-consuming liveness probe (MSG_PEEK): True only when the
        peer's FIN has arrived AND no data remains to deliver — pending
        bytes keep the connection alive until a drain sees them."""
        try:
            return self._sock.recv(1, pysocket.MSG_PEEK) == b""
        except (BlockingIOError, InterruptedError):
            return False
        except OSError:
            return True

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        global_dispatcher().remove_consumer(self._sock.fileno())
        try:
            self._sock.close()
        except OSError:
            pass

    def start_events(self, on_readable, on_writable) -> None:
        self._on_writable = on_writable
        # LEVEL-triggered: with inline processing the drain runs on the
        # dispatcher thread itself, so by the time the callback returns
        # the kernel buffer is empty and the level trigger is silent —
        # zero read-interest syscalls on the common path. The consumer
        # pauses read interest explicitly for the rare busy period
        # (handler suspended with data still arriving), which is where
        # one-shot arming paid a disarm+rearm syscall PER MESSAGE.
        global_dispatcher().add_consumer(self._sock.fileno(), on_readable,
                                         oneshot_read=False)

    def pause_read_events(self) -> None:
        global_dispatcher().pause_read(self._sock.fileno())

    def resume_read_events(self) -> None:
        global_dispatcher().resume_read(self._sock.fileno())

    def request_writable_event(self) -> None:
        global_dispatcher().request_writable(self._sock.fileno(), self._on_writable)

    @property
    def local_endpoint(self):
        return self._local

    @property
    def remote_endpoint(self):
        return self._remote


class _TcpListener(Listener):
    def __init__(self, sock: pysocket.socket, ep: EndPoint,
                 on_new_conn: Callable[[Conn], None]):
        self._sock = sock
        self._ep = ep
        self._on_new_conn = on_new_conn
        self._stopped = False
        sock.setblocking(False)
        global_dispatcher().add_consumer(sock.fileno(), self._on_acceptable)

    def _on_acceptable(self):
        # accept-until-EAGAIN (acceptor.cpp:253 OnNewConnectionsUntilEAGAIN)
        while True:
            _c_accept.add(1)
            try:
                s, addr = self._sock.accept()
            except BlockingIOError:
                return
            except OSError as e:
                if e.errno in (errno.EMFILE, errno.ENFILE, errno.ENOMEM):
                    # fd exhaustion: the pending connection stays in the
                    # kernel backlog, so this LEVEL-triggered fd would
                    # re-fire the instant we return — a hot loop pinning
                    # the dispatcher exactly when the process is
                    # resource-starved. Pause accept interest and let a
                    # timer resume it once some fds may have freed
                    # (acceptor.cpp's EMFILE backoff discipline).
                    self._pause_accept()
                return
            local = self._ep
            remote = str2endpoint(f"tcp://{addr[0]}:{addr[1]}")
            self._on_new_conn(TcpConn(s, local, remote))

    def _pause_accept(self) -> None:
        naccept_pauses.add(1)
        global_dispatcher().pause_read(self._sock.fileno())
        from brpc_tpu.fiber.timer import global_timer
        global_timer().schedule_after(
            flag("acceptor_backoff_ms") / 1e3, self._resume_accept)

    def _resume_accept(self) -> None:
        if self._stopped:
            return     # raced stop(): never re-arm a closed (reusable) fd
        # re-arming is enough: the listener is LEVEL-triggered, so a
        # still-pending backlog re-fires _on_acceptable on the
        # dispatcher thread at its next select — accepting here on the
        # timer thread would both race that fire and stall every queued
        # timer behind a potentially backlog-deep accept loop
        global_dispatcher().resume_read(self._sock.fileno())

    def stop(self) -> None:
        self._stopped = True
        global_dispatcher().remove_consumer(self._sock.fileno())
        try:
            self._sock.close()
        except OSError:
            pass

    @property
    def endpoint(self) -> EndPoint:
        return self._ep


class TcpTransport(Transport):
    scheme = "tcp"

    def listen(self, ep: EndPoint, on_new_conn) -> Listener:
        sock = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_STREAM)
        sock.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_REUSEADDR, 1)
        if ep.extra("reuse_port") in ("1", "true"):
            # shard-group serving (the reference's -reuse_port,
            # server.cpp StartInternal): N worker processes each bind
            # this port and the kernel spreads accepted connections
            # across their listeners. Must be set BEFORE bind, and
            # every member of the group must set it.
            sock.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_REUSEPORT, 1)
        sock.bind((ep.host or "127.0.0.1", ep.port))
        sock.listen(1024)
        host, port = sock.getsockname()[:2]
        bound = EndPoint("tcp", host, port, ep.extras)
        return _TcpListener(sock, bound, on_new_conn)

    def connect(self, ep: EndPoint) -> Conn:
        sock = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_STREAM)
        # blocking connect here keeps bring-up simple; the Socket layer's
        # write queue already tolerates slow establishment (the reference
        # does non-blocking connect + epollout; our dispatcher supports it
        # via request_writable if this ever shows up in profiles)
        sock.settimeout(10.0)
        sock.connect((ep.host, ep.port))
        sock.settimeout(None)
        lh, lp = sock.getsockname()[:2]
        return TcpConn(sock, str2endpoint(f"tcp://{lh}:{lp}"), ep)
