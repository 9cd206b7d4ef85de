"""The read cycle of an ici:// connection (ISSUE 27).

``IciConn`` says what its fd is (level-triggered, pausable, peekable,
pollable by a joiner), so the ``Socket`` runs on it the read cycle TCP
has: a busy period pauses read interest once instead of spinning the
dispatcher, and a sync caller reads its own reply on the pluck lane,
one ``recv`` cheaper. What arrives on that fd are lane
frames, so nothing that reads a raw byte stream may start on it. Pinned
here on in-process ``ici://`` pairs: counts and wedges, not times.
"""

import socket as pysocket
import threading
import time

import numpy as np
import pytest

from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                          Service)
from brpc_tpu.transport import ici, syscall_stats
from brpc_tpu.transport.base import get_transport
from brpc_tpu.transport.tcp import TcpConn

from test_ici_write_path import _payload, limit_10s


class _Pair:
    """A Server and a single-connection Channel over ici://, warmed by
    one sync call. ``Echo`` answers at once, ``Slow`` after 50 ms on
    its worker, ``Native`` is a native-echo method (so the server
    offers its sockets the fd-draining serve hook)."""

    def __init__(self):
        svc = Service("R")

        def echo(cntl, request):
            cntl.response_device_arrays = list(
                cntl.request_device_arrays or ())
            return bytes(request)

        def slow(cntl, request):
            time.sleep(0.05)
            return echo(cntl, request)

        svc.register_method("Echo", echo)
        svc.register_method("Slow", slow)

        @svc.method(native="echo")
        def Native(cntl, request):
            return request

        self.server = Server(ServerOptions(enable_builtin_services=False))
        self.server.add_service(svc)
        ep = self.server.start("ici://127.0.0.1:0#device=0")
        self.channel = Channel(
            f"ici://127.0.0.1:{ep.port}#reply_device=0",
            ChannelOptions(timeout_ms=8000, max_retry=0,
                           connection_type="single"))
        self.call(b"warm")

    def call(self, tag, fill=0):
        cntl = self.channel.call_sync(
            "R", "Echo", tag, request_device_arrays=[_payload(fill=fill)])
        assert not cntl.failed(), cntl.error_text
        assert cntl.response_payload.to_bytes() == tag
        return cntl

    def call_async(self, tag, method="Echo", **kw):
        return self.channel.call(
            "R", method, tag, request_device_arrays=[_payload()], **kw)

    @property
    def client_socket(self):
        return self.channel._socket

    @property
    def server_socket(self):
        (sock,) = self.server.connections()
        return sock

    def close(self):
        self.channel.close()
        self.server.stop()
        self.server.join(2)


@pytest.fixture
def pair():
    p = _Pair()
    yield p
    p.close()


def _ticks():
    return syscall_stats.snapshot()["dispatcher_ticks"]


# ------------------------------------------------- the dispatcher's spin
@limit_10s
def test_a_busy_period_does_not_spin_the_dispatcher(pair):
    """Four async calls 10 ms apart into a handler that sleeps 50 ms on
    a worker: requests arrive while the socket's input pass is open.
    Read interest is paused once for the busy period; before, the level
    trigger re-fired for all of it (29,701 ticks in 0.11 s)."""
    time.sleep(0.05)                  # the warm call's idle ACKs settle
    before = _ticks()
    calls = []
    for i in range(4):
        calls.append(pair.call_async(b"s%d" % i, method="Slow"))
        time.sleep(0.01)
    for c in calls:
        assert c.join(5) and not c.failed(), c.error_text
    assert _ticks() - before < 20


@limit_10s
def test_an_echo_loop_ticks_the_dispatcher_a_few_times_a_call(pair):
    n = 100
    before = _ticks()
    for i in range(n):
        pair.call(b"e%d" % i)
    assert (_ticks() - before) / n < 4            # 43.5 a call before


# -------------------------------------------------- one recv a message
def _log_tcp(conn, reads, writes):
    """Log the recv results (a byte count, or "eagain") and the TCP
    writes of one IciConn."""
    inner_read, inner_write = conn._inner.read_into, conn._inner.write

    def read_into(mv):
        try:
            n = inner_read(mv)
        except BlockingIOError:
            reads.append("eagain")
            raise
        reads.append(n)
        return n

    def write(mv):
        n = inner_write(mv)
        writes.append(n)
        return n

    conn._inner.read_into, conn._inner.write = read_into, write


@limit_10s
def test_a_plucked_reply_costs_one_recv_less_and_no_fresh_buffer(pair):
    """The joiner ends its drain at the short read and goes back to its
    poll (two recv a reply: the data, the pump's EAGAIN; three before),
    and the pump reads into the buffer the conn keeps. The event-driven
    drain on the server's side still reads to EAGAIN: on the chip those
    recvs turned out to pace the pipelined cell (PERF.md section 6)."""
    n = 50
    c_reads, c_writes, s_reads, s_writes = [], [], [], []
    _log_tcp(pair.client_socket.conn, c_reads, c_writes)
    _log_tcp(pair.server_socket.conn, s_reads, s_writes)
    rbufs = {id(c._rbuf.obj) for c in (pair.client_socket.conn,
                                       pair.server_socket.conn)}
    for i in range(n):
        pair.call(b"r%d" % i)
    time.sleep(0.05)                              # idle ACKs land
    data = [r for r in c_reads if r != "eagain"]
    assert n <= len(data) <= len(s_writes)
    assert c_reads.count("eagain") <= len(data) + 2, c_reads
    assert s_reads.count("eagain") <= 2 * len(s_reads) // 3 + 2
    assert rbufs == {id(c._rbuf.obj) for c in (pair.client_socket.conn,
                                               pair.server_socket.conn)}


# ------------------------------------------- a sync caller's own reply
@limit_10s
def test_a_sync_call_with_device_arrays_settles_on_the_pluck_lane(pair):
    """The joiner polls the fd and processes its own reply: every join
    counts as plucked, and the dispatcher thread runs no input pass on
    the client socket (sticky pause between calls, claimed pre-send)."""
    sock = pair.client_socket
    passes = []
    entry = sock._process_input_entry

    def counted():
        passes.append(threading.current_thread().name)
        entry()

    sock._process_input_entry = counted
    n = 40
    before = syscall_stats.snapshot()
    for i in range(n):
        out = pair.call(b"p%d" % i, fill=i).response_device_arrays[0]
        assert (np.asarray(out) == i).all()
    after = syscall_stats.snapshot()
    assert after["join_plucked"] - before["join_plucked"] == n
    assert after["join_waited"] == before["join_waited"]
    assert not [t for t in passes if "dispatcher" in t], passes
    # between calls nothing is in flight: reads stay paused (sticky),
    # and the next call's claim finds them so
    assert sock._pluck_sticky and sock._busy_paused


# ------------------------------------- the fd's bytes are lane frames
@limit_10s
def test_no_raw_stream_reader_starts_on_an_ici_socket(pair):
    """pluck_fd says a joiner may poll the fd; stream_fd, which only
    TcpConn says, that its bytes are the application's. Everything that
    reads or writes the raw stream asks the second: no pinned dup for
    pluck_scan/serve_drain, no fd-draining serve hook, no async
    big-write routing."""
    cntl = pair.channel.call_sync("R", "Native", b"n")   # native method
    assert cntl.response_payload.to_bytes() == b"n"
    pair.call(b"after")
    for sock in (pair.client_socket, pair.server_socket):
        conn = sock.conn
        assert isinstance(conn, ici.IciConn)
        assert conn.pluck_fd() == conn._inner.pluck_fd()
        assert getattr(conn, "stream_fd", None) is None
        assert conn._inner.stream_fd() == conn.pluck_fd()
        assert sock.pin_fd_acquire() == -1
        hook = sock.fast_drain
        if hook is not None:
            # a hook takes itself off on the first pass that gets as
            # far as the conn (no stream_fd, no chunks). One still
            # there never got that far: the client's, on a socket whose
            # replies were all plucked, or a server's whose gates
            # (capture, rpcz, admission) stood it down first
            assert hook(sock) is False
            assert sock.fast_drain in (None, hook)
        assert sock._pin_cell == [None]       # no dup was ever made
        assert sock._async_write_min == 0
        assert sock._level_triggered


# --------------------------------------------------------- peek_closed
def _raw_ici_pair():
    """Two IciConns over one TCP connection, neither under a Socket."""
    lis = pysocket.socket()
    lis.bind(("127.0.0.1", 0))
    lis.listen(1)
    port = lis.getsockname()[1]
    a = pysocket.create_connection(("127.0.0.1", port))
    b, _ = lis.accept()
    lis.close()
    ep = str2endpoint(f"tcp://127.0.0.1:{port}")
    conns = [ici.IciConn(TcpConn(s, ep, ep), ep, ep) for s in (a, b)]
    return conns


@limit_10s
def test_peek_closed_waits_for_what_the_conn_still_holds():
    """After the peer's FIN the kernel is empty, but bytes the pump
    already de-enveloped into _appbuf are still to be delivered: the
    connection is alive until a drain has handed them on."""
    a, b = _raw_ici_pair()
    try:
        deadline = time.monotonic() + 3
        while time.monotonic() < deadline and not (a.peer_info and
                                                   b.peer_info):
            a._pump(), b._pump()             # the hellos (a clean FIN
            time.sleep(0.01)                 # needs a's side read)
        a.write(memoryview(b"last words"))
        a.close()
        while time.monotonic() < deadline and not b._appbuf:
            time.sleep(0.01)
            b._pump()                        # moves the bytes up
        while time.monotonic() < deadline and not b._inner.peek_closed():
            time.sleep(0.01)
        assert b._inner.peek_closed()        # FIN seen, kernel empty
        assert bytes(b._appbuf) == b"last words"
        assert not b.peek_closed()
        buf = bytearray(8192)
        assert b.read_into(memoryview(buf)) == 10
        assert b.peek_closed()
        assert b.read_into(memoryview(buf)) == 0          # EOF
    finally:
        b.close()


# ----------------------------------------- the lane's flow control
@pytest.fixture
def window_of_two():
    """Connections dialled inside get a hello window of 2 that no grant
    widens."""
    transport = get_transport("ici")
    saved = transport._window, flag("ici_adaptive_window")
    transport._window = 2
    assert set_flag("ici_adaptive_window", False)
    yield
    transport._window = saved[0]
    set_flag("ici_adaptive_window", saved[1])


@limit_10s
def test_async_calls_that_fill_the_window_then_a_sync_call(window_of_two):
    """A sync call leaves the client socket sticky-paused. Async calls
    then fill the lane's window of 2 and park behind it, and a sync call
    joins behind them: the ACKs that reopen the window arrive as frames
    on the paused fd, and somebody has to read them every time."""
    p = _Pair()
    try:
        assert p.client_socket.conn.lane_introspection()["window"] == 2
        for rnd in range(5):
            p.call(b"sticky-%d" % rnd)
            calls = [p.call_async(b"a%d-%d" % (rnd, i)) for i in range(6)]
            p.call(b"sync-%d" % rnd)
            for i, c in enumerate(calls):
                assert c.join(5) and not c.failed(), c.error_text
                assert c.response_payload.to_bytes() == b"a%d-%d" % (rnd, i)
        conn = p.client_socket.conn
        assert not conn.awaits_peer_frame()
        assert conn.lane_introspection()["enqueue_depth"] == 0
    finally:
        p.close()


@limit_10s
def test_parked_output_keeps_read_interest_on():
    """While the conn holds output for a frame of the peer's, a settling
    pluck leaves no sticky pause behind: reads come back."""
    p = _Pair()
    try:
        sock = p.client_socket
        p.call(b"one")
        assert sock._pluck_sticky
        assert sock.pluck_preclaim()
        sock.conn._want_writable = True       # parked for lane credit
        sock.pluck_release()
        assert not sock._pluck_sticky and not sock._busy_paused
        sock.conn._want_writable = False
        p.call(b"two")
    finally:
        p.close()


@limit_10s
def test_sync_calls_balance_every_device_cell_without_a_close(pair):
    """200 sync calls, nothing closed: the last reply's batch is ACKed
    by the client's idle timer, the last request's by the reply itself;
    a sticky-paused fd holds nothing a cell waits for."""
    from benchmark.lib import counters

    before = counters.snapshot()
    for i in range(200):
        pair.call(b"b%d" % i)
    assert counters.settle_and_check(before, timeout_s=5) == []
    lane = counters.delta(before, counters.snapshot())["lane"]
    # (the warm call's reply may be ACKed inside the window too)
    assert lane["transfers"] == 400 and lane["completed"] in (400, 401)


def test_fifty_sync_threads_on_one_connection(pair):
    """upstream's multi_threaded_echo shape: one joiner at a time plucks
    for all, the others wait on their events; 2,000 calls, each with its
    own tag and fill back."""
    threads, rounds = 50, 40
    bad, done = [], []

    def caller(i):
        for r in range(rounds):
            tag = b"t%d-%d" % (i, r)
            cntl = pair.channel.call_sync(
                "R", "Echo", tag, request_device_arrays=[_payload(fill=i)])
            if cntl.failed():
                bad.append((i, r, cntl.error_text))
                return
            out = np.asarray(cntl.response_device_arrays[0])
            if cntl.response_payload.to_bytes() != tag or \
                    not (out == i).all():
                bad.append((i, r, "another caller's reply"))
                return
        done.append(i)

    before = syscall_stats.snapshot()
    ts = [threading.Thread(target=caller, args=(i,), daemon=True)
          for i in range(threads)]
    for t in ts:
        t.start()
    deadline = time.monotonic() + 60
    for t in ts:
        t.join(max(0.0, deadline - time.monotonic()))
    assert not [t for t in ts if t.is_alive()], "callers still running"
    assert not bad, bad[:3]
    assert len(done) == threads
    after = syscall_stats.snapshot()
    joins = sum(after[k] - before[k] for k in ("join_plucked", "join_waited"))
    assert joins <= threads * rounds          # a reply may beat its join
    assert after["join_plucked"] > before["join_plucked"]
    # the spin is gone at depth 50 too (tens of ticks a call before)
    ticks = after["dispatcher_ticks"] - before["dispatcher_ticks"]
    assert ticks / (threads * rounds) < 4
