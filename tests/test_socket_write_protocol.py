"""Socket write-queue protocol tests: the MPSC claim/drain/retire
arbitration (queues.cc writer-retire via fastcore, _PyMpsc fallback) and
the event-driven blocked-write continuation (socket.py _drain_writes_
inline / _on_writable_event / set_failed handoff steal)."""

import threading
import time

from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.transport.base import Conn
from brpc_tpu.transport.socket import Socket


class ThrottledConn(Conn):
    """A conn that accepts only ``accept`` bytes per write() and then
    raises BlockingIOError until fed a writable event — the minimal
    harness for the mid-frame parking protocol."""

    inline_write_ok = True

    def __init__(self, accept: int = 4):
        self.accept = accept
        self.sent = bytearray()
        self.blocked = False
        self.writable_requested = 0
        self._on_writable = None
        self.closed = False

    def write(self, mv) -> int:
        if self.closed:
            raise BrokenPipeError("closed")
        if self.blocked:
            raise BlockingIOError
        n = min(self.accept, len(mv))
        self.sent += bytes(mv[:n])
        self.blocked = True          # every write blocks after one chunk
        return n

    def read_into(self, mv) -> int:
        raise BlockingIOError

    def start_events(self, on_readable, on_writable):
        self._on_writable = on_writable

    def request_writable_event(self):
        self.writable_requested += 1

    def fire_writable(self):
        self.blocked = False
        self._on_writable()

    def close(self):
        self.closed = True

    @property
    def local_endpoint(self):
        return str2endpoint("mem://throttle-local")

    @property
    def remote_endpoint(self):
        return str2endpoint("mem://throttle-remote")


def test_blocked_write_continues_on_writable_events():
    """A frame larger than the conn accepts parks mid-frame and
    completes chunk by chunk as writable events fire — with the done
    callback exactly once at the end."""
    conn = ThrottledConn(accept=4)
    sock = Socket(conn)
    done = []
    assert sock.write_small(b"ABCDEFGHIJ", on_done=done.append)
    # first chunk went out inline; writership parked on the event
    assert bytes(conn.sent) == b"ABCD"
    assert conn.writable_requested == 1
    assert not done
    conn.fire_writable()
    assert bytes(conn.sent) == b"ABCDEFGH"
    assert not done
    conn.fire_writable()
    assert bytes(conn.sent) == b"ABCDEFGHIJ"
    assert done == [None]
    # queued writes behind the parked frame drain in order
    done2 = []
    sock.write_small(b"123456", on_done=done2.append)
    sock.write(IOBuf(), on_done=done2.append)   # empty IOBuf completes too
    while bytes(conn.sent) != b"ABCDEFGHIJ123456":
        conn.fire_writable()
    assert done2 == [None, None]
    sock.set_failed(ConnectionError("test over"))


def test_set_failed_steals_parked_handoff_and_fails_queue():
    """set_failed must claim a parked writer's frame and fail-drain it
    plus everything queued behind it — no silent drops, no double
    delivery when a late writable event races the steal."""
    conn = ThrottledConn(accept=2)
    sock = Socket(conn)
    results = []
    sock.write_small(b"partial-frame", on_done=results.append)
    assert bytes(conn.sent) == b"pa"       # parked mid-frame
    sock.write_small(b"queued", on_done=results.append)
    sock.set_failed(ConnectionError("boom"))
    assert len(results) == 2
    assert all(isinstance(r, ConnectionError) for r in results)
    # a late writable event must no-op (handoff already stolen)
    n_sent = len(conn.sent)
    if conn._on_writable is not None:
        conn.blocked = False
        conn._on_writable()
    assert len(conn.sent) == n_sent
    # post-failure writes fail their callback immediately
    late = []
    assert sock.write_small(b"late", on_done=late.append) is False
    assert isinstance(late[0], ConnectionError)


def test_concurrent_writers_fifo_per_thread_over_one_socket():
    """N threads race small frames onto ONE multiplexed socket; the
    claim protocol must keep every thread's own frames in order and
    lose none (the socket.cpp StartWrite contract)."""
    from brpc_tpu.rpc import Channel, ChannelOptions, Server, Service

    server = Server()
    svc = Service("Seq")
    got = []
    lock = threading.Lock()

    @svc.method()
    async def Push(cntl, request):
        with lock:
            got.append(bytes(request))
        return b"ok"

    server.add_service(svc)
    ep = server.start("tcp://127.0.0.1:0")
    try:
        ch = Channel(f"tcp://{ep.host}:{ep.port}",
                     ChannelOptions(timeout_ms=10000))
        N, PER = 4, 120
        errs = []

        def worker(k):
            for i in range(PER):
                c = ch.call_sync("Seq", "Push", f"{k}:{i}".encode())
                if c.failed():
                    errs.append(c.error_text)
                    return

        ths = [threading.Thread(target=worker, args=(k,)) for k in range(N)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(60)
        assert not errs, errs[0]
        assert len(got) == N * PER
        for k in range(N):
            seq = [int(b.split(b":")[1]) for b in got
                   if b.startswith(f"{k}:".encode())]
            assert seq == sorted(seq), f"thread {k} reordered"
    finally:
        server.stop()
        server.join(2)
