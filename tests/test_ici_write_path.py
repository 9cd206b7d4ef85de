"""The write path of a device-payload call on ici:// (ISSUE 25).

A device batch and its envelope enter the socket's write queue as one
item; the context whose push claims writership hands both to the conn
and flushes once. Pinned here, on an in-process ``ici://`` pair: one
TCP write per direction carries batch and envelope, no ``keep_write``
fiber is spawned, the write's completion may re-issue the call without
deadlock, a closed window parks pairs in order and the ACK edge lets
them go, and the stage stamps are taken after the TCP write.
"""

import functools
import threading
import time

import numpy as np
import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                          Service)
from brpc_tpu.rpc.span import global_collector
from brpc_tpu.transport import ici, syscall_stats
from brpc_tpu.transport.socket import write_mode_totals


def limit_10s(fn):
    """Run the test body in a thread of its own and fail it, instead of
    hanging the suite, when it is not done within 10 s."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        box = {}

        def body():
            try:
                fn(*args, **kwargs)
            except BaseException as e:     # noqa: BLE001 (re-raised below)
                box["error"] = e

        t = threading.Thread(target=body, daemon=True)
        t.start()
        t.join(10)
        assert not t.is_alive(), f"{fn.__name__} not done within 10 s"
        if "error" in box:
            raise box["error"]
    return wrapper


def _payload(n=64, fill=0):
    import jax.numpy as jnp
    return jnp.full((n,), fill, jnp.float32)


class _Pair:
    """A Server and a single-connection Channel over ici://, warmed by
    one call so both sockets exist and the hello has landed."""

    def __init__(self, handler=None, **channel_options):
        svc = Service("W")

        def echo(cntl, request):
            cntl.response_device_arrays = list(
                cntl.request_device_arrays or ())
            return bytes(request)

        svc.register_method("Echo", handler or echo)
        self.server = Server(ServerOptions(enable_builtin_services=False))
        self.server.add_service(svc)
        ep = self.server.start("ici://127.0.0.1:0#device=0")
        opts = dict(timeout_ms=8000, max_retry=0,
                    connection_type="single")
        opts.update(channel_options)
        self.addr = f"ici://127.0.0.1:{ep.port}#reply_device=0"
        self.channel = Channel(self.addr, ChannelOptions(**opts))
        self.call(b"warm")

    def call(self, tag, **kw):
        cntl = self.channel.call_sync("W", "Echo", tag,
                                      request_device_arrays=[_payload()],
                                      **kw)
        assert not cntl.failed(), cntl.error_text
        assert cntl.response_payload.to_bytes() == tag
        return cntl

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


def _record_tcp_writes(conn, log):
    """Log every TCP write of one IciConn as (the frame types it
    carried, the time it returned, us)."""
    inner_write = conn._inner.write

    def write(mv):
        data = bytes(mv)
        n = inner_write(mv)
        types, pos = [], 0
        while pos + ici._HDR.size <= len(data):
            ftype, _ack, length = ici._HDR.unpack_from(data, pos)
            types.append(ftype)
            pos += ici._HDR.size + length
        assert n == len(data), "a short TCP write would split this log"
        log.append((types, time.monotonic_ns() // 1000))
        return n

    conn._inner.write = write


@limit_10s
def test_batch_and_envelope_leave_in_one_tcp_write_without_a_fiber(pair):
    calls = 12
    client_log, server_log = [], []
    _record_tcp_writes(pair.client_socket.conn, client_log)
    _record_tcp_writes(pair.server_socket.conn, server_log)
    before = syscall_stats.snapshot()
    for i in range(calls):
        pair.call(b"one-%d" % i)
    # a write is logged after it returns, by which time its reader may
    # have finished the call; the idle ACKs (2 ms timers) settle too
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline and any(
            sum(ici.F_DESCRIPTOR in t for t, _ in log) < calls
            for log in (client_log, server_log)):
        time.sleep(0.01)
    time.sleep(0.1)
    after = syscall_stats.snapshot()
    for log in (client_log, server_log):
        carrying = [t for t, _ in log if ici.F_DESCRIPTOR in t]
        # one TCP write a call and direction: the descriptor first,
        # its envelope behind it in the same write
        assert len(carrying) == calls, log
        for types in carrying:
            assert types[0] == ici.F_DESCRIPTOR, types
            assert set(types[1:]) == {ici.F_BYTES}, types
        # whatever else left was a bare ACK
        rest = [t for t, _ in log if ici.F_DESCRIPTOR not in t]
        assert all(t == [ici.F_ACK] for t in rest), rest
    acks = sum(1 for log in (client_log, server_log)
               for t, _ in log if t == [ici.F_ACK])
    assert after["writev"] - before["writev"] == 2 * calls + acks
    # every claim of writership sent in place
    assert after["write_fiber_spawns"] == before["write_fiber_spawns"]
    assert after["write_inplace"] - before["write_inplace"] >= 2 * calls
    for sock in (pair.client_socket, pair.server_socket):
        assert sock.family == "ici"
        assert sock.write_fiber_spawns == 0
        assert sock.write_inplace >= calls


@limit_10s
def test_write_mode_counters_split_by_conn_family():
    """A conn that cannot be written in place (tpud://) counts its
    claims as fiber spawns, under its own family."""
    from brpc_tpu.bvar.variable import dump_exposed

    svc = Service("W")
    svc.register_method("Echo", lambda cntl, request: bytes(request))
    server = Server(ServerOptions(enable_builtin_services=False))
    server.add_service(svc)
    ep = server.start("tpud://127.0.0.1:0")
    ch = Channel(f"tpud://127.0.0.1:{ep.port}",
                 ChannelOptions(timeout_ms=4000, max_retry=0,
                                connection_type="single"))
    try:
        _, fibers0 = write_mode_totals()
        for i in range(3):
            cntl = ch.call_sync("W", "Echo", b"t%d" % i)
            assert not cntl.failed(), cntl.error_text
        sock = ch._socket
        assert sock.family == "tpud"
        # a write that queues behind a live fiber spawns none
        assert sock.write_fiber_spawns >= 1 and sock.write_inplace == 0
        _, fibers1 = write_mode_totals()
        assert fibers1 - fibers0 >= 2          # both directions
        exposed = dict(dump_exposed("socket_write_"))
        assert exposed["socket_write_fiber_spawns_tpud"] >= 2
        assert exposed["socket_write_inplace_tpud"] == 0
    finally:
        ch.close()
        server.stop()
        server.join(2)


@limit_10s
def test_failed_write_completion_retries_without_deadlock():
    """The peer is gone under calls in flight: the flush that carries
    a pair fails, the socket fails, and the write's completion callback
    re-issues the call (max_retry 1) from the writer's own context, on
    a new socket. No lock is held there, so nothing deadlocks."""
    p = _Pair(max_retry=1, timeout_ms=5000)
    try:
        results, threads = [], []
        go = threading.Event()

        def caller(i):
            go.wait(5)
            for r in range(4):
                tag = b"r%d-%d" % (i, r)
                cntl = p.channel.call_sync(
                    "W", "Echo", tag, request_device_arrays=[_payload()])
                results.append((cntl.failed(), cntl.current_try,
                                cntl.failed() or
                                cntl.response_payload.to_bytes() == tag))

        for i in range(6):
            t = threading.Thread(target=caller, args=(i,), daemon=True)
            t.start()
            threads.append(t)
        first = p.client_socket

        def broken_pipe(mv):
            raise BrokenPipeError("peer closed")

        first.conn._inner.write = broken_pipe
        go.set()
        for t in threads:
            t.join(8)
        assert not any(t.is_alive() for t in threads), "callers hung"
        assert len(results) == 24
        assert first.failed
        assert p.client_socket is not first
        # every call has its verdict, and it is its own reply: the
        # calls that met the dead conn were retried and then answered
        assert all(not failed and tag_ok
                   for failed, _, tag_ok in results), results
        assert any(tries == 1 for _, tries, _ in results), results
        p.call(b"after")
    finally:
        p.close()


@pytest.fixture
def no_grant():
    saved = flag("ici_adaptive_window")
    assert set_flag("ici_adaptive_window", False)
    yield
    set_flag("ici_adaptive_window", saved)


@limit_10s
def test_more_callers_than_the_window_with_no_grant(no_grant):
    """48 sync callers on a hello window of 32 that never widens: the
    gate closes, the pairs behind it wait in the conn in order
    (envelope i always ahead of batch i+1, so the server can consume
    and acknowledge), and the ACK edge lets them go. Every reply
    carries its caller's tag. Before the pair was one queue item the
    envelopes of sent batches queued behind the gated batch and the
    connection deadlocked."""
    p = _Pair()
    try:
        callers, rounds = 48, 6
        bad, done = [], []

        def caller(i):
            for r in range(rounds):
                tag = b"g%d-%d" % (i, r)
                cntl = p.channel.call_sync(
                    "W", "Echo", tag,
                    request_device_arrays=[_payload(fill=i)])
                if cntl.failed():
                    bad.append((i, r, cntl.error_text))
                    return
                out = np.asarray(cntl.response_device_arrays[0])
                if cntl.response_payload.to_bytes() != tag or \
                        not (out == i).all():
                    bad.append((i, r, "another caller's reply"))
                    return
            done.append(i)

        threads = [threading.Thread(target=caller, args=(i,), daemon=True)
                   for i in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(9)
        assert not bad, bad[:3]
        assert len(done) == callers
        intro = p.client_socket.conn.lane_introspection()
        assert intro["effective_window"] == 32 and intro["peer_grant"] == 0
        assert intro["enqueue_depth"] == 0 and not intro["poisoned"]
    finally:
        p.close()


@pytest.fixture
def rpcz():
    saved = flag("rpcz_enabled")
    set_flag("rpcz_enabled", True)
    global_collector.clear()
    yield
    set_flag("rpcz_enabled", saved)
    global_collector.clear()


@limit_10s
def test_stage_stamps_are_taken_after_the_tcp_write(rpcz, pair):
    """write_done_us (client) and flushed_us (server) stamp a frame the
    conn has handed to TCP, not an enqueue."""
    client_log, server_log = [], []
    _record_tcp_writes(pair.client_socket.conn, client_log)
    _record_tcp_writes(pair.server_socket.conn, server_log)
    cntl = pair.call(b"stamp")
    deadline = time.monotonic() + 3
    spans = []
    while time.monotonic() < deadline:
        spans = [s.to_dict()
                 for s in global_collector.find_trace(cntl.trace_id)]
        sides = {s["side"] for s in spans
                 if s.get("service") not in ("device", "device-recv")}
        if {"client", "server"} <= sides:
            break
        time.sleep(0.02)
    while time.monotonic() < deadline and not (
            any(ici.F_DESCRIPTOR in t for t, _ in client_log)
            and any(ici.F_DESCRIPTOR in t for t, _ in server_log)):
        time.sleep(0.01)           # a write is logged after it returns
    by_side = {s["side"]: s for s in spans
               if s.get("service") not in ("device", "device-recv")
               and (s.get("write_done_us") or s.get("flushed_us"))}
    (request_write,) = [t for types, t in client_log
                        if ici.F_DESCRIPTOR in types]
    (response_write,) = [t for types, t in server_log
                         if ici.F_DESCRIPTOR in types]
    client, server = by_side["client"], by_side["server"]
    assert client["write_done_us"] >= request_write - 1, (client,
                                                          request_write)
    assert server["flushed_us"] >= response_write - 1, (server,
                                                        response_write)
    # and the write is inside its stage, not after it
    assert client["start_us"] <= request_write <= client["first_byte_us"]
    assert server["handler_end_us"] <= response_write


@pytest.mark.parametrize("syscalls, want", [
    # the parent's program counts no claims: nothing to report
    ({"recv": 9, "writev": 6}, None),
    # counted, but no claim inside the window
    ({"write_inplace": 0, "write_fiber_spawns": 0}, None),
    ({"write_inplace": 6, "write_fiber_spawns": 2}, 75.0),
    ({"write_inplace": 8, "write_fiber_spawns": 0}, 100.0),
])
def test_write_inplace_share_reader(syscalls, want):
    """benchmark/layer_metrics/write_inplace_share.py reads the two
    keys syscall_stats.snapshot() carries, and nothing under a program
    that lacks them."""
    import types

    from benchmark.layer_metrics import write_inplace_share

    run = types.SimpleNamespace(counters={"syscalls": syscalls},
                                verified_calls=4)
    assert write_inplace_share.read(run) == want
    assert {"write_inplace", "write_fiber_spawns"} <= \
        set(syscall_stats.snapshot())
