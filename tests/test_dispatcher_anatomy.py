"""The event thread's tick from inside (ISSUE 35): the three stamps the
loop publishes (``sleep_ns``, ``tick_ns``, ``callback_ns``), their copy
onto the span of every frame the loop cuts, and the five sums of the
loop's wall time, over real loopback transports on the CPU. The parts
of a wake are taken as the benchmark takes them
(``benchmark/lib/wake_split.py``): the attribution is proved here, not
assumed. No number here is a measurement.
"""

import socket as pysocket
import threading
import time

import pytest

from benchmark.lib.wake_split import parts_of
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.bvar.variable import dump_exposed
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.tpu_std import RpcMessage
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions
from brpc_tpu.rpc.service import Service
from brpc_tpu.rpc.span import FrameSpan, Span, copy_wake, global_collector
from brpc_tpu.rpc.stream import FastStreamMsg, StreamOptions, stream_accept
from brpc_tpu.transport import event_dispatcher as ed
from brpc_tpu.transport import syscall_stats

STAMPS = ("wake_sleep_us", "wake_tick_us", "wake_callback_us")
SCHEMES = ["tcp", "ici"]


@pytest.fixture
def spans_off():
    saved = flag("rpcz_enabled")
    set_flag("rpcz_enabled", False)
    global_collector.clear()
    yield
    set_flag("rpcz_enabled", saved)
    global_collector.clear()


@pytest.fixture
def rpcz(spans_off):
    set_flag("rpcz_enabled", True)


class Fabric:
    """One server and one channel over a loopback fd transport."""

    def __init__(self, scheme: str):
        self.frames = []
        self.server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("S")

        @svc.method()
        def Echo(cntl, request):
            return bytes(request)

        @svc.method()
        def Open(cntl, request):
            assert stream_accept(cntl, StreamOptions(
                on_received=lambda s, m: self.frames.append(m))) is not None
            return b"accepted"

        self.server.add_service(svc)
        tail = "#device=0" if scheme == "ici" else ""
        ep = self.server.start(f"{scheme}://127.0.0.1:0{tail}")
        self.channel = Channel(f"{scheme}://127.0.0.1:{ep.port}",
                               ChannelOptions(timeout_ms=10000))
        self.sync()                 # the dial (and the lane's hello)

    def sync(self, payload: bytes = b"tag"):
        cntl = self.channel.call_sync("S", "Echo", payload)
        assert not cntl.failed(), cntl.error_text
        return cntl

    def with_done(self, payload: bytes = b"tag"):
        """One call completed through ``done=``: no joiner plucks, so
        the event loop cuts the reply."""
        done = threading.Event()
        cntl = self.channel.call("S", "Echo", payload,
                                 done=lambda _c: done.set())
        assert done.wait(10) and not cntl.failed(), cntl.error_text
        return cntl

    def close(self):
        self.channel.close()
        self.server.stop()
        self.server.join(2)


@pytest.fixture(params=SCHEMES)
def fabric(request, spans_off):
    f = Fabric(request.param)
    yield f
    f.close()


def _spans_of(cntl, want=2, deadline_s=3.0):
    """The call's spans by side; the server's trails its response."""
    deadline = time.monotonic() + deadline_s
    while True:
        by_side = {s.side: s for s in global_collector.find_trace(
            cntl.trace_id)}
        if len(by_side) >= want or time.monotonic() >= deadline:
            return by_side
        time.sleep(0.01)


def _ordered(span, cut_us):
    stamps = [getattr(span, k) for k in STAMPS]
    assert all(stamps), stamps
    assert stamps == sorted(stamps) and stamps[-1] <= cut_us, (stamps,
                                                               cut_us)


# ------------------------------------------------------------- the stamps
def test_a_request_cut_on_the_loop_carries_its_tick(fabric, rpcz):
    for payload in (b"tiny", b"x" * 65536):     # scan lane, classic parse
        server = _spans_of(fabric.sync(payload))["server"]
        _ordered(server, server.received_us)


def test_a_plucked_reply_carries_zeros(fabric, rpcz):
    """A sync caller on a plain thread reads its own reply: cut off the
    loop, so the response's wake has no tick."""
    before = syscall_stats.snapshot()["join_plucked"]
    clients = [_spans_of(fabric.sync())["client"] for _ in range(5)]
    plucked = syscall_stats.snapshot()["join_plucked"] - before
    assert plucked > 0
    zeros = [c for c in clients
             if [getattr(c, k) for k in STAMPS] == [0, 0, 0]]
    assert len(zeros) >= plucked
    for c in clients:
        if c not in zeros:          # a reply that beat its joiner
            _ordered(c, c.first_byte_us)


def test_a_reply_nobody_plucks_is_cut_on_the_loop(fabric, rpcz):
    for payload in (b"tiny", b"x" * 65536):
        client = _spans_of(fabric.with_done(payload))["client"]
        _ordered(client, client.first_byte_us)


def test_a_stream_frame_carries_its_tick(fabric, rpcz):
    cntl = fabric.channel.call_sync("S", "Open", b"",
                                    stream_options=StreamOptions())
    assert not cntl.failed(), cntl.error_text
    for payload in (b"small", b"y" * 200000):
        assert cntl.stream.write_nowait(payload)
    deadline = time.monotonic() + 5
    while len(fabric.frames) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    halves = [s for s in global_collector.recent(1000)
              if s.side == "stream" and s.service == "stream-recv"]
    assert len(halves) == 2
    for half in halves:
        _ordered(half, half.received_us)
        assert {k: getattr(half, k) for k in STAMPS}.items() \
            <= half.to_dict().items()
    cntl.stream.close()


@pytest.mark.parametrize("make", [
    lambda: Span(trace_id=1, span_id=2),
    lambda: FrameSpan(trace_id=1, span_id=2, side="stream",
                      service="stream-recv"),
], ids=["call", "frame"])
def test_the_three_fields_are_in_the_dict(make):
    span = make()
    copy_wake(span, None)           # cut off the loop: stays zero
    assert [span.to_dict()[k] for k in STAMPS] == [0, 0, 0]
    copy_wake(span, (5_000_999, 6_000_000, 7_000_001))
    assert [span.to_dict()[k] for k in STAMPS] == [5000, 6000, 7000]


def test_a_span_keeps_its_values_inline():
    """CPython keeps an instance's attribute values inline only below 30
    names; from the thirtieth on every ``Span`` carries a dict of its
    own, which read as 12-27% of a rate with spans on (PERF.md section
    6, PR 35). The three stamps are ONE stored attribute for that."""
    span = Span(trace_id=1, span_id=2)
    assert len(vars(span)) < 30
    assert "wake_us" in vars(span) and "wake_tick_us" not in vars(span)


def _rpc_message():
    return RpcMessage(pb.RpcMeta(), IOBuf(), IOBuf())


@pytest.mark.parametrize("make", [
    _rpc_message, lambda: FastStreamMsg(b"p", b"", 1, 1)],
    ids=["RpcMessage", "FastStreamMsg"])
def test_a_message_made_off_the_loop_has_no_wake(make, rpcz):
    assert getattr(make(), "wake", None) is None


# ------------------------------------------------- a callback of our own
class Hook:
    """A consumer of the global dispatcher whose callback the test
    writes: ``poke()`` makes its fd readable, the loop runs ``body``."""

    def __init__(self, body):
        self.r, self.w = pysocket.socketpair()
        self.r.setblocking(False)
        self.body = body
        self.ran = threading.Event()
        self.d = ed.global_dispatcher()
        self.d.add_consumer(self.r.fileno(), self._on_readable)

    def _on_readable(self):
        self.r.recv(64)
        try:
            self.body(self)
        finally:
            self.ran.set()

    def poke(self):
        self.ran.clear()
        self.w.send(b"x")

    def close(self):
        self.d.remove_consumer(self.r.fileno())
        self.r.close()
        self.w.close()


@pytest.fixture
def hook():
    hooks = []

    def make(body):
        hooks.append(Hook(body))
        return hooks[-1]
    yield make
    for h in hooks:
        h.close()


def test_a_held_loop_shows_as_busy_or_queue_not_select(fabric, rpcz, hook):
    """A callback holds the loop 50 ms while a request is written: the
    request's wake is loop_busy (the tick that held it ended after the
    write) or queue (its socket fired in that same tick), not select.
    (The issue's 20 ms, with room for a loaded host's late writer.)"""
    held = threading.Event()

    def hold(_h):
        held.set()
        time.sleep(0.050)

    h = hook(hold)
    h.poke()
    assert held.wait(5)
    cntl = fabric.with_done()       # written while the loop is held
    assert h.ran.wait(5)
    by_side = _spans_of(cntl)
    server, client = by_side["server"], by_side["client"]
    m0 = min(client.write_done_us, server.received_us)
    busy, select, queue, read = parts_of(
        m0, server.wake_sleep_us, server.wake_tick_us,
        server.wake_callback_us, server.received_us)
    assert busy + select + queue + read == server.received_us - m0
    assert busy + queue >= 15_000, (busy, select, queue, read)
    assert select < (busy + queue) // 3, (busy, select, queue, read)


@pytest.mark.parametrize("on", [False, True], ids=["spans_off", "rpcz"])
def test_callback_ns_is_set_only_while_spans_record(on, spans_off, hook):
    set_flag("rpcz_enabled", on)
    seen = {}

    def look(h):
        seen.update(callback_ns=h.d.callback_ns, tick_ns=h.d.tick_ns,
                    sleep_ns=h.d.sleep_ns, stamping=ed.stamping,
                    here=ed.wake_stamps(), live=h.d._tick_start_ns,
                    msg=getattr(_rpc_message(), "wake", None))

    h = hook(look)
    for _ in range(2):              # the second wake has slept once
        h.poke()
        assert h.ran.wait(5)
    assert seen["live"] == seen["tick_ns"] != 0     # the watchdog's pair
    assert 0 < seen["sleep_ns"] <= seen["tick_ns"]
    if on:
        assert seen["stamping"] is h.d
        assert seen["tick_ns"] <= seen["callback_ns"]
        assert seen["here"] == seen["msg"] == (
            seen["sleep_ns"], seen["tick_ns"], seen["callback_ns"])
    else:
        assert seen["stamping"] is None and seen["callback_ns"] == 0
        assert seen["here"] is None and seen["msg"] is None
    deadline = time.monotonic() + 2
    while h.d._tick_start_ns and time.monotonic() < deadline:
        time.sleep(0.001)
    assert h.d._tick_start_ns == 0 and h.d.callback_ns == 0
    assert ed.stamping is None


def test_off_the_loops_thread_there_is_no_tick(rpcz, hook):
    """While the loop stamps, another thread's cut copies nothing and
    its pass's laps are not the loop's time."""
    inside, release = threading.Event(), threading.Event()

    def park(_h):
        inside.set()
        release.wait(5)

    h = hook(park)
    h.poke()
    assert inside.wait(5)
    try:
        assert ed.stamping is h.d
        before = list(h.d._phase_ns)
        assert ed.wake_stamps() is None
        assert getattr(_rpc_message(), "wake", None) is None
        h.d.lap(ed.PROCESS)
        assert h.d._phase_ns == before and h.d._phase == ed.REST
    finally:
        release.set()
    assert h.ran.wait(5)


# --------------------------------------------------------------- the sums
def _sums():
    snap = syscall_stats.snapshot()
    return {k: snap[k] for k in ed.LOOP_SUMS}


def test_the_sums_do_not_move_with_spans_off(fabric):
    before = _sums()
    for _ in range(5):
        fabric.sync()
        fabric.with_done(b"x" * 65536)
    time.sleep(0.05)
    assert _sums() == before
    assert global_collector.recent(10) == []
    assert ed.global_dispatcher().callback_ns == 0


def test_the_sums_move_while_spans_record_and_nest(fabric, rpcz):
    before = _sums()
    for _ in range(10):
        fabric.sync()
        fabric.with_done(b"x" * 65536)
    time.sleep(0.05)
    set_flag("rpcz_enabled", False)
    time.sleep(0.05)                # the iteration under way is summed
    after = _sums()
    d = {k: after[k] - before[k] for k in ed.LOOP_SUMS}
    assert all(v > 0 for v in d.values()), d
    # each sum is whole us, so a difference of two readings is off by
    # under one us: three parts against the whole by under four
    assert d["dispatcher_read_us"] + d["dispatcher_cut_us"] \
        + d["dispatcher_process_us"] <= d["dispatcher_awake_us"] + 4, d
    assert d["dispatcher_awake_us"] <= d["dispatcher_loop_us"] + 2, d
    time.sleep(0.05)
    assert _sums() == after         # and stand still again


@pytest.mark.parametrize("name", ed.LOOP_SUMS)
def test_a_sum_is_in_the_snapshot_and_on_vars(name):
    ed.expose_stall_vars()
    assert name in syscall_stats.snapshot()
    assert name in dict(dump_exposed("dispatcher_"))
    assert ed.loop_sums()[name] >= 0
