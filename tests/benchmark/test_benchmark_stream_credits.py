"""Streams with device frames under back-pressure, on ``ici://`` between
virtual CPU devices at small sizes: the program's stream counters
against the credit model of ``benchmark/reference/stream_ring.py``, and
the frame spans that record only while something records."""

import threading
import time

import numpy as np
import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path

FRAMES = 64
WINDOW = 4


class Edge:
    """A server on device ``dst`` whose ``Open`` accepts a stream with
    ``on_received``, and a stream opened to it with ``WINDOW`` credits."""

    def __init__(self, dst: int, on_received, credits: int = WINDOW):
        from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                                  ServerOptions, Service)
        from brpc_tpu.rpc.stream import StreamOptions, stream_accept

        self.server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Ring")

        def open_(cntl, request):
            self.accepted = stream_accept(
                cntl, StreamOptions(on_received=on_received))
            return b"ok"
        svc.register_method("Open", open_)
        self.server.add_service(svc)
        ep = self.server.start(f"ici://127.0.0.1:0#device={dst}")
        self.channel = Channel(
            f"ici://127.0.0.1:{ep.port}#reply_device=0",
            ChannelOptions(timeout_ms=20000, max_retry=0,
                           connection_type="single"))
        cntl = self.channel.call_sync(
            "Ring", "Open", b"",
            stream_options=StreamOptions(initial_credits=credits))
        assert not cntl.failed(), cntl.error_text
        self.out = cntl.stream

    def close(self):
        self.out.close()
        self.accepted.close()
        self.channel.close()
        self.server.stop()
        self.server.join(5)


def _frame(i: int):
    import jax
    import jax.numpy as jnp

    return jax.device_put(jnp.full((8, 16), i, jnp.bfloat16),
                          jax.devices()[0])


def _write_all(stream, n: int):
    """``n`` tagged device frames with ``await write`` from one fiber;
    the count of writes that returned True."""
    from brpc_tpu import fiber

    async def writer():
        sent = 0
        for i in range(n):
            sent += await stream.write(i.to_bytes(8, "little"),
                                       device_arrays=[_frame(i)])
        return sent
    f = fiber.spawn(writer)
    assert f.join(60)
    return f.value()


@pytest.mark.parametrize("hops", [1, 2])
def test_a_slow_consumer_parks_the_writer(hops):
    """64 device frames through a window of 4 to a consumer that sleeps:
    the writer parks, never holds more than 4 un-granted frames, and all
    arrive in order. With ``hops`` 2 a forwarder sits between (it writes
    from its drainer fiber), so the slow last stage parks the first
    writer through it."""
    import jax

    from benchmark.reference.stream_ring import credit_model
    from brpc_tpu.rpc.stream import CREDIT_BATCH

    got = []

    def consume(stream, msg):
        time.sleep(0.002)
        a = msg.device_arrays[0]
        got.append((int.from_bytes(msg.payload.to_bytes(), "little"),
                    int(np.asarray(a)[0, 0]), a.devices()))
    last = Edge(hops, consume)
    edges = [last]
    if hops == 2:
        async def forward(stream, msg):
            assert await last.out.write(msg.payload.to_bytes(),
                                        device_arrays=msg.device_arrays)
        edges.insert(0, Edge(1, forward))
    try:
        first = edges[0]
        assert _write_all(first.out, FRAMES) == FRAMES
        deadline = time.monotonic() + 20
        while len(got) < FRAMES and time.monotonic() < deadline:
            time.sleep(0.01)
        assert [g[0] for g in got] == list(range(FRAMES))      # in order
        assert [g[1] for g in got] == list(range(FRAMES))      # the values
        assert all(g[2] == {jax.devices()[hops]} for g in got)
        model = credit_model(FRAMES, WINDOW, CREDIT_BATCH)
        for e in edges:
            w, r = e.out.counters(), e.accepted.counters()
            assert w["data_frames_out"] == r["data_frames_in"] == FRAMES
            assert w["device_bytes_out"] == r["device_bytes_in"] \
                == FRAMES * 8 * 16 * 2
            assert w["host_bytes_out"] == FRAMES * 8
            assert w["ungranted_frames_max"] == \
                model["ungranted_frames_max"] == WINDOW
            assert r["recv_queue_depth_max"] <= WINDOW
        # the first writer against the model: a grant for every frame
        # that took its last credit, a park before each but the last
        w, r = first.out.counters(), first.accepted.counters()
        assert w["credit_parks"] > 0 and w["credit_park_us"] > 0
        assert w["credit_parks"] <= model["credit_parks"]
        assert r["ctrl_frames_out"] == w["ctrl_frames_in"] \
            == model["grant_frames"]
    finally:
        for e in edges:
            e.close()


def test_write_nowait_without_credits_sends_nothing():
    gate = threading.Event()
    edge = Edge(1, lambda stream, msg: gate.wait(10))
    try:
        sent = [edge.out.write_nowait(b"%d" % i, device_arrays=[_frame(i)])
                for i in range(WINDOW + 3)]
        assert sent == [True] * WINDOW + [False] * 3
        gate.set()
        assert edge.accepted.join_drained(10)
        time.sleep(0.05)
        w, r = edge.out.counters(), edge.accepted.counters()
        assert w["data_frames_out"] == r["data_frames_in"] == WINDOW
        assert w["credit_parks"] == 0
    finally:
        gate.set()
        edge.close()


def test_frame_spans_only_while_something_records(monkeypatch):
    """Off: a frame makes no Span at all. On: one span a side, joined by
    the receiving stream's id and frame_seq, every stamp in order, and
    the device transfer's child spans hang on them."""
    from brpc_tpu.butil.flags import flag, set_flag
    from brpc_tpu.rpc import span as span_mod

    made = []
    real_init = span_mod.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(self)
        real_init(self, *a, **kw)
    got = []
    edge = Edge(1, lambda stream, msg: got.append(msg))
    saved = {name: flag(name) for name in ("rpcz_enabled",
                                           "device_stats_enabled")}
    set_flag("rpcz_enabled", False)
    set_flag("device_stats_enabled", True)
    try:
        # FrameSpan's generated __init__ is its own: count both
        monkeypatch.setattr(span_mod.Span, "__init__", counting_init)
        frame_init = span_mod.FrameSpan.__init__

        def counting_frame_init(self, *a, **kw):
            made.append(self)
            frame_init(self, *a, **kw)
        monkeypatch.setattr(span_mod.FrameSpan, "__init__",
                            counting_frame_init)
        assert not span_mod.recording()
        assert _write_all(edge.out, 3) == 3
        assert edge.out.write_nowait(b"x", device_arrays=[_frame(3)])
        _wait(lambda: len(got) == 4)
        assert made == []

        span_mod.global_collector.clear()
        set_flag("rpcz_enabled", True)
        assert _write_all(edge.out, 2) == 2
        _wait(lambda: len(got) == 6)
        # the sending half's device child ends with the lane's ack
        _wait(lambda: sum(1 for s in span_mod.global_collector.recent(100)
                          if s.side in ("stream", "device")) == 8)
        spans = span_mod.global_collector.recent(100)
        halves = {(s.service, s.frame_seq): s for s in spans
                  if s.side == "stream"}
        assert set(halves) == {("stream-send", 5), ("stream-recv", 5),
                               ("stream-send", 6), ("stream-recv", 6)}
        for seq in (5, 6):
            s, r = halves["stream-send", seq], halves["stream-recv", seq]
            assert s.stream_id == r.stream_id == edge.accepted.id
            assert 0 < s.start_us <= s.credit_us <= s.write_done_us
            assert s.start_us <= r.received_us <= r.deliver_start_us \
                <= r.deliver_end_us
            d = s.to_dict()
            assert d["write_start_us"] == s.start_us and d["frame_seq"] == seq
            kids = {c.service for c in spans if c.side == "device"
                    and c.parent_span_id in (s.span_id, r.span_id)}
            assert kids == {"device", "device-recv"}
    finally:
        for name, value in saved.items():
            set_flag(name, value)
        edge.close()


def _wait(cond, timeout_s: float = 10.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert cond()
