"""Streaming RPC tests (test/brpc_streaming_rpc_unittest style): stream
setup piggybacks on an RPC, frames flow both ways with credit-based flow
control, device arrays ride the lane."""

import threading
import time

import numpy as np
import pytest

from brpc_tpu import fiber
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions, Service
from brpc_tpu.rpc.stream import (
    CREDIT_BATCH, DEFAULT_CREDITS, Stream, StreamOptions, stream_accept,
)

_seq = iter(range(100000))


def start_stream_server(server_received, echo_back=False, gate=None):
    """``gate``: the receiver delivers nothing until it is set (a
    receiver that can't drain)."""
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("StreamService")

    @svc.method()
    def Open(cntl, request):
        def on_received(stream, msg):
            if gate is not None:
                gate.wait(10)
            payload = msg.payload.to_bytes()
            server_received.append((payload, list(msg.device_arrays)))
            if echo_back:
                stream.write_nowait(b"echo:" + payload)
        st = stream_accept(cntl, StreamOptions(on_received=on_received))
        assert st is not None
        return b"accepted"

    @svc.method()
    def NoStream(cntl, request):
        assert stream_accept(cntl) is None
        return b"no-stream"

    server.add_service(svc)
    ep = server.start(f"mem://stream-{next(_seq)}")
    return server, ep


class TestStreaming:
    def test_client_to_server_frames(self):
        received = []
        server, ep = start_stream_server(received)
        try:
            ch = Channel(str(ep))
            client_got = []
            cntl = ch.call_sync("StreamService", "Open", b"",
                                stream_options=StreamOptions(
                                    on_received=lambda s, m: client_got.append(m)))
            assert not cntl.failed(), cntl.error_text
            stream = cntl.stream
            assert stream.peer_id != 0

            async def writer():
                for i in range(20):
                    ok = await stream.write(f"frame-{i}".encode())
                    assert ok
            f = fiber.spawn(writer)
            assert f.join(5)
            f.value()
            deadline = time.monotonic() + 5
            while len(received) < 20 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert [p for p, _ in received] == [f"frame-{i}".encode()
                                               for i in range(20)]
            stream.close()
        finally:
            server.stop(); server.join(2)

    def test_bidirectional_echo(self):
        received = []
        server, ep = start_stream_server(received, echo_back=True)
        try:
            ch = Channel(str(ep))
            client_got = []
            cntl = ch.call_sync(
                "StreamService", "Open", b"",
                stream_options=StreamOptions(
                    on_received=lambda s, m: client_got.append(m.payload.to_bytes())))
            stream = cntl.stream

            async def writer():
                for i in range(10):
                    assert await stream.write(f"m{i}".encode())
            f = fiber.spawn(writer)
            assert f.join(5)
            deadline = time.monotonic() + 5
            while len(client_got) < 10 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert client_got == [f"echo:m{i}".encode() for i in range(10)]
            stream.close()
        finally:
            server.stop(); server.join(2)

    def test_flow_control_blocks_writer(self):
        """With a tiny window and a receiver that can't drain, the writer
        must run out of credits rather than buffer unboundedly."""
        received = []
        gate = threading.Event()
        server, ep = start_stream_server(received, gate=gate)
        try:
            ch = Channel(str(ep))
            cntl = ch.call_sync("StreamService", "Open", b"",
                                stream_options=StreamOptions(initial_credits=4))
            stream = cntl.stream
            sent = 0
            for i in range(10):
                if not stream.write_nowait(f"f{i}".encode()):
                    break
                sent += 1
            assert sent == 4  # window exhausted without grants
            stream.close()
        finally:
            gate.set()
            server.stop(); server.join(2)

    def test_credits_replenish(self):
        """Receiver grants credits back after CREDIT_BATCH frames, so a
        long stream sustains more than the initial window."""
        received = []
        server, ep = start_stream_server(received)
        try:
            ch = Channel(str(ep))
            n = DEFAULT_CREDITS + CREDIT_BATCH * 2
            cntl = ch.call_sync("StreamService", "Open", b"",
                                stream_options=StreamOptions())
            stream = cntl.stream

            async def writer():
                sent = 0
                for i in range(n):
                    if await stream.write(f"x{i}".encode(), timeout_s=5):
                        sent += 1
                return sent
            f = fiber.spawn(writer)
            assert f.join(20)
            assert f.value() == n
            deadline = time.monotonic() + 5
            while len(received) < n and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(received) == n
            stream.close()
        finally:
            server.stop(); server.join(2)

    def test_device_arrays_over_stream(self):
        received = []
        server, ep = start_stream_server(received)
        try:
            ch = Channel(str(ep))
            cntl = ch.call_sync("StreamService", "Open", b"",
                                stream_options=StreamOptions())
            stream = cntl.stream
            arr = np.arange(32, dtype=np.float32)

            async def writer():
                return await stream.write(b"tensor", device_arrays=[arr])
            f = fiber.spawn(writer)
            assert f.join(5) and f.value()
            deadline = time.monotonic() + 5
            while not received and time.monotonic() < deadline:
                time.sleep(0.01)
            payload, arrays = received[0]
            assert payload == b"tensor"
            np.testing.assert_array_equal(np.asarray(arrays[0]), arr)
            stream.close()
        finally:
            server.stop(); server.join(2)

    def test_close_propagates(self):
        received = []
        server, ep = start_stream_server(received)
        try:
            ch = Channel(str(ep))
            closed = threading.Event()
            cntl = ch.call_sync("StreamService", "Open", b"",
                                stream_options=StreamOptions())
            stream = cntl.stream
            stream.on_close(lambda s: closed.set())
            stream.close()
            assert stream.closed
        finally:
            server.stop(); server.join(2)

    def test_no_stream_requested(self):
        received = []
        server, ep = start_stream_server(received)
        try:
            ch = Channel(str(ep))
            cntl = ch.call_sync("StreamService", "NoStream", b"")
            assert not cntl.failed(), cntl.error_text
            assert cntl.response_payload.to_bytes() == b"no-stream"
        finally:
            server.stop(); server.join(2)

    def test_peer_death_closes_stream(self):
        """Server's connection dropping mid-stream must fire the
        client's on_close and fail writes promptly — not strand readers
        forever or leave writers to their own timeouts (the reference
        fails streams on the socket's SetFailed path)."""
        received = []
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("StreamService")

        @svc.method()
        def Open(cntl, request):
            st = stream_accept(cntl, StreamOptions(
                on_received=lambda s, m: received.append(m)))
            assert st is not None
            return b"accepted"

        server.add_service(svc)
        ep = server.start("tcp://127.0.0.1:0")
        ch = Channel(f"tcp://{ep.host}:{ep.port}",
                     ChannelOptions(timeout_ms=5000))
        closed = threading.Event()
        try:
            cntl = ch.call_sync("StreamService", "Open", b"",
                                stream_options=StreamOptions())
            assert not cntl.failed(), cntl.error_text
            stream = cntl.stream
            stream.on_close(lambda s: closed.set())
            assert stream.write_nowait(b"frame-1")
            # abrupt peer death: drop every server-side connection
            for s in server.connections():
                s.set_failed(ConnectionError("chaos: server died"))
            assert closed.wait(5), "client never observed stream closure"
            assert stream.remote_closed

            # writers fail fast now (no 10s credit-timeout stall)
            t0 = time.monotonic()
            assert stream.write_nowait(b"after-death") is False
            assert time.monotonic() - t0 < 1.0
        finally:
            ch.close()
            server.stop()
            server.join(2)


class TestBenchStreamSink:
    def test_tool_server_sink_counts_and_acks(self):
        """The bench's streaming phase shape end to end: stream 2MB of
        256KB frames at the spawned tool server's StreamSink, expect
        exactly one done:<n> ack once every byte arrived (credit flow
        control live on a real subprocess boundary)."""
        import os
        import sys
        import threading
        import time

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from spawn_util import spawn_port_server

        from brpc_tpu import fiber
        from brpc_tpu.rpc import Channel, ChannelOptions
        from brpc_tpu.rpc.stream import StreamOptions

        base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc, port = spawn_port_server(
            [os.path.join(base, "tools", "bench_echo_server.py")],
            wall_s=20.0)
        assert port, "tool server spawn failed"
        try:
            frame = b"\x11" * (256 << 10)
            total = len(frame) * 8
            done = threading.Event()
            box = {}

            def on_done(stream, msg):
                box["reply"] = msg.payload.to_bytes()
                done.set()

            ch = Channel(f"tcp://127.0.0.1:{port}",
                         ChannelOptions(timeout_ms=10000))
            cntl = ch.call_sync(
                "Bench", "StreamSink", str(total).encode(),
                stream_options=StreamOptions(on_received=on_done))
            assert not cntl.failed(), (cntl.error_code, cntl.error_text)
            stream = cntl.stream
            assert stream is not None

            async def producer():
                for _ in range(8):
                    assert await stream.write(frame)

            f = fiber.spawn(producer)
            assert f.join(10)
            # join() returns True even when the coroutine died on an
            # exception — surface a failed write as itself, not as a
            # misleading ack timeout below
            assert f.exception is None, f.exception
            assert done.wait(10), "sink never acked"
            assert box["reply"] == b"done:%d" % total
            stream.close()
            ch.close()
        finally:
            proc.terminate()


class TestNativeStreamLane:
    def test_fast_pack_is_bit_identical_to_pb(self):
        """_send_frame's hand-encoded meta must match the protobuf
        serializer byte for byte — same ascending field order, same
        minimal varints — for every frame shape it covers."""
        from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
        from brpc_tpu.protocol.tpu_std import _HDR, MAGIC, pack_message

        class _Rec:
            def __init__(self):
                self.wires = []

            def write(self, w, on_done=None):
                self.wires.append(w if isinstance(w, bytes) else w.to_bytes())

        import array
        for kw, payload in [
            (dict(data=True), b"body"),
            (dict(data=True), b""),
            (dict(data=False, credits=37), b""),
            (dict(data=False, close=True), b""),
            (dict(data=True, credits=300), b"x" * 100),
            # multi-byte memoryview: len() counts elements, the header
            # must count BYTES (a desync here poisons the connection)
            (dict(data=True), memoryview(array.array("I", [1, 2, 3]))),
        ]:
            s = Stream()
            s.peer_id = 0x1234
            s.socket = _Rec()
            s._send_frame(payload, None, **kw)
            got = s.socket.wires[-1]

            meta = pb.RpcMeta()
            ss = meta.stream_settings
            ss.stream_id = 0x1234
            if kw.get("data"):
                ss.frame_seq = 1
            if kw.get("close"):
                ss.close = True
            if kw.get("credits"):
                ss.credits = kw["credits"]
            pay = bytes(payload)
            mb = meta.SerializeToString()
            want = _HDR.pack(MAGIC, len(mb) + len(pay), len(mb)) \
                + mb + pay
            assert got == want, (kw, got.hex(), want.hex())
            s.close()

    def test_scanner_yields_stream_records(self):
        from brpc_tpu.native import fastcore
        from brpc_tpu.protocol.tpu_std import MAGIC, SMALL_FRAME_MAX
        fc = fastcore.get()
        if fc is None:
            import pytest
            pytest.skip("fastcore unavailable")

        class _Rec:
            def __init__(self):
                self.wires = []

            def write(self, w, on_done=None):
                self.wires.append(w if isinstance(w, bytes) else w.to_bytes())

        s = Stream()
        s.peer_id = 99
        s.socket = _Rec()
        s._send_frame(b"payload-bytes", None)                  # data
        s._send_frame(b"", None, credits=16, data=False)       # grant
        s._send_frame(b"", None, close=True, data=False)       # close
        blob = b"".join(s.socket.wires)
        consumed, frames = fc.scan_frames(blob, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == len(blob)
        assert [f[0] for f in frames] == [2, 2, 2]
        k, sid, seq, credits, sclose, po, pl, ao, al = frames[0]
        assert (sid, seq, credits, sclose) == (99, 1, 0, 0)
        assert blob[po:po + pl] == b"payload-bytes"
        assert frames[1][1:5] == (99, 0, 16, 0)
        assert frames[2][1:5] == (99, 0, 0, 1)
        s.close()

    def test_establishment_frames_stay_classic(self):
        # request + stream_settings (the Open RPC) must DEFER — the
        # scanner serves live frames only, never establishment
        import struct

        from brpc_tpu.native import fastcore
        from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
        from brpc_tpu.protocol.tpu_std import MAGIC, SMALL_FRAME_MAX
        fc = fastcore.get()
        if fc is None:
            import pytest
            pytest.skip("fastcore unavailable")
        m = pb.RpcMeta()
        m.request.service_name = "S"
        m.request.method_name = "Open"
        m.correlation_id = 5
        m.stream_settings.stream_id = 7
        mb = m.SerializeToString()
        wire = struct.pack(">4sII", MAGIC, len(mb), len(mb)) + mb
        consumed, frames = fc.scan_frames(wire, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == 0 and frames == []

    def test_scanner_stream_cap_admits_big_data_frames(self):
        # the max_stream_body capability (default OFF in the lanes —
        # large payload delivery is zero-copy on the classic path):
        # complete big DATA frames become kind-2 records; big REQUEST
        # frames never do
        from brpc_tpu.native import fastcore
        from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
        from brpc_tpu.protocol.tpu_std import (MAGIC, SMALL_FRAME_MAX,
                                               _py_pack_small_frame)
        fc = fastcore.get()
        if fc is None:
            import pytest
            pytest.skip("fastcore unavailable")

        class _Rec:
            def __init__(self):
                self.wires = []

            def write(self, w, on_done=None):
                self.wires.append(w if isinstance(w, bytes) else w.to_bytes())

        s = Stream()
        s.peer_id = 5
        s.socket = _Rec()
        big = b"\x44" * (SMALL_FRAME_MAX * 3)
        s._send_frame(big, None)
        wire = s.socket.wires[-1]
        # without the cap: the scan stops (classic path territory)
        consumed, frames = fc.scan_frames(wire, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == 0 and frames == []
        # with the cap: one kind-2 record, payload offsets exact
        consumed, frames = fc.scan_frames(wire, MAGIC, SMALL_FRAME_MAX, 16,
                                          4 << 20)
        assert consumed == len(wire) and len(frames) == 1
        k, sid, seq, credits, sclose, po, pl, ao, al = frames[0]
        assert (k, sid, seq) == (2, 5, 1)
        assert wire[po:po + pl] == big
        # a big REQUEST frame stays classic even with the cap
        m = pb.RpcMeta()
        m.request.service_name = "S"
        m.request.method_name = "M"
        req = _py_pack_small_frame(m.SerializeToString(), 9, big)
        consumed, frames = fc.scan_frames(req, MAGIC, SMALL_FRAME_MAX, 16,
                                          4 << 20)
        assert consumed == 0 and frames == []
        s.close()


class TestScannerLaneParity:
    """ADVICE.md round-5 findings pinned: StreamSettings fields outside
    the scan record's vocabulary must DEFER to the classic lane, never
    ride the fast lane with divergent semantics."""

    @staticmethod
    def _fc():
        from brpc_tpu.native import fastcore
        fc = fastcore.get()
        if fc is None:
            import pytest
            pytest.skip("fastcore unavailable")
        return fc

    @staticmethod
    def _stream_frame(payload=b"data", **ss_fields):
        import struct

        from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
        from brpc_tpu.protocol.tpu_std import MAGIC
        m = pb.RpcMeta()
        ss = m.stream_settings
        for k, v in ss_fields.items():
            setattr(ss, k, v)
        mb = m.SerializeToString()
        return struct.pack(">4sII", MAGIC, len(mb) + len(payload),
                           len(mb)) + mb + payload

    def test_oversized_credits_defer_to_classic_lane(self):
        """credits is int32 on the wire: a varint past INT32_MAX (or a
        negative int32's 10-byte encoding) must stop the scan — the
        classic protobuf parser renders the verdict, and the writer's
        credit counter can never be inflated by a peer-controlled
        out-of-range grant (ADVICE.md finding 1)."""
        from brpc_tpu.protocol.tpu_std import MAGIC, SMALL_FRAME_MAX
        fc = self._fc()
        # INT32_MAX itself still rides the fast lane (in-range)
        ok = self._stream_frame(stream_id=3, frame_seq=1,
                                credits=2 ** 31 - 1)
        consumed, frames = fc.scan_frames(ok, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == len(ok) and len(frames) == 1
        assert frames[0][:5] == (2, 3, 1, 2 ** 31 - 1, 0)
        # negative int32 (wire: 10-byte varint) defers
        neg = self._stream_frame(stream_id=3, frame_seq=1, credits=-1)
        consumed, frames = fc.scan_frames(neg, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == 0 and frames == []
        # hand-encoded varint just past INT32_MAX defers (protobuf's
        # serializer can't produce it from the int32 field, but a raw
        # peer can)
        import struct

        from brpc_tpu.protocol.tpu_std import _varint
        inner = b"\x08\x03" + b"\x18\x01" + b"\x20" + _varint(2 ** 31)
        mb = b"\x32" + _varint(len(inner)) + inner
        raw = struct.pack(">4sII", MAGIC, len(mb) + 4, len(mb)) + mb + b"data"
        consumed, frames = fc.scan_frames(raw, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == 0 and frames == []

    def test_need_feedback_frames_defer_to_classic_lane(self):
        """The scan record carries (stream_id, frame_seq, credits,
        close) only: a frame with need_feedback=true must defer so the
        lazily materialized FastStreamMsg.meta can never show False
        where the classic lane's meta shows True (ADVICE.md finding 2)."""
        from brpc_tpu.protocol.tpu_std import MAGIC, SMALL_FRAME_MAX
        fc = self._fc()
        wire = self._stream_frame(stream_id=3, frame_seq=2,
                                  need_feedback=True)
        consumed, frames = fc.scan_frames(wire, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == 0 and frames == []
        # the same frame without the bit rides the fast lane
        wire = self._stream_frame(stream_id=3, frame_seq=2)
        consumed, frames = fc.scan_frames(wire, MAGIC, SMALL_FRAME_MAX, 16)
        assert consumed == len(wire) and len(frames) == 1

    def test_fast_msg_meta_matches_classic_lane_meta(self):
        """For every frame the scanner ADMITS, FastStreamMsg.meta must
        be field-for-field identical to the classic lane's parsed meta
        — the 'EVERY StreamSettings field' contract, now enforceable
        because unrepresentable frames defer (see the two tests above)."""
        from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
        from brpc_tpu.protocol.tpu_std import MAGIC, SMALL_FRAME_MAX
        from brpc_tpu.rpc.stream import FastStreamMsg
        fc = self._fc()
        shapes = [dict(stream_id=9, frame_seq=1),
                  dict(stream_id=9, frame_seq=4, credits=16),
                  dict(stream_id=9, close=True),
                  dict(stream_id=9, credits=2 ** 31 - 1)]
        for ss_fields in shapes:
            wire = self._stream_frame(**ss_fields)
            consumed, frames = fc.scan_frames(wire, MAGIC,
                                              SMALL_FRAME_MAX, 16)
            assert consumed == len(wire) and len(frames) == 1, ss_fields
            k, sid, seq, credits, sclose, po, pl, ao, al = frames[0]
            assert k == 2
            fast = FastStreamMsg(wire[po:po + pl], b"", sid, seq,
                                 credits, sclose)
            classic = pb.RpcMeta()
            classic.ParseFromString(wire[12:12 + (len(wire) - 12 - pl)])
            assert fast.meta == classic, ss_fields
            assert fast.payload.to_bytes() == b"data"
