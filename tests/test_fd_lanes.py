"""The native fd loops (fastcore pluck_scan + serve_drain).

Round-5 escalation of the per-call native loop: the client's sync-pluck
receive (poll+recv+frame scan) and the server's per-event serve
(recv+cut+match+response build) each run in ONE C call, crossing the
interpreter once per RPC instead of once per step — the reference runs
both compiled end to end (input_messenger.cpp:219-331 in-place
processing, socket.cpp:2402 DoRead, baidu_rpc_protocol.cpp:314/565).
These tests pin the C loops' judge-or-defer contract directly over
socketpairs, and the integration semantics the lanes must preserve.
"""

import socket
import threading
import time

import pytest

from brpc_tpu.native import fastcore
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.tpu_std import (MAGIC, SMALL_FRAME_MAX,
                                       _py_pack_small_frame)
from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                          Service)

fc = fastcore.get()
pytestmark = pytest.mark.skipif(
    fc is None or not hasattr(fc, "pluck_scan"),
    reason="fastcore fd loops unavailable")


def _req_prefix(service="Bench", method="Echo"):
    m = pb.RpcMeta()
    m.request.service_name = service
    m.request.method_name = method
    return m.SerializeToString()


def _req(cid, payload=b"ping", service="Bench", method="Echo", att=b""):
    return _py_pack_small_frame(_req_prefix(service, method), cid, payload,
                                att)


def _resp(cid, payload=b"pong", att=b""):
    return _py_pack_small_frame(b"", cid, payload, att)


def _err_resp(cid, code, text):
    m = pb.RpcMeta()
    m.response.error_code = code
    m.response.error_text = text
    return _py_pack_small_frame(m.SerializeToString(), cid, b"")


def _pair():
    a, b = socket.socketpair()
    a.setblocking(False)
    return a, b


class TestPluckScan:
    def test_plain_response(self):
        a, b = _pair()
        b.sendall(_resp(7, b"hello"))
        r = fc.pluck_scan(a.fileno(), MAGIC, 7, 200, SMALL_FRAME_MAX, b"")
        assert r[:6] == (0, 0, None, b"hello", b"", b"")
        assert r[6] == len(_resp(7, b"hello"))   # nread accounting
        a.close(); b.close()

    def test_attachment_and_leftover(self):
        a, b = _pair()
        b.sendall(_resp(8, b"x", b"ATT") + b"tail")
        r = fc.pluck_scan(a.fileno(), MAGIC, 8, 200, SMALL_FRAME_MAX, b"")
        assert r[0] == 0 and r[3] == b"x" and r[4] == b"ATT"
        assert r[5] == b"tail"     # bytes after the frame come back raw
        a.close(); b.close()

    def test_error_response(self):
        a, b = _pair()
        b.sendall(_err_resp(9, 1004, "boom"))
        r = fc.pluck_scan(a.fileno(), MAGIC, 9, 200, SMALL_FRAME_MAX, b"")
        assert r[:3] == (0, 1004, "boom")
        a.close(); b.close()

    @pytest.mark.parametrize("frame_fn", [
        lambda: _resp(11, b"y"),            # foreign correlation id
        lambda: _req(12),                   # a request, not a response
        lambda: b"GET / HTTP/1.1\r\nHo",    # not this protocol's bytes
        lambda: _py_pack_small_frame(       # oversized body
            b"", 12, b"z" * (SMALL_FRAME_MAX + 1)),
    ])
    def test_defers_hand_back_every_byte(self, frame_fn):
        wire = frame_fn()
        a, b = _pair()
        b.sendall(wire)
        r = fc.pluck_scan(a.fileno(), MAGIC, 12, 200, SMALL_FRAME_MAX, b"")
        assert r[0] == 1 and r[1] == wire
        a.close(); b.close()

    def test_slow_meta_defers(self):
        # a response carrying compress_type: only the classic path may
        # judge it (decompression, policy)
        m = pb.RpcMeta()
        m.correlation_id = 13
        m.compress_type = 1
        mb = m.SerializeToString()
        import struct
        wire = struct.pack(">4sII", MAGIC, len(mb) + 2, len(mb)) + mb + b"zz"
        a, b = _pair()
        b.sendall(wire)
        r = fc.pluck_scan(a.fileno(), MAGIC, 13, 200, SMALL_FRAME_MAX, b"")
        assert r[0] == 1 and r[1] == wire
        a.close(); b.close()

    def test_partial_then_carry_resume(self):
        wire = _resp(14, b"z" * 100)
        a, b = _pair()
        b.sendall(wire[:20])
        r = fc.pluck_scan(a.fileno(), MAGIC, 14, 50, SMALL_FRAME_MAX, b"")
        assert r[:2] == (2, wire[:20])     # slice elapsed, partial back
        b.sendall(wire[20:])
        r = fc.pluck_scan(a.fileno(), MAGIC, 14, 200, SMALL_FRAME_MAX, r[1])
        assert r[0] == 0 and r[3] == b"z" * 100
        a.close(); b.close()

    def test_eof_reports_buffered_bytes(self):
        wire = _resp(15, b"q")
        a, b = _pair()
        b.sendall(wire[:9])
        b.close()
        # partial frame then FIN: the loop must surface the error AND
        # the bytes (the classic path decides what they were)
        r = fc.pluck_scan(a.fileno(), MAGIC, 15, 200, SMALL_FRAME_MAX, b"")
        assert r[0] == 3 and "closed" in r[1] and r[2] == wire[:9]
        a.close()

    def test_empty_slice_timeout(self):
        a, b = _pair()
        t0 = time.monotonic()
        r = fc.pluck_scan(a.fileno(), MAGIC, 1, 60, SMALL_FRAME_MAX, b"")
        assert r[:2] == (2, b"")
        assert 0.04 <= time.monotonic() - t0 < 1.0
        a.close(); b.close()


class TestPluckScanFuzz:
    def test_differential_mutated_frames(self):
        """Seeded fuzz: random valid/mutated/truncated response frames
        through pluck_scan must either (a) complete with EXACTLY the
        payload/attachment the Python packer encoded, or (b) defer with
        every byte intact — never a third outcome. The defer bytes are
        then re-parsed by the classic protocol parser to prove nothing
        was corrupted in transit through the C loop."""
        import random
        rng = random.Random(0x51CC)
        from brpc_tpu.butil.iobuf import IOPortal
        from brpc_tpu.protocol.tpu_std import TpuStdProtocol
        from brpc_tpu.protocol.registry import PARSE_OK
        proto = TpuStdProtocol()

        class _Sock:    # parse() needs set_failed + input_need slots
            input_need = 0
            def set_failed(self, e): self.failed = e

        for trial in range(400):
            cid = rng.randrange(1, 1 << 48)
            payload = rng.randbytes(rng.randrange(0, 200))
            att = rng.randbytes(rng.randrange(0, 50)) \
                if rng.random() < 0.3 else b""
            wire = bytearray(_resp(cid, payload, att))
            mutate = rng.random()
            if mutate < 0.35:       # corrupt some bytes
                for _ in range(rng.randrange(1, 5)):
                    wire[rng.randrange(len(wire))] = rng.randrange(256)
            elif mutate < 0.5:      # truncate
                del wire[rng.randrange(1, len(wire)):]
            wire = bytes(wire)
            a, b = _pair()
            try:
                b.sendall(wire)
                r = fc.pluck_scan(a.fileno(), MAGIC, cid, 30,
                                  SMALL_FRAME_MAX, b"")
                if r[0] == 0:
                    # completion: fields must be byte-exact vs what a
                    # clean frame encodes (mutations inside payload
                    # bytes still parse — then the payload IS the
                    # mutated bytes; re-derive from the wire)
                    body = int.from_bytes(wire[4:8], "big")
                    meta = int.from_bytes(wire[8:12], "big")
                    frame = wire[:12 + body]
                    alen = len(r[4])
                    assert r[3] == frame[12 + meta:12 + body - alen]
                    assert r[5] == wire[12 + body:]
                elif r[0] in (1, 2):
                    assert r[1] == wire, (trial, r)
                    # classic parser renders the same verdict on the
                    # handed-back bytes without corruption
                    portal = IOPortal()
                    portal.append(r[1])
                    s = _Sock()
                    try:
                        status, msg = proto.parse(portal, s)
                    except Exception:
                        # classic refuses too (the input loop turns an
                        # escaping parse error into a dropped conn)
                        continue
                    if status == PARSE_OK and msg is not None and \
                            not msg.meta.HasField("request"):
                        # classic accepted a frame the C loop deferred:
                        # legal only for slow-featured metas (the C
                        # walk rejects compress/stream/trace/unknown)
                        m = msg.meta
                        assert (m.correlation_id != cid or m.compress_type
                                or m.HasField("stream_settings")
                                or m.device_payloads or m.trace_id
                                or m.HasField("response")), trial
                else:
                    assert r[0] == 3, (trial, r)
            finally:
                a.close(); b.close()


class TestServeDrainFuzz:
    def test_differential_vs_serve_scan(self):
        """serve_drain over a socketpair must produce byte-identical
        responses and consume/leftover decisions to serve_scan over the
        same bytes (they share serve_core — this pins the fd plumbing
        around it: recv boundaries, leftover slicing, nread)."""
        import random
        rng = random.Random(0xD12A)
        for trial in range(200):
            frames = []
            for _ in range(rng.randrange(1, 6)):
                kind = rng.random()
                cid = rng.randrange(1, 1 << 32)
                if kind < 0.6:
                    frames.append(_req(cid, rng.randbytes(
                        rng.randrange(0, 300))))
                elif kind < 0.8:
                    frames.append(_req(cid, b"x", service="Other"))
                else:
                    frames.append(_resp(cid, b"r"))
            blob = b"".join(frames)
            cut = rng.randrange(0, len(blob) + 1) \
                if rng.random() < 0.4 else len(blob)
            wire = blob[:cut]
            if not wire:
                continue
            want = fc.serve_scan(wire, MAGIC, b"Bench", b"Echo",
                                 SMALL_FRAME_MAX)
            a, b = _pair()
            try:
                b.sendall(wire)
                r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                                   SMALL_FRAME_MAX)
                consumed, out, n = want
                if n:
                    assert r[0] == 0 and r[1] == out and r[2] == n, trial
                    assert r[3] == wire[consumed:], trial
                else:
                    assert r[0] == 1 and r[1] == wire, trial
                assert r[-1] == len(wire), trial   # nread
            finally:
                a.close(); b.close()


class TestServeDrain:
    def test_single_request_round_trip(self):
        a, b = _pair()
        b.sendall(_req(21, b"data"))
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        assert r[0] == 0 and r[2] == 1 and r[3] == b""
        # the produced bytes must BE the wire response for cid 21
        rr = fc.pluck_scan(a.fileno(), MAGIC, 21, 0, SMALL_FRAME_MAX, r[1])
        assert rr[0] == 0 and rr[3] == b"data"
        a.close(); b.close()

    def test_attachment_reflected(self):
        a, b = _pair()
        b.sendall(_req(22, b"p", att=b"ATTACH"))
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        rr = fc.pluck_scan(a.fileno(), MAGIC, 22, 0, SMALL_FRAME_MAX, r[1])
        assert rr[3] == b"p" and rr[4] == b"ATTACH"
        a.close(); b.close()

    def test_batch_with_partial_tail(self):
        a, b = _pair()
        partial = _req(34)[:10]
        b.sendall(_req(31) + _req(32) + _req(33) + partial)
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        assert r[0] == 0 and r[2] == 3 and r[3] == partial
        a.close(); b.close()

    def test_foreign_method_defers_every_byte(self):
        wire = _req(41, service="Other", method="M")
        a, b = _pair()
        b.sendall(wire)
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        assert r[0] == 1 and r[1] == wire
        a.close(); b.close()

    def test_spurious_event(self):
        a, b = _pair()
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        assert r[:2] == (1, b"")
        a.close(); b.close()

    def test_eof(self):
        a, b = _pair()
        b.close()
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        assert r[0] == 2 and r[1] == "peer closed" and r[2] == b""
        a.close()

    def test_eof_behind_frames_still_serves_then_reports(self):
        wire = _req(51)
        a, b = _pair()
        b.sendall(wire)
        b.close()
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        # the short read stops the recv loop before the FIN is observed:
        # the arrived frame is still served (its response can go out)...
        assert r[0] == 0 and r[2] == 1 and r[3] == b""
        # ...and the next pass (the level trigger re-fires on EOF)
        # reports the close
        r = fc.serve_drain(a.fileno(), MAGIC, b"Bench", b"Echo",
                           SMALL_FRAME_MAX)
        assert r[0] == 2 and r[1] == "peer closed"
        a.close()


SCHEMES = ("tcp", "ici")
over_schemes = pytest.mark.parametrize("scheme", SCHEMES)


def _addr(scheme, port):
    """The channel address of a server started by _listen(scheme)."""
    return f"{scheme}://127.0.0.1:{port}" + (
        "#reply_device=0" if scheme == "ici" else "")


def _listen(server, scheme):
    return server.start(f"{scheme}://127.0.0.1:0" + (
        "#device=0" if scheme == "ici" else ""))


def _echo_server(scheme="tcp"):
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("Bench")

    @svc.method(native="echo")
    def Echo(cntl, request):
        return request

    @svc.method()
    def Upper(cntl, request):
        data = request if isinstance(request, (bytes, bytearray)) \
            else request.to_bytes()
        return data.upper()

    server.add_service(svc)
    return server, _listen(server, scheme)


class TestLanesEndToEnd:
    @over_schemes
    def test_sync_echo_uses_native_lanes(self, scheme):
        server, ep = _echo_server(scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=5000))
            for i in range(50):
                cl = ch.call_sync("Bench", "Echo", b"m%d" % i)
                assert not cl.failed()
                assert cl.response_payload.to_bytes() == b"m%d" % i
            # the server side must actually have served through the
            # native batch accounting (fast_drain or turbo lane); the
            # last response is written BEFORE its accounting lands, so
            # give the server thread a beat
            deadline = time.monotonic() + 2.0
            while server.method_status["Bench.Echo"].count() < 50 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server.method_status["Bench.Echo"].count() >= 50
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_mixed_native_and_classic_methods_interleave(self, scheme):
        server, ep = _echo_server(scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=5000))
            for i in range(20):
                a = ch.call_sync("Bench", "Echo", b"low%d" % i)
                b = ch.call_sync("Bench", "Upper", b"low%d" % i)
                assert a.response_payload.to_bytes() == b"low%d" % i
                assert b.response_payload.to_bytes() == b"LOW%d" % i
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_large_response_defers_mid_pluck(self, scheme):
        # response exceeds SMALL_FRAME_MAX: the native loop must defer
        # to the classic path, which assembles it correctly
        server, ep = _echo_server(scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=10000))
            big = b"B" * (SMALL_FRAME_MAX * 3 + 17)
            cl = ch.call_sync("Bench", "Echo", big)
            assert not cl.failed()
            assert cl.response_payload.to_bytes() == big
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_handler_error_via_native_pluck(self, scheme):
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Bench")

        @svc.method()
        def Fail(cntl, request):
            cntl.set_failed(1007, "handler says no")

        server.add_service(svc)
        ep = _listen(server, scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=5000, max_retry=0))
            cl = ch.call_sync("Bench", "Fail", b"x")
            assert cl.failed() and cl.error_code == 1007
            assert "handler says no" in cl.error_text
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_timeout_through_native_loop(self, scheme):
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Bench")
        release = threading.Event()

        @svc.method()
        async def Slow(cntl, request):
            from brpc_tpu.fiber.timer import sleep as fiber_sleep
            await fiber_sleep(2.0)
            return b"late"

        server.add_service(svc)
        ep = _listen(server, scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=150, max_retry=0))
            t0 = time.monotonic()
            cl = ch.call_sync("Bench", "Slow", b"x")
            dt = time.monotonic() - t0
            from brpc_tpu.rpc import errno_codes as berr
            assert cl.failed() and cl.error_code == berr.ERPCTIMEDOUT
            assert dt < 1.5        # the lazy deadline fired, not the join cap
            release.set()
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_peer_close_mid_pluck_fails_the_call(self, scheme):
        # a server that reads the request and closes without answering:
        # the native loop's EOF verdict must fail the call promptly
        # (connection error or timeout-free fast failure), never hang
        lis = socket.socket()
        lis.bind(("127.0.0.1", 0))
        lis.listen(1)
        port = lis.getsockname()[1]

        def evil():
            c, _ = lis.accept()
            c.recv(4096)
            c.close()

        t = threading.Thread(target=evil, daemon=True)
        t.start()
        ch = Channel(_addr(scheme, port),
                     ChannelOptions(timeout_ms=3000, max_retry=0))
        t0 = time.monotonic()
        cl = ch.call_sync("Bench", "Echo", b"x")
        assert cl.failed()
        assert time.monotonic() - t0 < 2.5   # EOF verdict, not the timeout
        ch.close()
        lis.close()
        t.join(2.0)

    def test_chunk_lanes_mem_echo_end_to_end(self):
        # mem:// (chunk-handoff): both sides run the chunk fast lanes —
        # server serve_scan straight off the writer's bytes, client
        # scan_frames dispatch without the portal
        server, _ = (None, None)
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Bench")

        @svc.method(native="echo")
        def Echo(cntl, request):
            return request

        @svc.method()
        def Upper(cntl, request):
            data = request if isinstance(request, (bytes, bytearray)) \
                else request.to_bytes()
            return data.upper()

        server.add_service(svc)
        server.start("mem://fdlanes-chunk")
        try:
            ch = Channel("mem://fdlanes-chunk",
                         ChannelOptions(timeout_ms=5000))
            for i in range(50):
                cl = ch.call_sync("Bench", "Echo", b"c%d" % i)
                assert not cl.failed()
                assert cl.response_payload.to_bytes() == b"c%d" % i
            # classic-method interleave still exact
            u = ch.call_sync("Bench", "Upper", b"abc")
            assert u.response_payload.to_bytes() == b"ABC"
            # large frames defer to the classic path mid-lane
            big = b"L" * (SMALL_FRAME_MAX * 2 + 5)
            cl = ch.call_sync("Bench", "Echo", big)
            assert cl.response_payload.to_bytes() == big
            # error responses flow through the fast response dispatch
            e = ch.call_sync("Bench", "Nope", b"x")
            assert e.failed()
            ch.close()
        finally:
            server.stop()

    def test_chunk_lane_pipelined_burst(self):
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Bench")

        @svc.method(native="echo")
        def Echo(cntl, request):
            return request

        server.add_service(svc)
        server.start("mem://fdlanes-burst")
        try:
            ch = Channel("mem://fdlanes-burst",
                         ChannelOptions(timeout_ms=5000))
            ctls = [ch.call("Bench", "Echo", b"b%d" % i) for i in range(32)]
            for i, c in enumerate(ctls):
                assert c.join(5.0) and not c.failed()
                assert c.response_payload.to_bytes() == b"b%d" % i
            ch.close()
        finally:
            server.stop()

    def test_client_hook_not_installed_for_other_protocols(self):
        from brpc_tpu.rpc.channel import client_fast_drain_hook
        assert client_fast_drain_hook(ChannelOptions(
            protocol="hulu_pbrpc")) is None
        assert client_fast_drain_hook(ChannelOptions()) is not None

    @over_schemes
    def test_timeout_releases_preclaim_and_socket_survives(self, scheme):
        # the sync issue path claims the pluck lane PRE-send; a timed-out
        # call must settle that claim (reads resumed) so the connection
        # keeps working — and the late response is dropped as stale
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Bench")

        @svc.method()
        async def Sometimes(cntl, request):
            if bytes(request) == b"slow":
                from brpc_tpu.fiber.timer import sleep as fiber_sleep
                await fiber_sleep(0.6)
            return b"ok:" + bytes(request)

        server.add_service(svc)
        ep = _listen(server, scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=150, max_retry=0))
            cl = ch.call_sync("Bench", "Sometimes", b"slow")
            from brpc_tpu.rpc import errno_codes as berr
            assert cl.failed() and cl.error_code == berr.ERPCTIMEDOUT
            # same channel, same socket: the lane must have been
            # released; the late 'slow' response must not corrupt or
            # complete this fresh call
            ch2 = Channel(_addr(scheme, ep.port),
                          ChannelOptions(timeout_ms=3000))
            for _ in range(5):
                cl = ch.call_sync("Bench", "Sometimes", b"fast")
                if not cl.failed():
                    break
                time.sleep(0.2)   # late response may race the reuse
            assert not cl.failed(), (cl.error_code, cl.error_text)
            assert cl.response_payload.to_bytes() == b"ok:fast"
            cl = ch2.call_sync("Bench", "Sometimes", b"fast")
            assert cl.response_payload.to_bytes() == b"ok:fast"
            ch.close(); ch2.close()
        finally:
            server.stop()

    def test_lane_counters_account_pluck_wins(self):
        # the fast lanes self-instrument like every other subsystem:
        # sequential sync echoes must land in pluck_fast_responses
        from brpc_tpu.transport.socket import npluck_fast
        server, ep = _echo_server()
        try:
            before = npluck_fast.get_value()
            ch = Channel(f"tcp://127.0.0.1:{ep.port}",
                         ChannelOptions(timeout_ms=5000))
            for i in range(30):
                cl = ch.call_sync("Bench", "Echo", b"n%d" % i)
                assert not cl.failed()
            assert npluck_fast.get_value() - before >= 25  # ~total wins
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_two_sync_threads_share_one_multiplexed_socket(self, scheme):
        # two threads call_sync on the SAME shared channel: one wins the
        # pre-send pluck claim, the other's response crosses the winner's
        # native loop as a foreign cid (defer -> classic dispatch) or
        # completes via the event path — results must stay exact
        server, ep = _echo_server(scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=5000))
            errs = []

            def worker(tag):
                try:
                    for i in range(150):
                        body = b"%s-%d" % (tag, i)
                        cl = ch.call_sync("Bench", "Echo", body)
                        assert not cl.failed(), (cl.error_code,
                                                 cl.error_text)
                        assert cl.response_payload.to_bytes() == body
                except Exception as e:   # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(t,))
                  for t in (b"alpha", b"beta")]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            assert not errs, errs
            ch.close()
        finally:
            server.stop()

    @over_schemes
    def test_pipelined_async_then_sync_share_the_connection(self, scheme):
        server, ep = _echo_server(scheme)
        try:
            ch = Channel(_addr(scheme, ep.port),
                         ChannelOptions(timeout_ms=5000))
            # async calls in flight force the multiplex gate: the sync
            # joiner must keep full semantics with responses for OTHER
            # cids crossing its pluck
            ctls = [ch.call("Bench", "Echo", b"a%d" % i) for i in range(8)]
            cl = ch.call_sync("Bench", "Echo", b"sync")
            assert cl.response_payload.to_bytes() == b"sync"
            for i, c in enumerate(ctls):
                assert c.join(5.0) and not c.failed()
                assert c.response_payload.to_bytes() == b"a%d" % i
            ch.close()
        finally:
            server.stop()


# --------------------------------------------------------------------
# The pluck protocol and the busy pause on the Socket itself, over every
# conn that says pluck_fd: a Socket on one end of a real connection, a
# scripted peer on the other, a messenger that records what it is given.
class _Messenger:
    """Stands where InputMessenger does (Socket finds the sync twin by
    these two names). ``hold``: the next pass that sees bytes suspends
    until the event is set, as a handler that awaits would."""

    def __init__(self):
        self.seen = bytearray()
        self.threads = []
        self.hold = None

    def _take(self, sock):
        self.threads.append(threading.current_thread().name)
        self.seen += sock.input_portal.to_bytes()
        sock.input_portal.clear()

    async def on_new_messages(self, sock):
        r = self.on_new_messages_sync(sock)
        if r is not None:
            await r

    def on_new_messages_sync(self, sock):
        if not sock.input_portal:
            return None
        self._take(sock)
        hold, self.hold = self.hold, None
        if hold is None:
            return None

        async def suspended():
            await hold.wait(5)
        return suspended()


class _Wire:
    """A Socket over a conn of ``kind`` and the far end of it."""

    def __init__(self, kind):
        from brpc_tpu.butil.endpoint import str2endpoint
        from brpc_tpu.transport.socket import Socket
        from brpc_tpu.transport.tcp import TcpConn

        lis = socket.socket()
        lis.bind(("127.0.0.1", 0))
        lis.listen(1)
        port = lis.getsockname()[1]
        near = socket.create_connection(("127.0.0.1", port))
        far, _ = lis.accept()
        lis.close()
        ep = str2endpoint(f"tcp://127.0.0.1:{port}")
        conn = TcpConn(near, ep, ep)
        self._far_sock = far
        self._far_conn = None
        if kind == "ici":
            from brpc_tpu.transport.ici import IciConn
            conn = IciConn(conn, ep, ep)
            self._far_conn = IciConn(TcpConn(far, ep, ep), ep, ep)
        self.messenger = _Messenger()
        self.pauses, self.resumes = [], []
        self.sock = Socket(conn, on_input=self.messenger.on_new_messages)
        pause, resume = conn.pause_read_events, conn.resume_read_events
        conn.pause_read_events = lambda: (self.pauses.append(1), pause())
        conn.resume_read_events = lambda: (self.resumes.append(1), resume())
        if kind == "ici":
            self.wait_seen(b"")     # the far hello is read by an event

    def send(self, data: bytes):
        if self._far_conn is not None:
            self._far_conn.write(memoryview(data))
        else:
            self._far_sock.sendall(data)

    def wait_seen(self, want: bytes, timeout=3.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self.sock._nevent_lock:
                idle = self.sock._nevent == 0
            if bytes(self.messenger.seen) == want and idle:
                return
            time.sleep(0.005)
        raise AssertionError(f"saw {bytes(self.messenger.seen)!r}, "
                             f"want {want!r}")

    def close(self):
        self.sock.set_failed(ConnectionError("test over"))
        if self._far_conn is not None:
            self._far_conn.close()
        else:
            self._far_sock.close()


@pytest.fixture(params=SCHEMES)
def wire(request):
    w = _Wire(request.param)
    yield w
    w.close()


class TestPluckProtocol:
    def test_preclaim_is_exclusive_and_pauses_reads_once(self, wire):
        s = wire.sock
        assert s.pluck_preclaim()
        assert s._plucking and s._busy_paused and len(wire.pauses) == 1
        assert not s.pluck_preclaim()          # one plucker at a time
        s.pluck_release()
        assert not s._plucking

    def test_release_with_nothing_in_flight_goes_sticky(self, wire):
        """The settle leaves reads OFF; the next claim consumes the
        pause with no read-interest syscall; any other consumer of the
        socket re-arms first (unstick_reads)."""
        s = wire.sock
        assert s.pluck_preclaim()
        s.pluck_release()
        assert s._pluck_sticky and s._busy_paused and not wire.resumes
        assert s.pluck_preclaim()              # free: no second pause
        assert len(wire.pauses) == 1 and not s._pluck_sticky
        s.pluck_release()
        assert s._pluck_sticky
        s.unstick_reads()
        assert not s._pluck_sticky and not s._busy_paused
        assert len(wire.resumes) == 1
        wire.send(b"after")                    # the dispatcher is back
        wire.wait_seen(b"after")
        assert "dispatcher" in wire.messenger.threads[-1]

    def test_release_with_a_call_in_flight_resumes_reads(self, wire):
        s = wire.sock
        assert s.pluck_preclaim()
        with s.pending_lock:
            s.client_inflight += 1             # another caller's call
        s.pluck_release()
        assert not s._pluck_sticky and not s._busy_paused
        assert len(wire.resumes) == 1
        with s.pending_lock:
            s.client_inflight -= 1

    def test_write_on_a_sticky_socket_rearms_reads(self, wire):
        s = wire.sock
        assert s.pluck_preclaim()
        s.pluck_release()
        assert s._pluck_sticky
        s.write(b"ping")                       # a non-pluck consumer
        assert not s._pluck_sticky and not s._busy_paused

    def test_the_plucker_reads_its_own_bytes(self, wire):
        """Between claim and release the fd's events are the joiner's:
        it polls, drains and processes on its own thread."""
        s, m = wire.sock, wire.messenger
        assert s.pluck_preclaim()
        wire.send(b"reply")
        me = threading.current_thread().name
        assert s.pluck_until(lambda: bytes(m.seen) == b"reply",
                             time.monotonic() + 3, preclaimed=True)
        assert m.threads[-1] == me
        assert not s._plucking and s._pluck_sticky
        with s._nevent_lock:
            assert s._nevent == 0

    def test_events_during_a_pluck_defer_to_it(self, wire):
        """An event that slips in while a joiner owns the socket bumps
        _nevent and starts no pass; the release settles it with one
        normal pass and read interest comes back."""
        s, m = wire.sock, wire.messenger
        assert s.pluck_preclaim()
        wire.send(b"late")
        s._on_readable_event()                 # as the dispatcher would
        assert not m.seen
        s.pluck_release()
        wire.wait_seen(b"late")
        assert not s._plucking and not s._busy_paused

    def test_escalation_hands_the_cycle_back(self, wire):
        """A message whose processing suspends ends the pluck: claim
        and pending-event accounting go back to the normal machinery,
        which resumes reads when the suspended pass completes."""
        from brpc_tpu.fiber.sync import FiberEvent
        s, m = wire.sock, wire.messenger
        m.hold = hold = FiberEvent()
        assert s.pluck_preclaim()
        wire.send(b"slow")
        done = s.pluck_until(lambda: False, time.monotonic() + 3,
                             preclaimed=True)
        assert not done and bytes(m.seen) == b"slow"
        assert not s._plucking                 # escalated, not expired
        with s._nevent_lock:
            assert s._nevent >= 1              # the pass is still owed
        assert not s.pluck_preclaim()          # and it owns the socket
        hold.set()
        wire.wait_seen(b"slow")
        # b"slow" was seen before the hold: wait for the suspended pass
        # to end its cycle, which is what hands the reads back
        def owed():                  # under the lock the cycle ends in
            with s._nevent_lock:
                return s._nevent
        deadline = time.monotonic() + 3
        while owed() and time.monotonic() < deadline:
            time.sleep(0.001)
        assert owed() == 0
        assert not s._busy_paused and not s._pluck_sticky
        wire.send(b"+next")
        wire.wait_seen(b"slow+next")
        assert "dispatcher" in m.threads[-1]


class TestBusyPause:
    def test_a_busy_period_pauses_once_and_resumes_once(self, wire):
        """A pass suspended on its handler with data still arriving:
        the level-triggered fd is paused for the rest of the busy
        period (one pause, no dispatcher spin), the pass re-drains what
        arrived when it resumes, and read interest comes back once."""
        from brpc_tpu.fiber.sync import FiberEvent
        from brpc_tpu.transport import syscall_stats
        s, m = wire.sock, wire.messenger
        m.hold = hold = FiberEvent()
        wire.send(b"one")
        deadline = time.monotonic() + 3
        while bytes(m.seen) != b"one" and time.monotonic() < deadline:
            time.sleep(0.005)
        assert bytes(m.seen) == b"one"
        ticks = syscall_stats.snapshot()["dispatcher_ticks"]
        wire.send(b"two")
        time.sleep(0.05)
        wire.send(b"three")
        time.sleep(0.1)
        assert s._busy_paused and len(wire.pauses) == 1
        assert bytes(m.seen) == b"one"          # still suspended
        assert syscall_stats.snapshot()["dispatcher_ticks"] - ticks < 10
        hold.set()
        wire.wait_seen(b"onetwothree")
        assert not s._busy_paused
        assert len(wire.pauses) == 1 and len(wire.resumes) == 1

    def test_a_peer_close_is_seen_while_the_pass_is_suspended(self, wire):
        """The busy probe is a non-consuming peek: a FIN behind a
        suspended pass fails the socket now, not when the handler is
        done."""
        from brpc_tpu.fiber.sync import FiberEvent
        s, m = wire.sock, wire.messenger
        m.hold = hold = FiberEvent()
        wire.send(b"one")
        deadline = time.monotonic() + 3
        while bytes(m.seen) != b"one" and time.monotonic() < deadline:
            time.sleep(0.005)
        if wire._far_conn is not None:
            wire._far_conn.close()
        else:
            wire._far_sock.close()
        deadline = time.monotonic() + 3
        while not s.failed and time.monotonic() < deadline:
            time.sleep(0.005)
        assert s.failed
        hold.set()
