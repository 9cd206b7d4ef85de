"""HTTP protocol tests: drive a real server with a raw HTTP client over
tcp:// (brpc_http_rpc_protocol_unittest style)."""

import json
import socket as pysocket
import time

import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, Server, ServerOptions, Service
from brpc_tpu.bvar import Adder, unexpose_all


@pytest.fixture(autouse=True, scope="module")
def _rpcz_flag_as_found():
    """test_flags_get_and_set leaves rpcz_enabled on and later tests of
    this file count on that; it must not outlive the file, or every
    test the xdist worker runs afterwards records spans (two of
    tier-1's flakes, ISSUE 28)."""
    saved = flag("rpcz_enabled")
    yield
    set_flag("rpcz_enabled", saved)


def http_get(ep, path, body=None, method=None):
    method = method or ("POST" if body else "GET")
    s = pysocket.create_connection((ep.host, ep.port), timeout=5)
    body = body or b""
    req = (f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
           f"Content-Length: {len(body)}\r\n\r\n").encode() + body
    s.sendall(req)
    data = b""
    while b"\r\n\r\n" not in data:
        chunk = s.recv(65536)
        if not chunk:
            break
        data += chunk
    head, _, rest = data.partition(b"\r\n\r\n")
    headers = head.decode().split("\r\n")
    status = int(headers[0].split(" ")[1])
    clen = 0
    for h in headers[1:]:
        if h.lower().startswith("content-length:"):
            clen = int(h.split(":")[1])
    while len(rest) < clen:
        chunk = s.recv(65536)
        if not chunk:
            break
        rest += chunk
    s.close()
    return status, rest


@pytest.fixture()
def server():
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("EchoService")

    @svc.method()
    def Echo(cntl, request):
        return request

    @svc.method()
    async def AsyncEcho(cntl, request):
        from brpc_tpu import fiber
        await fiber.sleep(0.001)
        return b"async:" + request

    server.add_service(svc)
    ep = server.start("tcp://127.0.0.1:0")
    yield server, ep
    server.stop()
    server.join(2)


class TestHttpPages:
    def test_index(self, server):
        _, ep = server
        status, body = http_get(ep, "/")
        assert status == 200
        assert b"/status" in body and b"EchoService" in body

    def test_health(self, server):
        _, ep = server
        assert http_get(ep, "/health") == (200, b"OK")

    def test_status_json(self, server):
        srv, ep = server
        # generate some traffic first over tpu_std on the same port
        ch = Channel(str(ep))
        assert not ch.call_sync("EchoService", "Echo", b"x").failed()
        status, body = http_get(ep, "/status")
        st = json.loads(body)
        assert status == 200
        assert st["processed"] >= 1
        assert "EchoService" in st["services"]

    def test_vars(self, server):
        _, ep = server
        unexpose_all()
        a = Adder()
        a.add(7)
        a.expose("http_test_var")
        status, body = http_get(ep, "/vars")
        assert status == 200
        assert b"http_test_var : 7" in body
        unexpose_all()

    def test_metrics_prometheus(self, server):
        _, ep = server
        unexpose_all()
        Adder().expose("prom_var")
        status, body = http_get(ep, "/brpc_metrics")
        assert status == 200
        assert b"prom_var 0" in body
        unexpose_all()

    def test_flags_get_and_set(self, server):
        _, ep = server
        from brpc_tpu.butil.flags import flag
        status, body = http_get(ep, "/flags")
        assert status == 200 and b"rpcz_enabled" in body
        status, _ = http_get(ep, "/flags/rpcz_enabled?setvalue=false")
        assert status == 200
        assert flag("rpcz_enabled") is False
        http_get(ep, "/flags/rpcz_enabled?setvalue=true")
        assert flag("rpcz_enabled") is True

    def test_flags_bad_value(self, server):
        _, ep = server
        status, _ = http_get(ep, "/flags/rpcz_max_spans?setvalue=3")
        assert status == 400  # validator requires >= 16

    def test_404(self, server):
        _, ep = server
        status, _ = http_get(ep, "/no/such/page/here")
        assert status == 404

    def test_rpcz_records_spans(self, server):
        _, ep = server
        ch = Channel(str(ep))
        assert not ch.call_sync("EchoService", "Echo", b"traced").failed()
        # the collector is process-global and other tests also run Echo
        # calls: assert OUR call's linked pair exists — some trace id
        # must carry BOTH sides (picking the first server span and first
        # client span independently pairs spans of different calls)
        deadline = time.monotonic() + 2
        linked = False
        while time.monotonic() < deadline and not linked:
            status, body = http_get(ep, "/rpcz?n=200")
            spans = json.loads(body)
            by_tid = {}
            for s in spans:
                if s["method"] == "Echo":
                    by_tid.setdefault(s["trace_id"], set()).add(s["side"])
            linked = any({"server", "client"} <= v
                         for v in by_tid.values())
            if not linked:
                time.sleep(0.05)
        assert linked, "no trace with both client and server Echo spans"


class TestHttpAuth:
    def test_auth_gates_http_side_door(self):
        from brpc_tpu.butil.flags import flag
        server = Server(ServerOptions(enable_builtin_services=False,
                                      auth_token="sekrit"))
        svc = Service("S")
        svc.register_method("Echo", lambda c, r: r)
        server.add_service(svc)
        ep = server.start("tcp://127.0.0.1:0")
        try:
            # no token: RPC access and flag mutation both rejected
            status, _ = http_get(ep, "/S/Echo", b"x")
            assert status == 403
            status, _ = http_get(ep, "/flags/rpcz_enabled?setvalue=false")
            assert status == 403
            assert flag("rpcz_enabled") is True
            # health stays open; token opens the rest
            assert http_get(ep, "/health")[0] == 200
            status, body = http_get(ep, "/S/Echo?token=sekrit", b"x")
            assert (status, body) == (200, b"x")
        finally:
            server.stop(); server.join(2)

    def test_bad_content_length_drops_conn_not_server(self):
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("S")
        svc.register_method("Echo", lambda c, r: r)
        server.add_service(svc)
        ep = server.start("tcp://127.0.0.1:0")
        try:
            s = pysocket.create_connection((ep.host, ep.port), timeout=2)
            s.sendall(b"POST /S/Echo HTTP/1.1\r\nContent-Length: abc\r\n\r\n")
            time.sleep(0.2)
            s.close()
            # the server keeps serving fresh connections
            assert http_get(ep, "/S/Echo", b"ok") == (200, b"ok")
        finally:
            server.stop(); server.join(2)


class TestHttpRpc:
    def test_call_method_raw(self, server):
        _, ep = server
        status, body = http_get(ep, "/EchoService/Echo", b"over http")
        assert status == 200
        assert body == b"over http"

    def test_call_async_method(self, server):
        _, ep = server
        status, body = http_get(ep, "/EchoService/AsyncEcho", b"hi")
        assert status == 200
        assert body == b"async:hi"

    def test_unknown_method(self, server):
        _, ep = server
        status, _ = http_get(ep, "/EchoService/Nope", b"x")
        assert status == 404

    def test_both_protocols_one_port(self, server):
        """tpu_std and http multiplex on the same listener (the
        InputMessenger protocol-sniffing design)."""
        _, ep = server
        ch = Channel(str(ep))
        cntl = ch.call_sync("EchoService", "Echo", b"binary")
        assert not cntl.failed()
        status, body = http_get(ep, "/EchoService/Echo", b"text")
        assert status == 200 and body == b"text"


# ------------------------------------------------- new builtin pages

def test_version_page(server):
    srv, ep = server
    status, body = http_get(ep, "/version")
    assert status == 200
    info = json.loads(body)
    assert info["brpc_tpu"] and info["jax"]


def test_protobufs_page(server):
    srv, ep = server
    status, body = http_get(ep, "/protobufs")
    assert status == 200
    table = json.loads(body)
    assert any(k.startswith("EchoService.") for k in table)
    for entry in table.values():
        assert "request" in entry and "response" in entry


def test_sockets_and_fibers_pages(server):
    srv, ep = server
    status, body = http_get(ep, "/sockets")
    assert status == 200
    rows = json.loads(body)
    assert isinstance(rows, list) and rows        # at least our own conn
    assert {"id", "remote", "failed"} <= set(rows[0])
    status, body = http_get(ep, "/fibers")
    assert status == 200
    fib = json.loads(body)
    assert fib["concurrency"] >= 1
    assert fib["fibers_created"] >= 0


def test_threads_page(server):
    srv, ep = server
    status, body = http_get(ep, "/threads")
    assert status == 200
    assert b"--- thread" in body


def test_ids_page(server):
    srv, ep = server
    status, body = http_get(ep, "/ids")
    assert status == 200
    assert "inflight_client_calls" in json.loads(body)


def test_hotspots_page(server):
    srv, ep = server
    status, body = http_get(ep, "/hotspots?seconds=0.2")
    assert status == 200
    assert b"samples" in body
    status, body = http_get(ep, "/hotspots?seconds=0.2&format=folded")
    assert status == 200


def test_vlog_page(server):
    import logging
    srv, ep = server
    status, _ = http_get(ep, "/vlog?module=test.vlog.mod&level=DEBUG")
    assert status == 200
    assert logging.getLogger("test.vlog.mod").level == logging.DEBUG
    status, body = http_get(ep, "/vlog")
    assert status == 200
    assert json.loads(body)["loggers"].get("test.vlog.mod") == "DEBUG"
    status, _ = http_get(ep, "/vlog?module=test.vlog.mod&level=BOGUS")
    assert status == 400


class TestObservabilityDepth:
    def test_tabbed_index_shell(self, server):
        _, ep = server
        status, body = http_get(ep, "/")
        assert status == 200
        # the tab shell carries every page and the fetch-render script
        for tab in (b"rpcz", b"hotspots", b"contentions", b"vlog"):
            assert tab in body
        assert b"<script>" in body and b"fetch(" in body

    def test_heap_profile_two_phase(self, server):
        _, ep = server
        try:
            status, body = http_get(ep, "/hotspots?type=heap")
            assert status == 200
            if b"STARTED" in body:
                status, body = http_get(ep, "/hotspots?type=heap")
                assert status == 200
            assert b"live traced bytes" in body
        finally:
            # tracing costs ~2x on allocations: stop it for the rest of
            # the suite (the page exposes the same control)
            http_get(ep, "/hotspots?type=heap&stop=1")

    def test_growth_profile(self, server):
        _, ep = server
        try:
            for _ in range(3):   # start tracing -> baseline -> delta
                status, body = http_get(ep, "/hotspots?type=growth")
                assert status == 200
                if b"delta_bytes" in body:
                    break
            assert b"delta_bytes" in body
        finally:
            status, body = http_get(ep, "/hotspots?type=heap&stop=1")
            assert status == 200 and b"STOPPED" in body

    def test_bad_profile_type(self, server):
        _, ep = server
        status, _ = http_get(ep, "/hotspots?type=nope")
        assert status == 400

    def test_rpcz_persistence_roundtrip(self, server, tmp_path):
        from brpc_tpu.butil.flags import set_flag
        _, ep = server
        set_flag("rpcz_dir", str(tmp_path))
        try:
            ch = Channel(str(ep))
            assert not ch.call_sync("EchoService", "Echo",
                                    b"persisted").failed()
            deadline = time.monotonic() + 3
            rows = []
            while time.monotonic() < deadline:
                status, body = http_get(ep, "/rpcz?history=1")
                assert status == 200
                rows = json.loads(body)
                if any(r["method"] == "Echo" for r in rows):
                    break
                time.sleep(0.05)
            assert any(r["method"] == "Echo" for r in rows)
            # filter by trace id through the disk path
            tid = rows[-1]["trace_id"]
            status, body = http_get(
                ep, f"/rpcz?history=1&trace_id={tid}")
            hits = json.loads(body)
            assert hits and all(r["trace_id"] == tid for r in hits)
            ch.close()
        finally:
            set_flag("rpcz_dir", "")

    def test_rpcz_trace_id_accepts_hex_and_decimal(self, server, tmp_path):
        """/rpcz?trace_id= must match both the hex form spans are
        dumped as AND the plain decimal an operator pastes from a log —
        on the in-memory ring and on the history=1 on-disk path."""
        from brpc_tpu.butil.flags import flag, set_flag
        _, ep = server
        saved_enabled = flag("rpcz_enabled")
        set_flag("rpcz_enabled", True)
        set_flag("rpcz_dir", str(tmp_path))
        try:
            ch = Channel(str(ep))
            cntl = ch.call_sync("EchoService", "Echo", b"dual-form")
            assert not cntl.failed()
            hex_id = f"{cntl.trace_id:016x}"
            dec_id = str(cntl.trace_id)
            deadline = time.monotonic() + 3
            while time.monotonic() < deadline:
                status, body = http_get(ep, f"/rpcz?trace_id={hex_id}")
                assert status == 200
                if len(json.loads(body)) >= 2:   # client + server span
                    break
                time.sleep(0.05)
            by_hex = json.loads(body)
            assert len(by_hex) >= 2 \
                and all(s["trace_id"] == hex_id for s in by_hex)
            # decimal spelling: same spans from the ring
            status, body = http_get(ep, f"/rpcz?trace_id={dec_id}")
            assert status == 200
            by_dec = json.loads(body)
            assert {s["span_id"] for s in by_dec} == \
                {s["span_id"] for s in by_hex}
            # and through the on-disk history path, both forms again
            for form in (hex_id, dec_id):
                status, body = http_get(
                    ep, f"/rpcz?history=1&trace_id={form}")
                assert status == 200
                rows = json.loads(body)
                assert rows and all(r["trace_id"] == hex_id
                                    for r in rows), (form, rows)
            # garbage query params are a clean 400, not a 500
            status, _ = http_get(ep, "/rpcz?trace_id=not-an-id")
            assert status == 400
            status, _ = http_get(ep, "/rpcz?n=abc")
            assert status == 400
            ch.close()
        finally:
            set_flag("rpcz_enabled", saved_enabled)
            set_flag("rpcz_dir", "")


def test_tools_rpc_press_drives_server(server):
    """tools/rpc_press as an e2e: load-generate against a live server
    and parse its summary line (the reference exercises its tools the
    same way)."""
    import subprocess
    import sys as _sys
    _, ep = server
    proc = subprocess.run(
        [_sys.executable, "tools/rpc_press.py", f"tcp://{ep.host}:{ep.port}",
         "EchoService", "Echo", "--duration", "1.5", "--fibers", "4",
         "--payload-size", "32"],
        capture_output=True, text=True, timeout=60,
        cwd=__file__.rsplit("/tests", 1)[0])
    assert proc.returncode == 0, proc.stderr[-500:]
    out = proc.stdout
    assert "qps" in out.lower(), out
    # and the run must have produced successful calls
    import re
    m = re.search(r"ok[=:\s]+(\d+)", out.lower())
    assert m and int(m.group(1)) > 0, out


def test_list_page_enumerates_services():
    """/list (builtin/list_service.cpp): services -> methods with
    message type names."""
    import json as _json

    from tests.proto import echo_pb2

    server = Server(ServerOptions())
    svc = Service("ListDemo")

    @svc.method()
    def Raw(cntl, request):
        return request

    svc.register_method("Typed", lambda c, r: echo_pb2.EchoResponse(),
                        request_class=echo_pb2.EchoRequest,
                        response_class=echo_pb2.EchoResponse)
    server.add_service(svc)
    ep = server.start(f"tcp://127.0.0.1:0")
    try:
        status, body = http_get(ep, "/list")
        assert status == 200
        d = _json.loads(body)
        assert d["ListDemo"]["Raw"]["request_type"] == "bytes"
        assert d["ListDemo"]["Typed"]["request_type"] == "EchoRequest"
        assert d["ListDemo"]["Typed"]["response_type"] == "EchoResponse"
    finally:
        server.stop()
        server.join(2)
