"""HTTP/1.1 protocol: curl-able observability + JSON access to services
(policy/http_rpc_protocol.cpp + builtin/* — SURVEY.md §2.5, §2.7).

Server side:
  GET  /            index of builtin pages
  GET  /status      server + per-method stats        (StatusService)
  GET  /vars[?prefix=] exposed bvars                 (VarsService)
  GET  /flags       runtime flags; POST /flags/<name>?setvalue=v mutates
  GET  /health      liveness                         (HealthService)
  GET  /connections live connections                 (ConnectionsService)
  GET  /brpc_metrics prometheus text                 (PrometheusMetrics)
  GET  /rpcz[?trace_id=] recent spans                (RpczService)
  POST /<Service>/<Method>  JSON (pb methods) or raw-byte body -> RPC

The parser is peek-based like every protocol here: TRY_OTHERS unless the
bytes start with an HTTP method. pb messages render via protobuf's
json_format (the reference's json2pb bridge)."""

from __future__ import annotations

import json
import time
import urllib.parse
from typing import Optional, Tuple

from brpc_tpu.butil.flags import flag, list_flags, set_flag
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.protocol.registry import (
    PARSE_NOT_ENOUGH_DATA, PARSE_OK, PARSE_TRY_OTHERS, Protocol,
    register_protocol,
)

_METHODS = (b"GET ", b"POST ", b"PUT ", b"DELETE ", b"HEAD ", b"OPTIONS ",
            b"PATCH ")
_MAX_HEADER = 64 * 1024


_FC = False          # unresolved sentinel (None is a valid answer)


def _fastcore():
    """The extension, or None — also None for a stale prebuilt .so that
    predates the http symbols (the loader's fallback contract must hold
    per-symbol, not just per-module). Memoized: the answer cannot
    change within a process."""
    global _FC
    if _FC is False:
        from brpc_tpu.native import fastcore
        m = fastcore.get()
        _FC = m if m is not None and hasattr(m, "http_parse_request") \
            else None
    return _FC


class HttpRequest:
    __slots__ = ("method", "path", "query", "headers", "body", "keep_alive")

    def __init__(self, method, path, query, headers, body, keep_alive):
        self.method = method
        self.path = path
        self.query = query
        self.headers = headers
        self.body = body
        self.keep_alive = keep_alive


def _response(status: int, body: bytes, content_type: str = "text/plain",
              keep_alive: bool = True) -> IOBuf:
    reason = {200: "OK", 400: "Bad Request", 403: "Forbidden",
              404: "Not Found", 405: "Method Not Allowed",
              500: "Internal Server Error"}.get(status, "OK")
    head = (f"HTTP/1.1 {status} {reason}\r\n"
            f"Content-Type: {content_type}\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: {'keep-alive' if keep_alive else 'close'}\r\n"
            f"\r\n").encode()
    out = IOBuf()
    out.append(head)
    out.append(body)
    return out


def _shard_param(agg, req: "HttpRequest"):
    """Parse ?shard=i against the aggregator: (index|None, error|None)
    — None index means 'merged view'; a malformed or out-of-range value
    is a client error, not a silent fallback to merged."""
    raw = req.query.get("shard")
    if raw is None:
        return None, None
    try:
        i = int(raw)
    except ValueError:
        return None, (400, "text/plain", f"bad shard {raw!r}".encode())
    if not 0 <= i < agg.num_shards:
        return None, (400, "text/plain",
                      f"shard {i} out of range 0.."
                      f"{agg.num_shards - 1}".encode())
    return i, None


def _query_flag(req: "HttpRequest", name: str) -> bool:
    """Boolean query param: ?x=1 / ?x=true are on; ?x=0 / ?x=false are
    off (a raw truthy-string check would treat \"0\" as on). Bare keys
    (?x with no value) are dropped by the query parser — spell the
    value out."""
    v = req.query.get(name)
    if v is None:
        return False
    return v.lower() in ("1", "true", "yes")


def _trace_id_candidates(tid: str) -> set:
    """Both readings of a trace id: spans dump ids as 016x hex, but
    operators paste decimal from logs just as often — "123456" is
    ambiguous, so /rpcz matches EITHER reading (a 64-bit random id
    virtually never collides with its other-base twin)."""
    out = set()
    try:
        out.add(int(tid, 16))
    except ValueError:
        pass
    if tid.isdigit():
        out.add(int(tid, 10))
    return out


def _thread_stacks() -> bytes:
    """All OS threads' Python stacks (the /bthreads + /threads pages of
    the reference — here workers ARE pthreads running fibers)."""
    import sys
    import traceback
    frames = sys._current_frames()
    names = {t.ident: t.name for t in __import__("threading").enumerate()}
    out = []
    for tid, frame in frames.items():
        out.append(f"--- thread {tid} ({names.get(tid, '?')}) ---\n")
        out.extend(traceback.format_stack(frame))
        out.append("\n")
    return "".join(out).encode()


class HttpProtocol(Protocol):
    name = "http"

    # ---------------------------------------------------------------- parse
    def parse(self, portal, socket) -> Tuple[str, object]:
        head = portal.peek_bytes(min(8, portal.size))
        if not any(m.startswith(head[:len(m)]) if len(head) < len(m)
                   else head.startswith(m) for m in _METHODS):
            return PARSE_TRY_OTHERS, None
        raw = portal.peek_bytes(min(portal.size, _MAX_HEADER))
        # fast lane: one native pass for head-find + start line + header
        # dict (httpparse.cc — the reference's C http_parser role,
        # details/http_parser.cpp). DEFER (-2) means "only CPython
        # semantics can judge these bytes": fall to the classic parser,
        # so the lanes cannot diverge (differential fuzz:
        # tests/test_http_native.py).
        parsed = None
        ext = _fastcore()
        if ext is not None:
            r = ext.http_parse_request(raw, _MAX_HEADER,
                                       flag("max_body_size"))
            if r is None:
                return PARSE_NOT_ENOUGH_DATA, None
            if isinstance(r, tuple):
                parsed = r
            elif r == -1:
                return PARSE_TRY_OTHERS, None
            # r == -2: defer to the classic lane below
        if parsed is None:
            sep = raw.find(b"\r\n\r\n")
            if sep < 0:
                if portal.size >= _MAX_HEADER:
                    return PARSE_TRY_OTHERS, None  # header flood: drop conn
                return PARSE_NOT_ENOUGH_DATA, None
            header_bytes = raw[:sep]
            lines = header_bytes.split(b"\r\n")
            try:
                method, target, _version = \
                    lines[0].decode("latin1").split(" ", 2)
            except ValueError:
                return PARSE_TRY_OTHERS, None
            headers = {}
            for line in lines[1:]:
                k, _, v = line.decode("latin1").partition(":")
                headers[k.strip().lower()] = v.strip()
            try:
                body_len = int(headers.get("content-length", "0") or "0")
            except ValueError:
                return PARSE_TRY_OTHERS, None  # malformed: drop connection
            if body_len < 0 or body_len > flag("max_body_size"):
                return PARSE_TRY_OTHERS, None
            keep_alive = \
                headers.get("connection", "keep-alive").lower() != "close"
            parsed = (sep + 4, method.upper(), target, body_len,
                      keep_alive, headers)
        # shared tail: both lanes produced the same normalized head
        header_len, method, target, body_len, keep_alive, headers = parsed
        if portal.size < header_len + body_len:
            return PARSE_NOT_ENOUGH_DATA, None
        portal.pop_front(header_len)
        body = portal.cut(body_len).to_bytes()
        split = urllib.parse.urlsplit(target)
        query = dict(urllib.parse.parse_qsl(split.query))
        return PARSE_OK, HttpRequest(method, split.path, query, headers,
                                     body, bool(keep_alive))

    # -------------------------------------------------------------- process
    def process_inline(self, req: HttpRequest, socket) -> bool:
        """HTTP/1.1 requires responses in request order: pipelined
        requests must NOT fan out to concurrent fibers (the
        InputMessenger default)."""
        from brpc_tpu.transport.input_messenger import process_in_parse_order
        process_in_parse_order(socket, "http", req, self.process)
        return True

    async def process(self, req: HttpRequest, socket):
        server = socket.user_data.get("server")
        if server is None:
            socket.write(_response(500, b"no server bound", keep_alive=False))
            return
        try:
            status, ctype, body = await self._route(server, req, socket)
        except Exception as e:
            status, ctype, body = 500, "text/plain", f"error: {e}".encode()
        from brpc_tpu.rpc.progressive import ProgressiveAttachment
        if isinstance(body, ProgressiveAttachment):
            # chunked transfer: headers now, body as the handler feeds it
            conn_hdr = "keep-alive" if req.keep_alive else "close"
            head = (f"HTTP/1.1 {status} OK\r\n"
                    f"Content-Type: {body.content_type}\r\n"
                    f"Transfer-Encoding: chunked\r\n"
                    f"Connection: {conn_hdr}\r\n\r\n").encode()
            out = IOBuf()
            out.append(head)
            socket.write(out)
            body._bind(socket)
            # hold the per-connection drain here until the body completes:
            # a pipelined request behind us would otherwise interleave its
            # response into the open chunked stream
            await body.wait_finished()
            if not req.keep_alive and not socket.failed:
                socket.write(IOBuf(), on_done=lambda ok: socket.set_failed(
                    ConnectionError("http connection: close")))
            return
        if req.keep_alive:
            socket.write(_response(status, body, ctype, True))
        else:
            # close only after the response actually flushes — set_failed
            # right after write() would race the async keep_write fiber
            # and drop the response
            socket.write(
                _response(status, body, ctype, False),
                on_done=lambda ok: socket.set_failed(
                    ConnectionError("http connection: close")))

    # --------------------------------------------------------------- routes
    async def _route(self, server, req: HttpRequest, socket=None):
        from brpc_tpu.rpc.auth import AuthError, resolve_server_auth
        path = req.path.rstrip("/") or "/"
        authenticator = resolve_server_auth(server.options)
        if authenticator is not None and path != "/health":
            # the tpu_std auth gate must not have an HTTP side door: require
            # the credential (Authorization: Bearer ... or ?token=)
            # everywhere except liveness; verified once per connection
            ctx = socket.user_data.get("auth_context") if socket else None
            if ctx is None:
                header = req.headers.get("authorization", "")
                cred = header[7:] if header.startswith("Bearer ") else \
                    req.query.get("token", "")
                try:
                    ctx = authenticator.verify_credential(
                        cred, socket.remote_endpoint if socket else None)
                except AuthError as e:
                    return 403, "text/plain", (
                        str(e) or "authentication failed").encode()
                except Exception:
                    return 403, "text/plain", b"authentication failed"
                if socket is not None:
                    socket.user_data["auth_context"] = ctx
        if path == "/":
            return 200, "text/html", self._index(server)
        if path == "/health":
            reporter = getattr(server.options, "health_reporter", None)
            if reporter is not None:
                # health_reporter.h: the app decides what healthy means
                try:
                    r = reporter(server)
                except Exception as e:
                    return 500, "text/plain", f"health reporter: {e}".encode()
                if isinstance(r, tuple):
                    status, ctype, body = r
                    body = body if isinstance(body, bytes) else str(body).encode()
                    return status, ctype, body
                return 200, "text/plain", (
                    r if isinstance(r, bytes) else str(r).encode())
            return 200, "text/plain", b"OK"
        # shard-group supervisor: /status, /vars and the prometheus dump
        # serve the MERGED view over the per-shard stores; ?shard=i
        # narrows any of them to one worker's snapshot
        agg = getattr(server, "shard_aggregator", None)
        if path == "/status":
            if agg is not None:
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    dump = agg.shard_dump(shard)
                    if dump is None:
                        return (404, "text/plain",
                                f"no dump for shard {shard}".encode())
                    view = dict(dump.get("status", {}))
                    view.update(shard=dump.get("shard"),
                                pid=dump.get("pid"))
                    return 200, "application/json", json.dumps(
                        view, default=str).encode()
                return 200, "application/json", json.dumps(
                    agg.merged_status(), default=str).encode()
            return 200, "application/json", self._status(server)
        if path == "/vars" or path.startswith("/vars/"):
            from brpc_tpu.bvar.variable import dump_exposed
            prefix = req.query.get("prefix", path[6:] if len(path) > 6 else "")
            sname = req.query.get("series")
            if sname is not None:
                # ?series=<name>: that one variable's trend rings as
                # JSON (the /timeline data, scoped to one var — what
                # the inline sparkline links to). Unknown name = 400.
                if agg is not None:
                    merged = agg.merged_timeline(names=[sname])
                    ser = merged.get("series", {}).get(sname)
                else:
                    from brpc_tpu.bvar.series import global_series
                    ser = global_series().dump_series(
                        names=[sname]).get(sname)
                if ser is None:
                    return (400, "text/plain",
                            f"no series for {sname!r}".encode())
                return 200, "application/json", json.dumps(
                    {sname: ser}, default=str).encode()
            if agg is not None:
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    dump = agg.shard_dump(shard)
                    if dump is None:
                        return (404, "text/plain",
                                f"no dump for shard {shard}".encode())
                    items = sorted((n, v)
                                   for n, v in dump.get("vars", {}).items()
                                   if n.startswith(prefix))
                else:
                    items = sorted(agg.merged_vars(prefix).items())
                lines = [f"{n} : {v}" for n, v in items]
            else:
                items = dump_exposed(prefix)
                # inline sparklines: the last minute's trend next to
                # each instant value (only names with a warm ring —
                # merged/shard views stay sparkline-free, their values
                # come from dumps, not the local rings)
                from brpc_tpu.bvar.series import (global_series,
                                                  series_enabled)
                col = global_series() if series_enabled() else None
                lines = []
                for n, v in items:
                    spark = col.spark(n) if col is not None else ""
                    lines.append(f"{n} : {v}  {spark}" if spark
                                 else f"{n} : {v}")
            return 200, "text/plain", ("\n".join(lines) + "\n").encode()
        if path == "/timeline":
            from brpc_tpu.builtin.services import timeline_page_payload
            names = req.query.get("name") or req.query.get("names")
            names = [n for n in names.split(",") if n] if names else None
            tprefix = req.query.get("prefix", "")
            if agg is not None:
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    dump = agg.shard_dump(shard)
                    if dump is None or not dump.get("timeline"):
                        return (404, "text/plain",
                                f"no timeline for shard {shard}"
                                .encode())
                    payload = dict(dump["timeline"])
                    if names or tprefix:
                        payload["series"] = {
                            k: v for k, v in
                            (payload.get("series") or {}).items()
                            if (names is None or k in names)
                            and k.startswith(tprefix)}
                else:
                    payload = agg.merged_timeline(names=names,
                                                  prefix=tprefix)
            else:
                payload = timeline_page_payload(server, names=names,
                                                prefix=tprefix)
            if names:
                missing = [n for n in names
                           if n not in payload.get("series", {})]
                if missing:
                    return (400, "text/plain",
                            f"no series for {missing[0]!r}".encode())
            return 200, "application/json", json.dumps(
                payload, default=str).encode()
        if path == "/brpc_metrics" or path == "/metrics":
            from brpc_tpu.bvar.prometheus import dump_prometheus
            if agg is not None:
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    from brpc_tpu.bvar.prometheus import (
                        dump_prometheus_items)
                    dump = agg.shard_dump(shard)
                    if dump is None:
                        return (404, "text/plain",
                                f"no dump for shard {shard}".encode())
                    return 200, "text/plain", dump_prometheus_items(
                        sorted(dump.get("vars", {}).items())).encode()
                return 200, "text/plain", agg.prometheus_text().encode()
            return 200, "text/plain", dump_prometheus().encode()
        if path == "/shards":
            if agg is None:
                return (404, "text/plain",
                        b"not a shard-group supervisor")
            out = {"shards": agg.num_shards,
                   "heartbeat_age_s": {
                       str(i): agg.heartbeat_age_s(i)
                       for i in range(agg.num_shards)}}
            if agg.group is not None:
                out["group"] = agg.group.group_status()
            return 200, "application/json", json.dumps(
                out, default=str).encode()
        if path == "/flags" or path.startswith("/flags/"):
            return self._flags(req, path)
        if path == "/connections":
            from brpc_tpu.builtin.services import connections_page
            return 200, "application/json", json.dumps(
                connections_page(server), default=str).encode()
        if path == "/backends":
            # per-backend CLIENT telemetry: this process's channels,
            # one row per (channel, backend) stat cell — the data
            # tools/cluster_top.py scrapes and pools across nodes
            from brpc_tpu.rpc.backend_stats import backends_page_payload
            return 200, "application/json", json.dumps(
                backends_page_payload(), default=str).encode()
        if path == "/serving":
            from brpc_tpu.serving.service import serving_page_payload
            if agg is not None:
                # supervisor: merge the shard engines' payloads
                # (counters sum, histograms merge); ?shard=i narrows
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    dump = agg.shard_dump(shard)
                    if dump is None or not dump.get("serving"):
                        return (404, "text/plain",
                                f"no serving dump for shard {shard}"
                                .encode())
                    return 200, "application/json", json.dumps(
                        dump["serving"], default=str).encode()
                return 200, "application/json", json.dumps(
                    agg.merged_serving(), default=str).encode()
            return 200, "application/json", json.dumps(
                serving_page_payload(server), default=str).encode()
        if path == "/device":
            from brpc_tpu.transport.device_stats import device_page_payload
            if agg is not None:
                # supervisor: merge the shard device views (counters
                # sum, latency samples pool); ?shard=i narrows
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    dump = agg.shard_dump(shard)
                    if dump is None or not dump.get("device"):
                        return (404, "text/plain",
                                f"no device dump for shard {shard}"
                                .encode())
                    return 200, "application/json", json.dumps(
                        dump["device"], default=str).encode()
                return 200, "application/json", json.dumps(
                    agg.merged_device(), default=str).encode()
            return 200, "application/json", json.dumps(
                device_page_payload(server), default=str).encode()
        if path == "/lb_trace":
            from brpc_tpu.rpc.backend_stats import lb_trace_payload
            try:
                n = max(1, int(req.query.get("n", "100")))
            except ValueError:
                return (400, "text/plain",
                        f"bad n {req.query.get('n')!r}".encode())
            name = req.query.get("channel")
            payload = lb_trace_payload(name, n)
            if payload is None:
                return (404, "text/plain",
                        f"no decision ring for channel {name!r}".encode())
            return 200, "application/json", json.dumps(
                payload, default=str).encode()
        if path == "/rpcz":
            from brpc_tpu.rpc.span import global_collector, global_store
            tid = req.query.get("trace_id")
            ids = None
            if tid:
                ids = _trace_id_candidates(tid)
                if not ids:
                    return (400, "text/plain",
                            f"bad trace_id {tid!r}".encode())
            try:
                n = max(1, int(req.query.get("n", "50")))
            except ValueError:
                return (400, "text/plain",
                        f"bad n {req.query.get('n')!r}".encode())
            if _query_flag(req, "history"):
                # read back from the on-disk SpanDB analog (rpcz_dir):
                # spans that aged out of the in-memory ring
                rows = global_store.read(n, trace_id=ids)
                return 200, "application/json", json.dumps(rows).encode()
            if ids:
                spans = global_collector.find_trace(ids)
            else:
                spans = global_collector.recent(n)
            return 200, "application/json", json.dumps(
                [s.to_dict() for s in spans]).encode()
        if path == "/list":
            # service/method enumeration with message types
            # (builtin/list_service.cpp)
            out = {}
            for name, s in server.services().items():
                out[name] = {
                    m.name: {
                        "request_type": (m.request_class.__name__
                                         if m.request_class else "bytes"),
                        "response_type": (m.response_class.__name__
                                          if m.response_class else "bytes"),
                    } for m in s.methods.values()
                }
            return 200, "application/json", json.dumps(out).encode()
        if path == "/version":
            import jax
            from brpc_tpu import __version__
            return 200, "application/json", json.dumps({
                "brpc_tpu": __version__, "jax": jax.__version__,
                "server": "brpc-tpu"}).encode()
        if path == "/protobufs":
            return 200, "application/json", self._protobufs(server)
        if path == "/sockets":
            return 200, "application/json", self._sockets(server)
        if path == "/fibers" or path == "/bthreads":
            if _query_flag(req, "stacks"):
                from brpc_tpu.fiber.stacks import dump_fiber_stacks
                return 200, "text/plain", dump_fiber_stacks().encode()
            return 200, "application/json", self._fibers(server)
        if path == "/threads":
            return 200, "text/plain", _thread_stacks()
        if path == "/ids":
            from brpc_tpu.rpc.controller import _call_pool
            return 200, "application/json", json.dumps(
                {"inflight_client_calls": max(0, len(_call_pool) - 1)}
            ).encode()
        if path == "/hotspots" or path == "/pprof/profile":
            return await self._hotspots(req, agg=agg)
        if path == "/census":
            from brpc_tpu.builtin.services import census_page_payload
            if agg is not None:
                # supervisor: the group-wide census (per-shard payloads
                # ride the dumps; counts/bytes sum); ?shard=i narrows
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                if shard is not None:
                    dump = agg.shard_dump(shard)
                    if dump is None or not dump.get("census"):
                        return (404, "text/plain",
                                f"no census for shard {shard}".encode())
                    return 200, "application/json", json.dumps(
                        dump["census"], default=str).encode()
                return 200, "application/json", json.dumps(
                    agg.merged_census(), default=str).encode()
            return 200, "application/json", json.dumps(
                census_page_payload(server), default=str).encode()
        if path == "/capture":
            return self._capture(server, req, agg=agg)
        if path == "/incidents":
            return self._incidents(server, req, agg=agg)
        if path == "/contentions":
            from brpc_tpu.fiber.contention import contention_report
            rows = contention_report(int(req.query.get("n", "30")))
            lines = ["count  total_wait_us  site\n"] + [
                f"{c:6d} {w:13.1f}  {site}\n" for site, c, w in rows]
            return 200, "text/plain", "".join(lines).encode()
        if path == "/vlog":
            return self._vlog(req)
        # /Service/Method RPC access
        parts = [p for p in path.split("/") if p]
        if len(parts) == 2:
            return await self._call_method(server, req, parts[0], parts[1],
                                           socket)
        return 404, "text/plain", f"no such page {req.path}".encode()

    # ------------------------------------------------- introspection pages
    def _incidents(self, server, req: HttpRequest, agg=None):
        """/incidents: capture-on-anomaly state + artifact ledger
        (incident/manager.py), and the artifact download
        (?action=download&path=...). On a shard-group SUPERVISOR the
        state view merges per-shard incident sections (?shard=i
        narrows to one shard's dump) and downloads resolve against
        any shard's ledger."""
        from brpc_tpu.builtin.services import incidents_page_payload
        action = req.query.get("action", "")
        if action == "download":
            from brpc_tpu.incident.artifact import SUFFIX as _INC_SUFFIX
            path = req.query.get("path", "")
            if agg is not None:
                rows = agg.merged_incidents().get("artifacts") or []
            else:
                rows = incidents_page_payload(server).get(
                    "artifacts") or []
            known = {r.get("path") for r in rows}
            # ledger membership IS the authorization: an arbitrary
            # ?path= must not read arbitrary files
            if not path or path not in known \
                    or not path.endswith(_INC_SUFFIX):
                return 404, "text/plain", b"no such incident artifact"
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                return 404, "text/plain", b"artifact unreadable"
            return 200, "application/octet-stream", data
        if action:
            return (400, "text/plain",
                    f"unknown incidents action {action!r}".encode())
        if agg is not None:
            shard, err = _shard_param(agg, req)
            if err is not None:
                return err
            if shard is not None:
                dump = agg.shard_dump(shard)
                if dump is None or not dump.get("incidents"):
                    return (404, "text/plain",
                            f"no incidents for shard {shard}".encode())
                return 200, "application/json", json.dumps(
                    dump["incidents"], default=str).encode()
            return 200, "application/json", json.dumps(
                agg.merged_incidents(), default=str).encode()
        return 200, "application/json", json.dumps(
            incidents_page_payload(server), default=str).encode()

    def _capture(self, server, req: HttpRequest, agg=None):
        """/capture: traffic-recorder state, runtime control
        (?action=start&dir=...&rate=..., ?action=stop) and the merged
        corpus download (?action=download). On a shard-group
        SUPERVISOR, start/stop write the control file the shards apply
        on their next dump tick, the state view merges per-shard
        recorder snapshots, and the download merges every shard's
        per-pid corpus files into one arrival-ordered corpus."""
        from brpc_tpu.builtin.services import (capture_control,
                                               capture_download_bytes,
                                               capture_page_payload)
        action = req.query.get("action", "")
        if agg is not None:
            group = agg.group
            if action in ("start", "stop"):
                if group is None:
                    return (404, "text/plain",
                            b"no supervisor for capture control")
                seq = group.write_capture_control(action, dict(req.query))
                return 200, "application/json", json.dumps(
                    {"control": action, "seq": seq,
                     "applied_within_s": group.options.dump_interval_s},
                    default=str).encode()
            if action == "download":
                data = capture_download_bytes(agg.capture_paths())
                if not data:
                    return 404, "text/plain", b"no captured corpus"
                return 200, "application/octet-stream", data
            if action:
                return (400, "text/plain",
                        f"unknown capture action {action!r}".encode())
            return 200, "application/json", json.dumps(
                agg.merged_capture(), default=str).encode()
        if action in ("start", "stop"):
            try:
                snap = capture_control(action, dict(req.query))
            except (ValueError, OSError) as e:
                return 400, "text/plain", str(e).encode()
            return 200, "application/json", json.dumps(
                snap, default=str).encode()
        if action == "download":
            data = capture_download_bytes()
            if not data:
                return 404, "text/plain", b"no captured corpus"
            return 200, "application/octet-stream", data
        if action:
            return (400, "text/plain",
                    f"unknown capture action {action!r}".encode())
        return 200, "application/json", json.dumps(
            capture_page_payload(server), default=str).encode()

    def _protobufs(self, server) -> bytes:
        out = {}
        for sname, svc in server.services().items():
            for mname, method in svc.methods.items():
                entry = {}
                for side, cls in (("request", method.request_class),
                                  ("response", method.response_class)):
                    if cls is None:
                        entry[side] = "bytes"
                    else:
                        desc = getattr(cls, "DESCRIPTOR", None)
                        entry[side] = desc.full_name if desc else cls.__name__
                        if desc is not None:
                            entry[f"{side}_fields"] = sorted(
                                f.name for f in desc.fields)
                out[f"{sname}.{mname}"] = entry
        return json.dumps(out, indent=1).encode()

    def _sockets(self, server) -> bytes:
        rows = []
        for s in server.connections():
            rows.append({
                "id": s.id,
                "remote": str(s.remote_endpoint) if s.remote_endpoint else None,
                "local": str(s.local_endpoint) if s.local_endpoint else None,
                "failed": s.failed,
                "fail_reason": str(getattr(s, "fail_reason", "") or ""),
                "write_queue": (s._wq.depth()
                                if getattr(s, "_wq", None) is not None else 0),
                "write_queue_bytes": getattr(s, "wq_bytes", 0),
                # claims of writership that sent in place / spawned a
                # keep_write fiber, and the conn family they count under
                "family": s.family,
                "write_inplace": s.write_inplace,
                "write_fiber_spawns": s.write_fiber_spawns,
                "preferred_protocol": s.preferred_protocol,
            })
            # device-lane introspection for ici:// conns (the page the
            # RDMA build exposes per-endpoint window state on)
            conn = s.conn
            if hasattr(conn, "outstanding_batches"):
                rows[-1]["lane_kind"] = conn.lane_kind
                rows[-1]["outstanding_batches"] = conn.outstanding_batches
        return json.dumps(rows, indent=1).encode()

    def _fibers(self, server) -> bytes:
        c = server._control
        return json.dumps({
            "concurrency": c.concurrency,
            "alive_fibers": c.nfibers.get_value(),
            "fibers_created": c.nfibers_created.get_value(),
            "switches_per_group": {g.index: g.nswitches for g in c.groups},
            "steals_per_group": {g.index: g.nsteals for g in c.groups},
            "runqueue_depth": {
                g.index: len(g.rq) + len(g.remote_rq) + len(g.bound_rq)
                for g in c.groups},
        }).encode()

    async def _hotspots(self, req: HttpRequest, agg=None):
        from brpc_tpu.builtin.profiler import (
            growth_profile, heap_profile, heap_stop, render_flamegraph_svg,
            render_folded, render_text)
        from brpc_tpu.fiber.sync import FiberEvent
        ptype = req.query.get("type", "cpu")
        if ptype in ("heap", "growth"):
            # tracemalloc snapshots are quick; no sampler thread needed
            if _query_flag(req, "stop"):
                return 200, "text/plain", heap_stop().encode()
            try:
                top = min(200, int(req.query.get("top", "40")))
            except ValueError:
                return 400, "text/plain", b"bad top"
            text = (heap_profile(top) if ptype == "heap"
                    else growth_profile(top))
            return 200, "text/plain", text.encode()
        if ptype != "cpu":
            return 400, "text/plain", b"type must be cpu|heap|growth"
        fmt = req.query.get("format")
        from brpc_tpu.builtin import flight_recorder as fr
        if req.query.get("mode") == "continuous":
            # the always-on flight recorder: serve the windowed ring,
            # no sample wait. A shard-group SUPERVISOR merges the
            # per-shard recorder states from the dumps (counters sum —
            # the PR 5 aggregation discipline); ?shard=i narrows.
            if agg is not None:
                if _query_flag(req, "diff"):
                    return (400, "text/plain",
                            b"diff is per-process; use ?shard=i on a "
                            b"worker")
                shard, err = _shard_param(agg, req)
                if err is not None:
                    return err
                states = []
                dumps = [agg.shard_dump(shard)] if shard is not None \
                    else agg.read_dumps()
                for d in dumps:
                    if d and d.get("hotspots"):
                        states.append(d["hotspots"])
                m = fr.merge_dump_states(states)
            else:
                rec = fr.global_recorder()
                if _query_flag(req, "diff"):
                    return (200, "text/plain",
                            fr.render_diff_text(rec.window_diff()).encode())
                m = rec.merged()
                from brpc_tpu.transport.event_dispatcher import (
                    stall_ms_max_10s)
                m["stall_ms_max_10s"] = stall_ms_max_10s()
            if fmt == "folded":
                return 200, "text/plain", render_folded(
                    m["folded"]).encode()
            if fmt in ("svg", "flamegraph"):
                return (200, "image/svg+xml",
                        render_flamegraph_svg(m["folded"]).encode())
            if fmt == "json":
                return 200, "application/json", json.dumps({
                    "nsamples": m["nsamples"], "nbusy": m["nbusy"],
                    "windows": m.get("windows"),
                    "span_s": m.get("span_s"),
                    "stall_ms_max_10s": m.get("stall_ms_max_10s"),
                    "labels": dict(m["labels"]),
                    "folded": dict(m["folded"].most_common(200)),
                }).encode()
            return (200, "text/plain",
                    fr.render_continuous_text(m).encode())
        try:
            seconds = min(30.0, float(req.query.get("seconds", "1")))
        except ValueError:
            return 400, "text/plain", b"bad seconds"
        # on-demand profile: the sample loop runs on the flight
        # recorder's sampler thread; THIS handler fiber parks on an
        # event (a worker is never pinned for the sample window), and a
        # concurrent profile is refused with 503 instead of queueing —
        # one profile at a time, like the reference's /hotspots.
        done = FiberEvent()
        result: dict = {}

        def on_done(leaves, folded, n):
            result["v"] = (leaves, folded, n)
            done.set()

        rec = fr.global_recorder()
        if not rec.request_profile(seconds, 0.005, on_done):
            return (503, "text/plain",
                    b"another profile is already running")
        await done.wait(seconds + 30)
        if "v" not in result:
            return 503, "text/plain", b"profile did not complete"
        leaves, folded, n = result["v"]
        if fmt == "folded":
            return 200, "text/plain", render_folded(folded).encode()
        if fmt in ("svg", "flamegraph"):
            return (200, "image/svg+xml",
                    render_flamegraph_svg(folded).encode())
        return 200, "text/plain", render_text(leaves, n).encode()

    def _vlog(self, req: HttpRequest):
        import logging as pylog
        module = req.query.get("module", "")
        level = req.query.get("level")
        vmod = req.query.get("vmodule")
        if vmod is not None:
            # per-module VLOG verbosity (--vmodule): "pat=N,pat=N" or "N"
            from brpc_tpu.butil.logging import set_vmodule
            try:
                set_vmodule(vmod)
            except ValueError as e:
                return 400, "text/plain", f"bad vmodule: {e}".encode()
            return 200, "text/plain", b"OK"
        if level is not None:
            try:
                pylog.getLogger(module or None).setLevel(level.upper())
            except ValueError as e:
                return 400, "text/plain", f"bad level: {e}".encode()
            return 200, "text/plain", b"OK"
        from brpc_tpu.butil.logging import vmodule
        loggers = {"root": pylog.getLevelName(pylog.getLogger().level)}
        for name in sorted(pylog.root.manager.loggerDict):
            lg = pylog.root.manager.loggerDict[name]
            if isinstance(lg, pylog.Logger) and lg.level != pylog.NOTSET:
                loggers[name] = pylog.getLevelName(lg.level)
        return 200, "application/json", json.dumps(
            {"loggers": loggers, "vmodule": vmodule()}).encode()

    def _index(self, server) -> bytes:
        from brpc_tpu.builtin.tabbed import render_index
        return render_index(server)

    def _status(self, server) -> bytes:
        from brpc_tpu.builtin.services import status_page
        return json.dumps(status_page(server), default=str).encode()

    def _flags(self, req: HttpRequest, path: str):
        if path.startswith("/flags/") and ("setvalue" in req.query
                                           or req.method == "POST"):
            name = path[len("/flags/"):]
            value = req.query.get("setvalue", req.body.decode() or "")
            if set_flag(name, value):
                return 200, "text/plain", b"OK"
            return 400, "text/plain", f"cannot set flag {name!r}".encode()
        rows = [f"{n} = {v!r} (default {d!r})  # {h}"
                for n, v, d, h in list_flags()]
        return 200, "text/plain", ("\n".join(rows) + "\n").encode()

    async def _call_method(self, server, req: HttpRequest, service: str,
                           method_name: str, socket=None):
        method = server.find_method(service, method_name)
        if method is None:
            return 404, "text/plain", b"no such service/method"
        from brpc_tpu.rpc.controller import Controller
        cntl = Controller()
        cntl.remote_side = socket.remote_endpoint if socket else None
        cntl._service_name = service
        cntl._method_name = method_name
        if socket is not None:
            cntl.auth_context = socket.user_data.get("auth_context")
        if method.request_class is not None:
            from google.protobuf import json_format
            request = method.request_class()
            if req.body:
                try:
                    json_format.Parse(req.body.decode(), request)
                except Exception as e:
                    return 400, "text/plain", f"bad json: {e}".encode()
        else:
            request = req.body
        # cost rides to on_request_end: weighted limiter slots must
        # release what they charged (rpc/admission.CostModel)
        cost = server.on_request_start(f"{service}.{method_name}",
                                       len(req.body or b""))
        if not cost:
            return 500, "text/plain", b"max_concurrency reached"
        interceptor = getattr(server.options, "interceptor", None)
        if interceptor is not None:
            from brpc_tpu.rpc.auth import InterceptorError
            try:
                verdict = interceptor(cntl)
            except InterceptorError as e:
                verdict = (e.error_code, e.reason)
            except Exception as e:
                verdict = (500, f"interceptor error: {e}")
            if verdict is not None:
                server.on_request_end(f"{service}.{method_name}", 0,
                                      True, cost)
                return 403, "text/plain", str(verdict[1]).encode()
        t0 = time.monotonic_ns()
        try:
            import inspect
            r = method.handler(cntl, request)
            if inspect.isawaitable(r):
                r = await r  # we run inside the dispatch fiber
            response = r
        except Exception as e:
            server.on_request_end(f"{service}.{method_name}",
                                  (time.monotonic_ns() - t0) / 1e3, True,
                                  cost)
            return 500, "text/plain", f"handler error: {e}".encode()
        server.on_request_end(f"{service}.{method_name}",
                              (time.monotonic_ns() - t0) / 1e3,
                              cntl.failed(), cost)
        if cntl.failed():
            # honor the cntl.set_failed error pattern over HTTP too
            from brpc_tpu.rpc import errno_codes as berr
            status = 400 if cntl.error_code == berr.EREQUEST else 500
            return (status, "text/plain",
                    f"[{cntl.error_code}] {cntl.error_text}".encode())
        if cntl._progressive is not None:
            # body arrives in chunks after the handler (progressive
            # attachment); process() writes the chunked headers
            return 200, cntl._progressive.content_type, cntl._progressive
        if response is None:
            return 200, "application/json", b"{}"
        if hasattr(response, "SerializeToString") and not isinstance(
                response, (bytes, bytearray)):
            from google.protobuf import json_format
            return (200, "application/json",
                    json_format.MessageToJson(response).encode())
        if isinstance(response, IOBuf):
            return 200, "application/octet-stream", response.to_bytes()
        return 200, "application/octet-stream", bytes(response)


_instance: Optional[HttpProtocol] = None


def ensure_registered() -> HttpProtocol:
    global _instance
    if _instance is None:
        _instance = HttpProtocol()
        register_protocol(_instance)
    return _instance
