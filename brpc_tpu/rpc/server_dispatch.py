"""Server-side request processing (ProcessRpcRequest,
policy/baidu_rpc_protocol.cpp:314 -> user service -> SendRpcResponse :139).

Runs inside the fiber the InputMessenger dispatched; the user handler may
be async (awaited in place) or sync.
"""

from __future__ import annotations

import inspect
import os
import time
from typing import Optional

from brpc_tpu.butil.flags import flag
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.fiber.keys import FiberLocal
from brpc_tpu.fiber.scheduler import SchedAwaitable, current_group
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.tpu_std import (
    SMALL_FRAME_MAX, RpcMessage, TpuStdProtocol, pack_message,
    pack_small_frame, serialize_payload, unpack_inline_device_arrays)
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.span import recording
from brpc_tpu.rpc.usercode import note_held, run_usercode


_UNSET = object()
_cap = None   # lazily bound brpc_tpu.traffic.capture (one-time import)


def capture_active() -> bool:
    """Whether the traffic recorder wants requests — the gate the
    all-C serving lanes (serve_drain / serve_scan / cut-through) check:
    those never cross the interpreter per request, so they cannot
    capture and must stand down while recording is on. The Python
    lanes (classic AND turbo) capture in-line instead of standing
    down. Covers the legacy rpc_dump_dir flag alias."""
    global _cap
    if _cap is None:
        from brpc_tpu.traffic import capture as _cap
    return _cap.global_recorder().capture_enabled()

# requests shed with ERPCTIMEDOUT because their client budget was gone
# before handler entry (the tail-at-scale lever: a pod under load must
# not burn cycles on requests whose callers gave up) — /vars
nshed = Adder().expose("server_deadline_shed")

# requests shed with ELIMIT by the overload-control gates — the
# concurrency limiter's admission reject and the queue-delay gate
# below (DAGOR-style: shed early, shed cheaply) — /vars
nlimit_shed = Adder().expose("server_limit_shed")

# requests shed with EPRIORITYSHED by the two-level priority-admission
# threshold (rpc/admission.py): below-threshold work rejected at the
# door while the server is in overload — /vars
npriority_shed = Adder().expose("server_priority_shed")


def _queue_delay_shed(server, arrival_ns: int, level: int = 0,
                      level_counted: bool = False) -> bool:
    """True = this request sat in the dispatch queue past the server's
    queue-delay budget and must be shed NOW (before parse/handler):
    a saturated node rejecting in microseconds beats every caller
    timing out in seconds. Counts from the frame's cut-time stamp —
    the same arrival authority as the deadline gates. A trip is an
    overload signal for the priority-admission controller: the NEXT
    below-threshold request sheds by class instead of by age
    (``level_counted`` = admit_level already tallied this request)."""
    qns = server._queue_shed_ns
    if not qns or not arrival_ns:
        return False
    if time.monotonic_ns() - arrival_ns <= qns:
        return False
    nlimit_shed.add(1)
    adm = server._admission
    if adm is not None:
        adm.signal_overload(level, level_counted)
    return True


def _request_level(priority: int, auth_token: str, socket) -> int:
    """Compose the request's admission level: business priority from
    the wire tag, user sub-priority from the caller's cookie (auth
    token) when present, else the connection identity (the server's
    remote_endpoint IS the client socket's local_endpoint — the
    shared admission.cached_socket_slot keeps both sides' hash in
    lockstep)."""
    from brpc_tpu.rpc.admission import (cached_socket_slot, compose_level,
                                        user_slot)
    slot = user_slot(auth_token) if auth_token \
        else cached_socket_slot(socket, socket.remote_endpoint)
    return compose_level(priority, slot)

# the controller of the request THIS fiber is currently serving —
# nested Channel.call inside a handler reads it to inherit the parent's
# remaining deadline budget (min(own timeout, parent remaining)). Set
# around handler invocation only, cleared in finally: input fibers
# serve many requests over their life and a stale context would clamp
# an unrelated later call.
_serving_cntl = FiberLocal()


def current_serving_controller() -> Optional[Controller]:
    """The server-side Controller whose handler is running on this
    fiber/thread, or None outside a handler. Not propagated into
    ``usercode_in_pthread`` pool threads (those handlers see None)."""
    return _serving_cntl.get()


class _NullSpan:
    """Span stand-in when rpcz is off: field writes are absorbed so the
    dispatch path stays branch-free (the reference skips span creation
    the same way when rpcz is disabled, span.cpp:149)."""

    __slots__ = ()

    def __setattr__(self, name, value):
        pass


_NULL_SPAN = _NullSpan()


def _null_finish_span(span, cntl) -> None:
    pass


class _HopToWorker(SchedAwaitable):
    """Move the current fiber from an inline (non-worker) context onto
    a fiber worker before running potentially-blocking user code."""

    def _register(self, fiber):
        fiber.control.schedule(fiber, None)


def _call_handler(method, cntl, request):
    """The user's handler on the calling thread. A sync one holds that
    thread (a fiber worker) until it returns, and how long is counted
    (``usercode_held_us``); an async one only makes its coroutine."""
    if method.is_coroutine:
        return method.handler(cntl, request)
    held0 = time.monotonic_ns()
    try:
        return method.handler(cntl, request)
    finally:
        note_held(time.monotonic_ns() - held0)


def _track_pending(socket) -> bool:
    """Whether this socket maintains the pending_responses gate at all:
    only sockets serving a native-echo-capable server can ever enter
    cut-through, so everyone else skips the per-request lock pair."""
    t = socket.__dict__.get("_tracks_pending")
    if t is None:
        server = socket.user_data.get("server")
        t = server is not None and server._native_echo is not None
        socket._tracks_pending = t
    return t


def _settle_pending(socket) -> None:
    with socket.pending_lock:
        if socket.pending_responses > 0:
            socket.pending_responses -= 1


# Claim ownership (the cut-through gate's correctness contract): each
# +1 on socket.pending_responses has exactly ONE owner with a
# try/finally settle —
#   * counted_spawn's and counted_run_inline's wrapper (queue-time
#     claim for every spawned message and for a cycle's last message,
#     processed in place, held until its coroutine completes), and
#   * process_request_fast's claim for turbo-driven requests, settled
#     by _drive_fast's finally.
# On both lanes a suspended handler lets the input loop continue
# reading its connection, so the claim must outlive the suspension.
async def process_request(proto, msg: RpcMessage, socket) -> None:
    server = socket.user_data.get("server")
    meta = msg.meta
    cid = meta.correlation_id
    if server is None:
        _send_error(proto, socket, cid, berr.EINTERNAL, "no server bound to socket")
        return
    req_meta = meta.request
    # auth precedes lookup: unauthenticated peers must not be able to
    # enumerate the service/method namespace from distinct error codes.
    # verify once per connection, cache the AuthContext on the socket
    # (authenticator.h: only the first message carries/verifies auth).
    # The resolved Authenticator is cached on the server — per-request
    # resolution sat on the hot path for no benefit (the reference
    # resolves once at Server::Start)
    auth = getattr(server, "_resolved_auth_cache", _UNSET)
    if auth is _UNSET:
        from brpc_tpu.rpc.auth import resolve_server_auth
        auth = resolve_server_auth(server.options)
        server._resolved_auth_cache = auth
    auth_ctx = socket.user_data.get("auth_context")
    if auth is not None and auth_ctx is None:
        from brpc_tpu.rpc.auth import AuthError
        try:
            auth_ctx = auth.verify_credential(req_meta.auth_token,
                                              socket.remote_endpoint)
        except AuthError as e:
            _send_error(proto, socket, cid, berr.ERPCAUTH,
                        str(e) or "authentication failed")
            return
        except Exception:
            _send_error(proto, socket, cid, berr.ERPCAUTH, "authentication failed")
            return
        socket.user_data["auth_context"] = auth_ctx
    method = server.find_method(req_meta.service_name, req_meta.method_name)
    if method is None:
        has_svc = req_meta.service_name in server.services()
        _send_error(proto, socket, cid,
                    berr.ENOMETHOD if has_svc else berr.ENOSERVICE,
                    f"unknown {req_meta.service_name}.{req_meta.method_name}")
        return
    method_key = method.full_name or \
        f"{req_meta.service_name}.{req_meta.method_name}"
    # DAGOR priority admission (before parse/interceptor/handler and
    # before any slot): while the server is in overload, requests whose
    # (business, user) level sits below the adaptive threshold shed
    # with a distinct errno — a µs-cheap reject the client's reject
    # discipline treats as neither breakage nor a retry-token spend
    level = 0
    counted = False
    adm = server._admission
    if adm is not None and adm.threshold_engaged():
        level = _request_level(req_meta.priority, req_meta.auth_token,
                               socket)
        counted = True          # admit_level tallies, pass or shed
        if not adm.admit_level(level):
            npriority_shed.add(1)
            _send_error(proto, socket, cid, berr.EPRIORITYSHED,
                        "priority below admission threshold "
                        "(server overloaded)")
            return
    if _queue_delay_shed(server, getattr(msg, "arrival_ns", 0), level,
                         counted):
        # overload: this request aged past the queue-delay budget before
        # dispatch even saw it — reject before parse, interceptor,
        # handler and before taking a concurrency slot
        _send_error(proto, socket, cid, berr.ELIMIT,
                    "queue delay over shed budget (server overloaded)")
        return
    cost = server.on_request_start(
        method_key, msg.payload.size + msg.attachment.size, level,
        counted)
    if not cost:
        _send_error(proto, socket, cid, berr.ELIMIT, "max_concurrency reached")
        return

    t0 = time.monotonic_ns()
    cntl = Controller()
    d = cntl.__dict__
    # serving context for the WHOLE request residence (parse, shed
    # gates, interceptor, handler, response serialize/write): nested
    # Channel.call reads it for deadline/trace inheritance, and the
    # flight recorder's sampler reads it to attribute this fiber's
    # samples to the method. Cleared in the outermost finally — input
    # fibers serve many requests and a stale context would clamp an
    # unrelated later call.
    _serving_cntl.set(cntl)
    try:
        await _process_request_body(proto, msg, socket, server, method,
                                    method_key, cntl, d, t0, cost)
    finally:
        _serving_cntl.set(None)


async def _process_request_body(proto, msg: RpcMessage, socket, server,
                                method, method_key: str, cntl: Controller,
                                d: dict, t0: int,
                                cost: float = 1.0) -> None:
    meta = msg.meta
    cid = meta.correlation_id
    req_meta = meta.request
    auth_ctx = socket.user_data.get("auth_context")
    # deadline propagation: the wire's timeout_ms is the client's whole
    # budget; it counts from the message's cut-time stamp so dispatch
    # queueing (spawned fibers behind busy workers) spends it. The
    # native lanes DEFER timeout-carrying requests to this path
    # (fastcore.cc walk_request_meta), so this stamp-and-shed is the
    # single server-side deadline authority.
    budget_ms = req_meta.timeout_ms
    if budget_ms > 0:
        d["_deadline_ns"] = (getattr(msg, "arrival_ns", 0) or t0) \
            + budget_ms * 1_000_000
    # zero/empty proto3 defaults match the Controller's class defaults:
    # write only what's actually set (instance-dict writes add up here)
    if meta.trace_id:
        d["trace_id"] = meta.trace_id
    if meta.span_id:
        d["span_id"] = meta.span_id
    if req_meta.log_id:
        d["log_id"] = req_meta.log_id
    if req_meta.priority:
        d["request_priority"] = req_meta.priority
    d["remote_side"] = socket.remote_endpoint
    d["local_side"] = socket.local_endpoint
    if req_meta.auth_token:
        d["auth_token"] = req_meta.auth_token
    if auth_ctx is not None:
        d["auth_context"] = auth_ctx
    d["_service_name"] = req_meta.service_name
    d["_method_name"] = req_meta.method_name
    d["_server_socket"] = socket
    # connection-affinity hint for the flight recorder: transport-side
    # samples (the dispatcher draining this conn's bytes) attribute to
    # the method the conn last served — one attr store per request
    socket.last_method = method_key
    rz = recording()
    if rz:
        from brpc_tpu.rpc.span import (copy_wake, finish_span,
                                       start_server_span)
        span = start_server_span(cntl, req_meta.service_name,
                                 req_meta.method_name)
        # the flight recorder's stall watchdog reaches the ACTIVE span
        # through the serving controller (thread -> fiber -> cntl ->
        # span) to annotate an event-thread monopolization in place
        d["_span"] = span
        span.request_size = msg.payload.size + msg.attachment.size
        # timeline base: the frame's cut-time stamp — latency_us then
        # measures full server residence (arrival -> response flushed),
        # and the received->dispatch gap IS the dispatch queueing a
        # flat start/end span could never show (span.h received_us)
        arrival_us = (getattr(msg, "arrival_ns", 0) or t0) // 1000
        span.received_us = arrival_us
        copy_wake(span, getattr(msg, "wake", None))
        span.start_us = arrival_us
        span.dispatch_us = t0 // 1000
        if current_group() is not None:
            # spilled to a fiber worker: it has had one since dispatch
            span.worker_us = span.dispatch_us
    else:
        span = _NULL_SPAN
        finish_span = _null_finish_span
    if budget_ms > 0 and time.monotonic_ns() >= d["_deadline_ns"]:
        # the client's budget was spent before this request reached
        # dispatch (queued behind busy workers / a pipelined burst):
        # shed it NOW — before parse, interceptor and handler — instead
        # of computing a response nobody is waiting for (Dean & Barroso,
        # The Tail at Scale: expired work amplifies the tail)
        nshed.add(1)
        server.on_request_end(method_key, 0, failed=True, cost=cost)
        cntl.set_failed(berr.ERPCTIMEDOUT,
                        f"deadline {budget_ms}ms expired before dispatch")
        _send_error(proto, socket, cid, berr.ERPCTIMEDOUT,
                    f"deadline {budget_ms}ms expired before dispatch")
        finish_span(span, cntl)   # shed load must show in /rpcz
        cntl.flush_session_kv()
        return
    peer_stream = meta.stream_settings.stream_id   # absent -> 0
    if peer_stream:
        cntl._peer_stream_id = peer_stream
    cntl.request_attachment = msg.attachment
    if meta.device_payloads:
        inline = unpack_inline_device_arrays(msg)
        lane_iter = iter(msg.device_arrays)
        cntl.request_device_arrays = [
            inl if dp.inline_bytes else next(lane_iter, None)
            for dp, inl in zip(meta.device_payloads, inline)]
        dr = getattr(msg, "device_recv", None)
        if rz and dr is not None:
            # the request's device-recv leg as a child of this server
            # span — the receiving half of the sender's stage-resolved
            # device span (shared helper; the client-side twin lives in
            # client_dispatch._fill_response)
            from brpc_tpu.rpc.span import submit_device_recv_span
            submit_device_recv_span(span, dr)

    # decode request payload
    request = None
    cap_rec = None
    try:
        payload_bytes = msg.payload.to_bytes()
        if meta.compress_type:
            from brpc_tpu.rpc.compress import decompress
            payload_bytes = decompress(payload_bytes, meta.compress_type)
            cntl.compress_type = meta.compress_type  # reply in kind
        # capture AFTER decompression so replay re-issues plaintext.
        # Observability must never fail serving: a broken capture dir
        # (perms, disk full) is swallowed here, not turned into
        # EREQUEST. The record completes below with status + latency;
        # a request shed BEFORE this point (deadline/queue gates) is
        # dropped at the door and deliberately not recorded.
        try:
            global _cap
            if _cap is None:
                from brpc_tpu.traffic import capture as _cap
            rec = _cap.global_recorder()
            if rec.capture_enabled():
                # service/method ride as "" — the corpus writer splits
                # the key once per method, so this path never pays the
                # per-request pb string reads
                cap_rec = rec.sample_request(
                    method_key, "", "", payload_bytes, msg.attachment,
                    getattr(msg, "arrival_ns", 0) or t0,
                    req_meta.timeout_ms, req_meta.log_id,
                    req_meta.priority)
        except Exception:
            cap_rec = None
        if method.request_class is not None:
            request = method.request_class()
            request.ParseFromString(payload_bytes)
        else:
            request = payload_bytes
    except Exception as e:
        server.on_request_end(method_key, 0, failed=True, cost=cost)
        cntl.set_failed(berr.EREQUEST, f"cannot parse request: {e}")
        _send_error(proto, socket, cid, berr.EREQUEST, f"cannot parse request: {e}")
        finish_span(span, cntl)  # malformed traffic must show in /rpcz
        if cap_rec is not None:   # malformed is a capture verdict too
            _cap.global_recorder().record_complete(
                cap_rec, berr.EREQUEST,
                (time.monotonic_ns() - t0) / 1e3)
        cntl.flush_session_kv()
        return
    if rz:
        span.parse_done_us = time.monotonic_ns() // 1000

    # interceptor gate (interceptor.h Accept): runs with the decoded
    # request visible on cntl, before the user handler
    interceptor = getattr(server.options, "interceptor", None)
    if interceptor is not None:
        from brpc_tpu.rpc.auth import InterceptorError
        try:
            verdict = interceptor(cntl)
        except InterceptorError as e:
            verdict = (e.error_code, e.reason)
        except Exception as e:
            verdict = (berr.EINTERNAL, f"interceptor error: {e}")
        if verdict is not None:
            code, reason = verdict
            latency_us = (time.monotonic_ns() - t0) / 1e3
            server.on_request_end(method_key, latency_us, failed=True,
                                  cost=cost)
            if cap_rec is not None:   # rejected sessions are corpus too
                _cap.global_recorder().record_complete(cap_rec, code,
                                                   latency_us)
            cntl.set_failed(code, reason)
            _send_error(proto, socket, cid, code, reason)
            finish_span(span, cntl)
            # rejected sessions are the ones operators grep for most:
            # interceptor annotations must still flush (the reference
            # flushes at controller destruction, covering every outcome)
            cntl.flush_session_kv()
            return

    pool = getattr(server, "session_local_pool", None)
    if pool is not None:
        cntl._session_local = pool.borrow()
    response = None
    # (the serving context was installed by process_request for the
    # whole request residence; nested Channel.call inherits through it)
    try:
        if not method.is_coroutine and current_group() is None and \
                not getattr(server.options, "usercode_in_pthread", False):
            # this request is being processed INLINE on a non-worker
            # thread (the event-raising context — socket_inline_process).
            # A sync handler may block, and blocking the caller/dispatcher
            # thread would hijack async call() and stall every other
            # connection — hop to a fiber worker first (the reference
            # never runs user code on the event thread either; its
            # in-place processing happens inside a worker bthread).
            # Async handlers stay inline: suspension converts them to a
            # normal fiber at their first real await.
            await _HopToWorker()
            if rz:
                span.worker_us = time.monotonic_ns() // 1000
        r = None
        if budget_ms > 0 and time.monotonic_ns() >= d["_deadline_ns"]:
            # the hop parked this request behind busy workers long
            # enough to spend the client's whole budget: shed at the
            # last gate before handler entry (the entry-time shed above
            # catches fan-out queueing; this one catches worker-queue
            # delay)
            nshed.add(1)
            cntl.set_failed(berr.ERPCTIMEDOUT,
                            f"deadline {budget_ms}ms expired before "
                            "handler entry")
        elif _queue_delay_shed(server, getattr(msg, "arrival_ns", 0)):
            # the hop parked this request behind busy workers past the
            # queue-delay budget: the last gate before handler entry
            # (the entry-time gate catches fan-out queueing; this one
            # catches worker-queue delay)
            cntl.set_failed(berr.ELIMIT,
                            "queue delay over shed budget before "
                            "handler entry (server overloaded)")
        else:
            if rz:
                span.handler_start_us = time.monotonic_ns() // 1000
            if getattr(server.options, "usercode_in_pthread", False) and \
                    not method.is_coroutine:
                # blocking user code runs on the backup pthread pool;
                # this fiber (and its worker) stays free to pump IO
                r = await run_usercode(method.handler, cntl, request)
            else:
                r = _call_handler(method, cntl, request)
        if inspect.isawaitable(r):
            r = await r
        response = r
    except Exception as e:
        cntl.set_failed(berr.EINTERNAL, f"{type(e).__name__}: {e}")
    finally:
        # handler exit stamp covers the exception path too (a span whose
        # handler raised still shows where the time went)
        if rz and span.handler_start_us and not span.handler_end_us:
            span.handler_end_us = time.monotonic_ns() // 1000
        if pool is not None:
            pool.give_back(cntl._session_local)
            cntl._session_local = None

    latency_us = (time.monotonic_ns() - t0) / 1e3
    server.on_request_end(method_key, latency_us, failed=cntl.failed(),
                          cost=cost)
    if cap_rec is not None:
        # the record carries its verdict: status + latency ride to disk
        # on the recorder's writer thread, never this dispatch fiber
        _cap.global_recorder().record_complete(cap_rec, cntl.error_code,
                                           latency_us)
    # drop cancel subscriptions BEFORE the response leaves: the peer may
    # read the response and close faster than this context runs its
    # post-write cleanup, and a finished request must not hear about
    # that close (notify_on_cancel exists to stop RUNNING work)
    cntl._drop_cancel_subs()
    try:
        _send_response(proto, socket, cid, cntl, response,
                       span=span if rz else None)
    finally:
        # finish in the finally: a response write that throws (peer
        # already gone) must still land the span in /rpcz — the error
        # sessions are exactly the ones operators grep for. With the
        # flush latch armed, submission waits for the write's on_done.
        finish_span(span, cntl)
        # kvmap.h: one greppable line per session — even when the
        # response write throws
        cntl.flush_session_kv()


def _synth_request_msg(cid: int, service: str, method_name: str,
                       log_id: int, payload: bytes, att: bytes,
                       arrival_ns: int = 0) -> RpcMessage:
    """Rebuild a classic RpcMessage from scan_frames fields (the rare
    turbo->classic fallback: unknown method, configured auth, rpcz on).
    ``arrival_ns`` carries the scan lane's cut-time stamp forward so the
    deadline budget and the span's received_us anchor at the real frame
    cut, not at this re-synthesis."""
    meta = pb.RpcMeta()
    meta.correlation_id = cid
    meta.request.service_name = service
    meta.request.method_name = method_name
    if log_id:
        meta.request.log_id = log_id
    meta.attachment_size = len(att)
    p = IOBuf()
    if payload:
        p.append(payload)
    a = IOBuf()
    if att:
        a.append(att)
    msg = RpcMessage(meta, p, a)
    if arrival_ns:
        msg.arrival_ns = arrival_ns
    return msg


def make_fast_drain(server):
    """Build the native per-event serving hook (Socket.fast_drain): ONE
    fastcore serve_drain call reads the readable fd and echo-serves its
    front run — recv, frame cut, meta walk, dispatch match and response
    build never cross the interpreter (the reference's compiled drain +
    in-place serve, socket.cpp:2402 DoRead + input_messenger.cpp:219 +
    baidu_rpc_protocol.cpp:314). Anything the C pass can't judge is
    re-injected into the portal for the classic machinery. Returns None
    when the extension is unavailable."""
    from brpc_tpu.native import fastcore as _fc_loader
    fc = _fc_loader.get()
    sd = getattr(fc, "serve_drain", None) if fc is not None else None
    ss = getattr(fc, "serve_scan", None) if fc is not None else None
    if sd is None or ss is None:
        return None
    from brpc_tpu.protocol.tpu_std import MAGIC
    from brpc_tpu.transport.socket import nreads as _nreads
    from brpc_tpu.transport.socket import pull_chunks as _pull_chunks

    def _defer_streak(sock, served: bool) -> None:
        """Disable the lane for a connection that keeps deferring: a
        tpu_std client that never hits the native-echo method would
        otherwise pay the recv-copy-reinject detour on every event,
        forever. Any served frame resets the streak."""
        if served:
            sock.__dict__["_fdrain_defer_streak"] = 0
            return
        streak = sock.__dict__.get("_fdrain_defer_streak", 0) + 1
        if streak >= 16:
            sock.fast_drain = None
        else:
            sock._fdrain_defer_streak = streak

    def fast_drain(sock) -> bool:
        tgt = server._native_echo
        adm = server._admission
        if tgt is None or not _server_turbo_ok(server) \
                or recording() or capture_active() \
                or (adm is not None and adm.threshold_engaged()) \
                or sock.input_portal or sock.input_need \
                or sock.user_data.get("_cut_forward") is not None:
            # (the admission clause: the all-C echo loop serves without
            # crossing the interpreter, so it can neither judge levels
            # nor piggyback the threshold — while the server is
            # shedding by priority it stands down, like capture)
            return False
        pfd = sock.conn.stream_fd
        if pfd is not None:
            # the pinned dup (Socket.pin_fd_acquire) pins the kernel
            # socket against fd-number recycling mid-recv, amortized
            # over the connection instead of a dup+close per event
            dfd = sock.pin_fd_acquire()
            if dfd < 0:
                return False
            t0 = time.monotonic_ns()
            try:
                r = sd(dfd, MAGIC, tgt[0], tgt[1], SMALL_FRAME_MAX)
            finally:
                sock.pin_fd_release()
            tag = r[0]
            nr = r[-1]            # bytes the C loop read this call
            if nr:
                _nreads.add(nr)   # classic _drain_readable's accounting
            if tag == 0:
                _, out, n, leftover, _nr = r
                sock.write_small(out)
                server.account_native_batch(
                    tgt[2], n, (time.monotonic_ns() - t0) / 1e3)
                _defer_streak(sock, True)
                if leftover:
                    # non-echo tail (pipelined slow frame / partial):
                    # the classic pass judges it with full semantics
                    sock.input_portal.append_user_data(leftover)
                    return False
                return True
            if tag == 1:
                leftover = r[1]
                if leftover:
                    if not MAGIC.startswith(leftover[:4]):
                        # the portal was empty, so these bytes sit at a
                        # frame boundary — a magic mismatch means this
                        # connection speaks another protocol (HTTP,
                        # redis, ...): stop paying the native recv
                        # detour on its every readable event
                        sock.fast_drain = None
                    else:
                        _defer_streak(sock, False)
                    sock.input_portal.append_user_data(leftover)
                    return False
                return True           # spurious wake: nothing arrived
            # tag == 2: EOF/error. With buffered bytes the classic pass
            # processes them first and its next drain re-observes the
            # sticky EOF/error state; with none, fail now (the classic
            # drain's "peer closed" verdict, Socket._drain_readable)
            if r[2]:
                sock.input_portal.append_user_data(r[2])
                return False
            sock.set_failed(ConnectionResetError(r[1]))
            return True
        # chunk-handoff transports (mem://): the writer's exact bytes
        # objects are the stream — serve straight off them, skipping
        # the portal wrap/view/pop round trip entirely
        data, handled = _pull_chunks(sock)
        if data is None:
            return handled
        t0 = time.monotonic_ns()
        consumed, out, n = ss(data, MAGIC, tgt[0], tgt[1], SMALL_FRAME_MAX)
        if n:
            sock.write_small(out)
            server.account_native_batch(
                tgt[2], n, (time.monotonic_ns() - t0) / 1e3)
        if consumed < len(data):
            rest = data[consumed:] if consumed else data
            if not n and not MAGIC.startswith(rest[:4]):
                sock.fast_drain = None    # another protocol: stop here
            else:
                _defer_streak(sock, bool(n))
            sock.input_portal.append_user_data(rest)
            return False
        _defer_streak(sock, bool(n))
        return True

    return fast_drain


def _server_turbo_ok(server) -> bool:
    """Feature gate for the turbo request path, resolved once: servers
    with auth / interceptor / session pools / pthread usercode need the
    classic path's full semantics."""
    ok = server.__dict__.get("_turbo_ok")
    if ok is None:
        from brpc_tpu.rpc.auth import resolve_server_auth
        o = server.options
        ok = (resolve_server_auth(o) is None
              and getattr(o, "interceptor", None) is None
              and getattr(server, "session_local_pool", None) is None
              and not getattr(o, "usercode_in_pthread", False))
        server._turbo_ok = ok
    return ok


async def _drive_fast(proto, socket, server, method, method_key: str,
                      cid: int, service: str, method_name: str,
                      log_id: int, payload: bytes, att: bytes,
                      arrival_ns: int = 0, cost: float = 1.0) -> None:
    """The turbo request body: Controller setup, handler, response —
    the classic process_request minus every branch the scan_frames
    eligibility rules already guarantee can't apply (no auth, no
    interceptor, no compression, no streams, no device payloads, rpcz
    off). Driven by ONE coro.send(None) from process_request_fast, so
    a synchronously-completing handler touches no Fiber at all."""
    if not _track_pending(socket):
        await _drive_fast_inner(proto, socket, server, method, method_key,
                                cid, service, method_name, log_id, payload,
                                att, arrival_ns, cost)
        return
    try:
        await _drive_fast_inner(proto, socket, server, method, method_key,
                                cid, service, method_name, log_id, payload,
                                att, arrival_ns, cost)
    finally:
        # THE single settle of process_request_fast's claim — exactly
        # once, on success and on every escape path alike
        _settle_pending(socket)


async def _drive_fast_inner(proto, socket, server, method, method_key: str,
                            cid: int, service: str, method_name: str,
                            log_id: int, payload: bytes, att: bytes,
                            arrival_ns: int = 0,
                            cost: float = 1.0) -> None:
    t0 = time.monotonic_ns()
    cntl = Controller()
    d = cntl.__dict__
    if log_id:
        d["log_id"] = log_id
    d["remote_side"] = socket.remote_endpoint
    d["local_side"] = socket.local_endpoint
    d["_service_name"] = service
    d["_method_name"] = method_name
    d["_server_socket"] = socket
    if att:
        ab = IOBuf()
        ab.append(att)
        d["request_attachment"] = ab
    # traffic capture, turbo flavor: the scan lane only admits metas
    # with no timeout/priority/auth (the C walker defers the rest to
    # the classic path), so those fields are 0 by construction here.
    # payload/att are already bytes — the sampled path costs one
    # sampling decision + one slots-object allocation.
    cap_rec = None
    if capture_active():
        try:
            cap_rec = _cap.global_recorder().sample_request(
                method_key, "", "", payload, att,
                arrival_ns or t0, 0.0, log_id, 0)
        except Exception:
            cap_rec = None
    request: object = payload
    if method.request_class is not None:
        try:
            request = method.request_class()
            request.ParseFromString(payload)
        except Exception as e:
            server.on_request_end(method_key, 0, failed=True, cost=cost)
            if cap_rec is not None:
                _cap.global_recorder().record_complete(
                    cap_rec, berr.EREQUEST,
                    (time.monotonic_ns() - t0) / 1e3)
            _send_error(proto, socket, cid, berr.EREQUEST,
                        f"cannot parse request: {e}")
            return
    response = None
    try:
        if not method.is_coroutine and current_group() is None:
            # blocking user code must not run on the event thread
            # (same rule as the classic path)
            await _HopToWorker()
        if _queue_delay_shed(server, arrival_ns):
            # the turbo lane's post-hop queue-delay gate (mirrors the
            # classic path): this request aged behind busy workers
            # past the shed budget — reject before the handler runs
            server.on_request_end(method_key, 0, failed=True, cost=cost)
            if cap_rec is not None:
                _cap.global_recorder().record_complete(
                    cap_rec, berr.ELIMIT,
                    (time.monotonic_ns() - t0) / 1e3)
            cntl._drop_cancel_subs()
            _send_error(proto, socket, cid, berr.ELIMIT,
                        "queue delay over shed budget before handler "
                        "entry (server overloaded)")
            return
        r = _call_handler(method, cntl, request)
        if inspect.isawaitable(r):
            r = await r
        response = r
    except Exception as e:
        cntl.set_failed(berr.EINTERNAL, f"{type(e).__name__}: {e}")
    latency_us = (time.monotonic_ns() - t0) / 1e3
    server.on_request_end(method_key, latency_us, failed=cntl.failed(),
                          cost=cost)
    if cap_rec is not None:
        _cap.global_recorder().record_complete(cap_rec, cntl.error_code,
                                           latency_us)
    # before the send: see process_request's twin comment (the peer can
    # close faster than post-write cleanup runs)
    cntl._drop_cancel_subs()
    try:
        # _send_response's own small-frame fast path covers the
        # plain-bytes success shape; one sender, one eligibility ladder
        _send_response(proto, socket, cid, cntl, response)
    finally:
        cntl.flush_session_kv()


def process_request_fast(proto, socket, server, cid: int, service: str,
                         method_name: str, log_id: int, payload: bytes,
                         att: bytes, is_last: bool = True,
                         arrival_ns: int = 0):
    """Dispatch one scan_frames request record. Returns None when fully
    handled (inline completion or adopted continuation), or a classic
    process_request coroutine for the caller to run (fallback cases).

    This is the Python half of the native per-call loop: scan_frames
    already cut the frame and decoded the meta in C; what remains here
    is the method lookup, the handler, and the (native) response pack —
    the reference runs the same span compiled
    (baidu_rpc_protocol.cpp:314 ProcessRpcRequest)."""
    if server is None or not _server_turbo_ok(server) or recording():
        # NOTE: capture no longer bounces this lane to the classic
        # path — the turbo body records in-line (_drive_fast_inner),
        # so the hot lane keeps serving while the recorder runs
        return process_request(
            proto, _synth_request_msg(cid, service, method_name, log_id,
                                      payload, att, arrival_ns), socket)
    method = server.find_method(service, method_name)
    if method is None:
        # error responses here run synchronously in the input context:
        # nothing can interleave, no claim needed
        has_svc = service in server.services()
        _send_error(proto, socket, cid,
                    berr.ENOMETHOD if has_svc else berr.ENOSERVICE,
                    f"unknown {service}.{method_name}")
        return None
    method_key = method.full_name or f"{service}.{method_name}"
    # priority admission, turbo flavor: scan-lane requests carry no
    # priority/auth BY CONSTRUCTION (the C walker defers those metas
    # to the classic lane), so the level is business class 0 + the
    # connection's user slot — below-threshold conns shed here exactly
    # like the classic path (the gate discipline must not depend on
    # which dispatch lane a burst landed in)
    level = 0
    counted = False
    adm = server._admission
    if adm is not None and adm.threshold_engaged():
        level = _request_level(0, "", socket)
        counted = True          # admit_level tallies, pass or shed
        if not adm.admit_level(level):
            npriority_shed.add(1)
            _send_error(proto, socket, cid, berr.EPRIORITYSHED,
                        "priority below admission threshold "
                        "(server overloaded)")
            return None
    if _queue_delay_shed(server, arrival_ns, level, counted):
        # the turbo lane sheds through the same queue-delay gate as the
        # classic path
        _send_error(proto, socket, cid, berr.ELIMIT,
                    "queue delay over shed budget (server overloaded)")
        return None
    cost = server.on_request_start(method_key, len(payload) + len(att),
                                   level, counted)
    if not cost:
        _send_error(proto, socket, cid, berr.ELIMIT,
                    "max_concurrency reached")
        return None
    socket.last_method = method_key   # flight-recorder affinity hint
    if _track_pending(socket):
        # claimed HERE (before the handler can suspend and let the
        # input loop continue); _drive_fast's finally settles it
        with socket.pending_lock:
            socket.pending_responses += 1
    # the fiber is NAMED with the method key: the flight recorder's
    # sampler attributes a turbo-lane sample to its RPC method through
    # the fiber name alone — the slim path never pays a fiber-local set
    coro = _drive_fast(proto, socket, server, method, method_key, cid,
                       service, method_name, log_id, payload, att,
                       arrival_ns, cost)
    if not method.is_coroutine and not is_last:
        # the classic loop's fan-out discipline (QueueMessage,
        # input_messenger.cpp:183): a blocking handler for a non-last
        # burst message gets a fresh fiber, so it can't serialize the
        # burst behind it (async handlers stay inline — suspension is
        # their fan-out)
        socket._control.spawn(coro, name=method_key)
    else:
        # run_inline gives the first leg full fiber context
        # (_tls.current for fiber-locals) and owns the depth cap /
        # suspension parking
        socket._control.run_inline(coro, name=method_key)
    return None


def _send_response(proto, socket, cid: int, cntl: Controller,
                   response, span=None) -> None:
    """``span``: a live rpcz Span to stamp the serialize/flush stages
    on. The flushed_us stamp rides the write's completion callback
    (expect_flush/mark_flushed latch), so a blocked response write —
    saturated peer, chaos delay — shows up as write-stage time instead
    of vanishing between dispatch and /rpcz."""
    on_done = None
    if span is not None:
        from brpc_tpu.rpc.span import expect_flush, mark_flushed
        on_done = lambda err, s=span: mark_flushed(s, err)  # noqa: E731
    # DAGOR threshold piggyback: while this server is shedding by
    # priority, the current admission threshold rides EVERY response
    # (success and shed alike) so senders can fail doomed traffic fast
    # at the source. Calm servers (threshold 0) pay two lookups and
    # keep the wire byte-identical — the field stays absent, and
    # responses stay eligible for the client's native scan lane
    # (which defers unknown response-meta fields to the classic parse,
    # exactly when the threshold needs full semantics).
    adm_thr = 0
    srv = socket.user_data.get("server")
    if srv is not None:
        adm = srv._admission
        if adm is not None:
            adm_thr = adm.wire_threshold()
    # small-call fast path: a successful tpu_std-framed response with no
    # stream/device/progressive sections needs only correlation_id (+
    # attachment_size) in its meta — hand-encoded varints over a single
    # bytes frame, no pb object, no IOBuf
    att = cntl.__dict__.get("response_attachment")
    if (not adm_thr and not cntl.failed() and cntl.compress_type == 0
            and getattr(cntl, "_accepted_stream", None) is None
            and not cntl.__dict__.get("response_device_arrays")
            and type(proto).frame is TpuStdProtocol.frame):
        try:
            payload = serialize_payload(response)
        except TypeError as e:
            cntl.set_failed(berr.EINTERNAL, str(e))
        else:
            if len(payload) + (att.size if att else 0) <= SMALL_FRAME_MAX:
                wire = pack_small_frame(b"", cid, payload,
                                        att.to_bytes() if att else b"",
                                        magic=proto.MAGIC)
                if span is not None:
                    span.response_size = len(payload)
                    span.serialized_us = time.monotonic_ns() // 1000
                    expect_flush(span)
                socket.write_small(wire, on_done=on_done)
                return
            # big response: stay zero-copy (IOBuf chain) below
    meta = pb.RpcMeta()
    meta.correlation_id = cid
    meta.response.error_code = cntl.error_code
    meta.response.error_text = cntl.error_text
    if adm_thr:
        meta.response.admission_threshold = adm_thr
    accepted = getattr(cntl, "_accepted_stream", None)
    if accepted is not None:
        meta.stream_settings.stream_id = accepted.id
    payload = b""
    if not cntl.failed():
        try:
            payload = serialize_payload(response)
            if cntl.compress_type and payload:
                from brpc_tpu.rpc.compress import compress
                payload = compress(payload, cntl.compress_type)
                meta.compress_type = cntl.compress_type
        except TypeError as e:
            meta.response.error_code = berr.EINTERNAL
            meta.response.error_text = str(e)
    use_lane = (bool(cntl.response_device_arrays)
                and socket.conn.supports_device_lane)
    att = IOBuf()
    att.append_buf(cntl.response_attachment)
    framer = getattr(proto, "frame", None)
    if framer is not None:
        wire, lane = framer(meta, payload, attachment=att,
                            device_arrays=cntl.response_device_arrays,
                            device_lane=use_lane)
    else:
        wire, lane = pack_message(meta, payload, attachment=att,
                                  device_arrays=cntl.response_device_arrays,
                                  device_lane=use_lane)
    if span is not None:
        span.response_size = len(payload)
        span.serialized_us = time.monotonic_ns() // 1000
    if span is not None:
        expect_flush(span)
    # a lane batch rides the same queue item as its envelope (see
    # Socket.write); its stage tracker hangs the device span off this
    # request's server span (trace inheritance)
    socket.write(wire, on_done=on_done, device_arrays=lane, span=span)


def _send_error(proto, socket, cid: int, code: int, text: str) -> None:
    cntl = Controller()
    cntl.set_failed(code, text)
    _send_response(proto, socket, cid, cntl, None)
