"""Client-side response processing (ProcessRpcResponse,
policy/baidu_rpc_protocol.cpp:565 -> OnVersionedRPCReturned)."""

from __future__ import annotations

import time

from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.protocol.tpu_std import RpcMessage, unpack_inline_device_arrays
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.controller import address_call, take_call
from brpc_tpu.rpc.span import copy_wake, stamp_first_byte
from brpc_tpu.transport.event_dispatcher import wake_stamps
from brpc_tpu.transport.syscall_stats import (note_rpc_messages as
                                              _note_rpc_messages)


class PayloadBytes(bytes):
    """bytes carrying the read surface response consumers use
    (``to_bytes``/``size``) — the fast response path hands payloads over
    without IOBuf/Block machinery; every documented read works
    identically (it IS bytes)."""

    __slots__ = ()

    def to_bytes(self) -> bytes:
        return self

    @property
    def size(self) -> int:
        return len(self)


def make_client_fast_drain():
    """Build the client-side chunk fast lane (Socket.fast_drain for
    chunk-handoff transports like mem://): pull the writer's exact bytes
    objects, scan_frames them in one C pass, and complete the response
    records through process_response_fast — no portal wrap/view/pop, no
    turbo-lane indirection. Anything that isn't a clean run of fast
    responses re-injects into the portal for the classic machinery.
    Returns None when the extension is unavailable."""
    from brpc_tpu.native import fastcore as _fc_loader
    fc = _fc_loader.get()
    scan = getattr(fc, "scan_frames", None) if fc is not None else None
    from brpc_tpu.protocol.tpu_std import (MAGIC, SMALL_FRAME_MAX,
                                           STREAM_SCAN_MAX)
    if scan is not None:
        try:
            scan(b"", MAGIC, 0, 0, 0, 1)   # materialize support probe
        except TypeError:
            scan = None                    # prebuilt-stale extension
    if scan is None:
        return None
    from brpc_tpu.rpc.stream import process_stream_frame_fast
    from brpc_tpu.transport.socket import pull_chunks as _pull_chunks

    def fast_drain(sock) -> bool:
        if sock.input_portal or sock.input_need:
            return False
        data, handled = _pull_chunks(sock)   # self-disables on fd conns
        if data is None:
            return handled
        consumed, frames = scan(data, MAGIC, SMALL_FRAME_MAX, 128,
                                STREAM_SCAN_MAX, 1)
        if any(f[0] == 0 for f in frames):
            # a request-shaped frame on a client socket: hand the WHOLE
            # run to the classic machinery in parse order (the records
            # don't carry frame starts, so a partial dispatch could not
            # find its cut point)
            sock.input_portal.append_user_data(data)
            return False
        if frames:
            # these completions bypass record_dispatch_batch: stamp the
            # syscalls_per_rpc denominator here (transport/syscall_stats)
            _note_rpc_messages(len(frames))
        for f in frames:
            if f[0] == 2:
                # live stream frame: dispatched in parse order, like
                # the turbo lane
                _, sid, seq, credits, sclose, pay, att = f
                process_stream_frame_fast(sid, seq, credits, sclose,
                                          pay, att)
                continue
            _, cid, ec, et, pay, att = f
            process_response_fast(cid, ec, et, pay, att, sock)
        if consumed == len(data):
            if frames:
                sock.__dict__["_fdrain_defer_streak"] = 0
            return True
        # tail the scanner stopped at (partial frame / slow meta): the
        # classic path judges it from the stop offset — a connection
        # whose responses are ALWAYS slow-shaped stops paying the lane
        if not frames:
            streak = sock.__dict__.get("_fdrain_defer_streak", 0) + 1
            if streak >= 16:
                sock.fast_drain = None
            else:
                sock._fdrain_defer_streak = streak
        sock.input_portal.append_user_data(data[consumed:])
        return False

    return fast_drain


def process_response_fast(cid: int, err_code: int, err_text, payload: bytes,
                          att: bytes, socket) -> None:
    """Complete a call from scan_frames response fields — no RpcMeta
    object, no portal cuts. The scanner guarantees no compression, no
    stream settings, no device payloads; the error path (retry/policy
    interplay) reuses the classic flow via a synthesized message."""
    cntl = address_call(cid)
    if cntl is None:
        return  # stale: the call already completed (timeout/backup winner)
    ch = cntl._owner_channel
    if ch is not None and ch._adm_cache:
        # a response that rode the FAST lane cannot carry an admission
        # threshold (the C scanner defers unknown response-meta fields
        # to the classic parser) — its absence here is therefore
        # definitive: the backend relaxed, clear the cached entry
        ch._track_admission_threshold(socket.remote_endpoint,
                                      cntl._service_name, 0)
    if err_code:
        from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
        meta = pb.RpcMeta()
        meta.correlation_id = cid
        meta.response.error_code = err_code
        meta.response.error_text = err_text or ""
        process_response(None, RpcMessage(meta, IOBuf(), IOBuf()), socket)
        return
    with cntl._arb_lock:
        if take_call(cid) is not cntl:
            return  # raced with timeout/backup completion
    cntl.responded_server = socket.remote_endpoint
    # wire size of the winning response, for the backend stat cell's
    # bytes_in (the completion sweep attributes it to the responder)
    cntl.__dict__["_bs_resp_bytes"] = len(payload) + len(att)
    span = cntl.__dict__.get("_client_span")
    if span is not None:
        # the scan lane's record has no message: its cut was in this
        # callback, if the event loop made it
        stamp_first_byte(span, time.monotonic_ns() // 1000)
        copy_wake(span, wake_stamps())
    try:
        cntl.response_payload = PayloadBytes(payload)
        if cntl.response_msg is not None:
            cntl.response_msg.ParseFromString(payload)
        if att:
            ab = IOBuf()
            ab.append(att)
            cntl.__dict__["response_attachment"] = ab
    except Exception as e:
        cntl.set_failed(berr.ERESPONSE, f"bad response: {e}")
    if span is not None:
        span.parse_done_us = time.monotonic_ns() // 1000
        span.response_size = len(payload)
    cntl._complete()


def process_response(proto, msg: RpcMessage, socket) -> None:
    cid = msg.meta.correlation_id
    # take FIRST: exactly one response/timer wins the call; stale or
    # concurrent losers never touch the controller (the versioned-id
    # arbitration of OnVersionedRPCReturned, controller.cpp:575)
    cntl = address_call(cid)
    if cntl is None:
        return  # stale: the call already completed (timeout/backup winner)
    has_resp = msg.meta.HasField("response")
    ch = cntl._owner_channel
    if ch is not None:
        # DAGOR threshold piggyback: an overloaded backend stamps its
        # admission threshold on every response — cache it so doomed
        # sends fail fast locally (Channel._doomed_by_threshold); a
        # response WITHOUT the stamp means that backend relaxed, so a
        # non-empty cache clears its entry. The calm common case pays
        # one int read (0) + one empty-dict truthiness check.
        thr = msg.meta.response.admission_threshold if has_resp else 0
        if thr or ch._adm_cache:
            ch._track_admission_threshold(socket.remote_endpoint,
                                          cntl._service_name, thr)
    is_error = has_resp and msg.meta.response.error_code != 0
    if is_error:
        code = msg.meta.response.error_code
        text = msg.meta.response.error_text
        channel = getattr(cntl, "_owner_channel", None)
        if channel is not None:
            # policy consult BEFORE the lock: user policy code must not
            # run while the process-wide timer thread can block on
            # cntl._arb_lock in _on_timeout
            allow = channel._policy_allows(cntl, code, text)
            with cntl._arb_lock:
                if take_call(cid) is not cntl:
                    return  # lost to a concurrent winner
                retrying = channel._retry_taken_call(
                    cntl, code, text, socket.remote_endpoint, allow=allow)
            if retrying:
                # re-registered under a fresh correlation id; issue the
                # new attempt outside the lock (connects can block) —
                # through the backoff gate, like every other retry
                channel._launch_retry(cntl, code, text)
                return
            cntl.responded_server = socket.remote_endpoint
            cntl.set_failed(code, text)
            cntl._complete()
            return
    with cntl._arb_lock:
        if take_call(cid) is not cntl:
            return  # raced with timeout/backup completion
    # record the WINNER for LB/breaker attribution: with a backup request
    # in flight, the last-selected server is not necessarily the one
    # whose response completed the call
    cntl.responded_server = socket.remote_endpoint
    # wire size before decompression — the backend cell accounts what
    # the network carried, not what the codec expanded it to
    cntl.__dict__["_bs_resp_bytes"] = msg.payload.size + msg.attachment.size
    span = cntl.__dict__.get("_client_span")
    if span is not None:
        # the frame's cut-time stamp is the closest honest "first
        # response byte" the classic path has (span.h received_us)
        stamp_first_byte(span, (getattr(msg, "arrival_ns", 0)
                                or time.monotonic_ns()) // 1000)
        copy_wake(span, getattr(msg, "wake", None))
    try:
        _fill_response(cntl, msg, socket)
    except Exception as e:
        # the controller is already out of the pool: it MUST complete here
        # or join() hangs forever (e.g. corrupt compressed payload)
        cntl.set_failed(berr.ERESPONSE, f"bad response: {e}")
    if span is not None:
        span.parse_done_us = time.monotonic_ns() // 1000
        span.response_size = msg.payload.size
    cntl._complete()


def _fill_response(cntl, msg: RpcMessage, socket) -> None:
    if msg.meta.HasField("response") and msg.meta.response.error_code != 0:
        cntl.set_failed(msg.meta.response.error_code,
                        msg.meta.response.error_text)
        # (a piggybacked stream is closed by cntl._complete on failure)
    else:
        if msg.meta.compress_type:
            from brpc_tpu.butil.iobuf import IOBuf
            from brpc_tpu.rpc.compress import decompress
            raw = decompress(msg.payload.to_bytes(), msg.meta.compress_type)
            msg.payload = IOBuf()
            msg.payload.append(raw)
        cntl.response_payload = msg.payload
        if cntl.response_msg is not None:
            try:
                cntl.response_msg.ParseFromString(msg.payload.to_bytes())
            except Exception as e:
                cntl.set_failed(berr.ERESPONSE, f"cannot parse response: {e}")
        stream = getattr(cntl, "stream", None)
        if stream is not None and msg.meta.HasField("stream_settings"):
            stream.peer_id = msg.meta.stream_settings.stream_id
            stream.bind_socket(socket)
            stream._on_established()
        if msg.meta.device_payloads:
            inline = unpack_inline_device_arrays(msg)
            lane_iter = iter(msg.device_arrays)
            arrays = []
            for dp, inl in zip(msg.meta.device_payloads, inline):
                arrays.append(inl if dp.inline_bytes else next(lane_iter, None))
            cntl.response_device_arrays = arrays
            dr = getattr(msg, "device_recv", None)
            span = cntl.__dict__.get("_client_span")
            if dr is not None and span is not None:
                # the response's device-recv leg as a child of the
                # client span (shared helper; the server-side twin
                # lives in server_dispatch._process_request_body)
                from brpc_tpu.rpc.span import submit_device_recv_span
                submit_device_recv_span(span, dr)
        cntl.response_attachment = msg.attachment
