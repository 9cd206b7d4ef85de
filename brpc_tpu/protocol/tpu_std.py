"""tpu_std: the native protocol — fixed header + proto meta + payload +
attachment, a re-design of baidu_std framing
(policy/baidu_rpc_protocol.cpp: ParseRpcMessage:95, PackRpcRequest:646,
ProcessRpcRequest:314, ProcessRpcResponse:565).

Wire layout:
    "TRPC" | body_size:u32be | meta_size:u32be | meta | payload | attachment
body_size = meta_size + len(payload) + len(attachment).

Device payloads do NOT serialize into the byte stream on device-capable
transports: meta.device_payloads describes them and the arrays ride the
socket's device lane (write_device_payload / take_device_payload) — the
tpu analogue of RDMA SGEs pointing into registered blocks. On plain byte
transports they are inlined into the attachment (inline_bytes=true).
"""

from __future__ import annotations

import struct
import time
from typing import List, Optional, Tuple

import numpy as np

from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.registry import (
    PARSE_NOT_ENOUGH_DATA, PARSE_OK, PARSE_TRY_OTHERS, Protocol,
    register_protocol,
)
from brpc_tpu.transport import event_dispatcher as _event_dispatcher

MAGIC = b"TRPC"
HEADER_SIZE = 12
_HDR = struct.Struct(">4sII")

# ---------------------------------------------------------- small-call pack
# Hand-encoded protobuf fields for the per-call variable part of RpcMeta.
# The constant part (request submessage: service/method/timeout/auth) is
# serialized ONCE per channel+method and cached; per call we append only
# the correlation_id (field 4, varint) and attachment_size (field 5,
# varint) — wire-identical to a full SerializeToString, at bytes-concat
# cost. The reference pays a full meta pack per call in C++
# (PackRpcRequest, baidu_rpc_protocol.cpp:646); in Python the pb object
# build is the hot cost, so the fast path removes it entirely.
_TAG_CORRELATION_ID = 0x20   # field 4, wire type 0
_TAG_ATTACHMENT_SIZE = 0x28  # field 5, wire type 0

# frames at/under this total size take the single-bytes fast path on BOTH
# wire ends (channel request pack / server response pack); bigger frames
# stay zero-copy IOBuf chains — the fast path's attachment flatten +
# one-allocation assembly would COPY them
SMALL_FRAME_MAX = 32768
# scan_frames additionally admits complete live-stream DATA frames up
# to THIS size (its max_stream_body arg; both lanes pass it). 0 = off:
# the record's payload slice is a memcpy, while the classic path moves
# large payloads as zero-copy IOBuf refs that consumers which only
# size/forward never flatten — measured at parity-to-slightly-worse on
# 256KB frames here (box noise bounds the comparison). Scan admission
# pays off for small frames, where the pb-parse saving dominates.
STREAM_SCAN_MAX = 0


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out += bytes((b | 0x80,))
        else:
            return out + bytes((b,))


def _py_pack_small_frame(meta_prefix: bytes, cid: int, payload: bytes,
                         attachment: bytes = b"",
                         magic: bytes = MAGIC) -> bytes:
    meta = meta_prefix + _TAG_CORRELATION_ID.to_bytes(1, "big") + _varint(cid)
    if attachment:
        meta += _TAG_ATTACHMENT_SIZE.to_bytes(1, "big") + _varint(len(attachment))
    meta_size = len(meta)
    body = meta_size + len(payload) + len(attachment)
    return b"".join((_HDR.pack(magic, body, meta_size), meta, payload,
                     attachment))


# the fastcore extension resolves on FIRST USE, not import (get() may
# compile it — imports must stay cheap); False = not yet resolved
_fc = False


def _resolve_fc():
    global _fc
    from brpc_tpu.native import fastcore as _fastcore
    _fc = _fastcore.get()
    return _fc


def pack_small_frame(meta_prefix: bytes, cid: int, payload: bytes,
                     attachment: bytes = b"",
                     magic: bytes = MAGIC) -> bytes:
    """One-allocation frame assembly for the small-call fast path:
    native (fastcore.cc pack_frame — header + cached meta prefix +
    hand-encoded varint fields + payload + attachment in one memcpy
    pass, no pb object, no IOBuf) with a bit-identical Python twin."""
    fc = _fc
    if fc is False:
        fc = _resolve_fc()
    if fc is not None:
        return fc.pack_frame(magic, meta_prefix, cid, payload, attachment)
    return _py_pack_small_frame(meta_prefix, cid, payload, attachment, magic)


def _py_pack_frame_head(meta_prefix: bytes, cid: int, att_size: int,
                        tail_len: int, magic: bytes = MAGIC) -> bytes:
    meta = meta_prefix + _TAG_CORRELATION_ID.to_bytes(1, "big") + _varint(cid)
    if att_size:
        meta += _TAG_ATTACHMENT_SIZE.to_bytes(1, "big") + _varint(att_size)
    return _HDR.pack(magic, len(meta) + tail_len + att_size,
                     len(meta)) + meta


def pack_frame_head(meta_prefix: bytes, cid: int, att_size: int,
                    tail_len: int, magic: bytes = MAGIC) -> bytes:
    """Header + meta scratch for a BIG frame whose payload/attachment
    ride behind it as zero-copy IOBuf refs (fastcore.cc
    pack_frame_head; bit-identical Python twin). body_size covers
    meta + tail_len + att_size — the caller appends exactly those
    bytes. Kills the per-call prefix+varint byte joins on the
    big-attachment request path and the cut-through response head."""
    fc = _fc
    if fc is False:
        fc = _resolve_fc()
    fn = getattr(fc, "pack_frame_head", None) if fc is not None else None
    if fn is not None:
        return fn(magic, meta_prefix, cid, att_size, tail_len)
    return _py_pack_frame_head(meta_prefix, cid, att_size, tail_len, magic)


class RpcMessage:
    """One parsed tpu_std message."""

    __slots__ = ("meta", "payload", "attachment", "device_arrays",
                 "arrival_ns", "device_recv", "wake")

    def __init__(self, meta: pb.RpcMeta, payload: IOBuf, attachment: IOBuf,
                 device_arrays: Optional[List] = None):
        self.meta = meta
        self.payload = payload
        self.attachment = attachment
        self.device_arrays = device_arrays or []
        # device-lane recv info (peer/lane/recv_us) stamped by the
        # socket's take_device_payload — dispatch hangs a device-recv
        # child span off the server span from it
        self.device_recv = None
        # cut-time stamp: the server-side deadline budget (request
        # timeout_ms) counts from HERE, so dispatch queueing — a burst
        # fanned out to fibers behind busy workers — spends the budget
        # (the reference stamps received_us in InputMessenger the same
        # way; pre-cut kernel/portal buffering is invisible to both)
        self.arrival_ns = time.monotonic_ns()
        # cut inside a callback of the event loop while spans record:
        # the tick's three stamps (event_dispatcher.wake_stamps), which
        # the message's span carries beside the cut's own. Left unset
        # otherwise (readers ask with getattr): a frame a plucking
        # joiner or a fiber cut has no tick. A message the scan lane's
        # record is rebuilt into is made in the callback that scanned it
        if _event_dispatcher.stamping is not None:
            self.wake = _event_dispatcher.wake_stamps()


def cut_message(meta: pb.RpcMeta, payload: IOBuf, attachment: IOBuf,
                socket) -> RpcMessage:
    """The message of a frame just cut, with its lane device arrays
    taken from the socket (every parse site's last step). ``arrival_ns``
    is the cut: the take can wait (pull DMA, recv-pool admission) and
    belongs to what follows the arrival, where its own device-recv span
    times it."""
    msg = RpcMessage(meta, payload, attachment)
    if meta.device_payloads and any(not dp.inline_bytes
                                    for dp in meta.device_payloads):
        lane, msg.device_recv = socket.take_device_payload_with_recv()
        if lane is not None:
            msg.device_arrays = list(lane)
    return msg


def serialize_payload(obj) -> bytes:
    """Shared request/response serialization ladder: bytes-likes pass
    through, IOBufs flatten, protobuf messages serialize."""
    if obj is None:
        return b""
    if isinstance(obj, (bytes, bytearray, memoryview)):
        return bytes(obj)
    if isinstance(obj, IOBuf):
        return obj.to_bytes()
    ser = getattr(obj, "SerializeToString", None)
    if ser is not None:
        return ser()
    raise TypeError(f"cannot serialize payload of type {type(obj)!r}")


def pack_message(meta: pb.RpcMeta, payload: bytes | IOBuf,
                 attachment: Optional[IOBuf] = None,
                 device_arrays: Optional[List] = None,
                 device_lane: bool = False,
                 magic: bytes = MAGIC) -> Tuple[IOBuf, Optional[List]]:
    """Encode a frame. Returns (wire_iobuf, device_arrays_for_lane|None).

    device_arrays: jax/numpy arrays. With device_lane they stay out of the
    byte stream; otherwise their bytes are appended to the attachment.
    """
    user_attachment = attachment if attachment is not None else IOBuf()
    lane = None
    attachment = IOBuf()
    if device_arrays:
        del meta.device_payloads[:]
        for arr in device_arrays:
            dp = meta.device_payloads.add()
            dp.dtype = str(arr.dtype)
            dp.shape.extend(int(s) for s in arr.shape)
            dp.inline_bytes = not device_lane
            nbytes = int(np.prod(arr.shape or (1,))) * arr.dtype.itemsize
            dp.nbytes = nbytes
            if not device_lane:
                host = np.asarray(arr)
                attachment.append(host.tobytes())
        if device_lane:
            lane = list(device_arrays)
    # layout: inline device bytes FIRST, then the user attachment — the
    # receiver front-cuts dp.nbytes per inline payload and what remains is
    # the user attachment (unpack_inline_device_arrays)
    attachment.append_buf(user_attachment)
    meta.attachment_size = len(attachment)
    meta_bytes = meta.SerializeToString()
    if isinstance(payload, IOBuf):
        payload_buf = payload
    else:
        payload_buf = IOBuf()
        payload_buf.append(payload)
    body_size = len(meta_bytes) + payload_buf.size + attachment.size
    out = IOBuf()
    out.append(_HDR.pack(magic, body_size, len(meta_bytes)))
    out.append(meta_bytes)
    out.append_buf(payload_buf)
    out.append_buf(attachment)
    return out, lane


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def unpack_inline_device_arrays(msg: RpcMessage) -> List:
    """Materialize inline device payloads from the attachment bytes."""
    out = []
    buf = msg.attachment
    for dp in msg.meta.device_payloads:
        if dp.inline_bytes:
            raw = buf.cut(dp.nbytes).to_bytes()
            arr = np.frombuffer(raw, dtype=_np_dtype(dp.dtype)).reshape(tuple(dp.shape))
            out.append(arr)
        else:
            out.append(None)  # filled from the device lane by the caller
    return out


class TpuStdProtocol(Protocol):
    name = "tpu_std"
    MAGIC = MAGIC          # subclass variants (hulu/sofa pbrpc) re-magic it
    _scan_fn = False       # scan_frames resolved on first turbo_scan
    _serve_fn = False      # serve_scan resolved on first native_serve

    def frame(self, meta, payload, attachment=None, device_arrays=None,
              device_lane=False):
        """Wire framing for this protocol family; Channel and the server
        dispatch call this so replies match the request's framing."""
        return pack_message(meta, payload, attachment=attachment,
                            device_arrays=device_arrays,
                            device_lane=device_lane, magic=self.MAGIC)

    # ---------------------------------------------------------------- parse
    def parse(self, portal, socket) -> Tuple[str, object]:
        # fast path: header (and usually the whole meta) sits in the
        # portal's contiguous head block — one native probe (fastcore.cc
        # parse_head) replaces peek copies + struct.unpack + slicing
        win = portal.first_host_view()
        meta_bytes = None
        body_size = None
        fc = _fc
        if fc is False:
            fc = _resolve_fc()
        if win is not None and fc is not None:
            r = fc.parse_head(win, self.MAGIC)
            if r == -1:
                # a magic/header mismatch is definitive even on a short
                # window (the C probe compares the available prefix)
                return PARSE_TRY_OTHERS, None
            if r is not None:
                body_size, meta_size, meta_bytes = r
            # r is None: matching prefix shorter than a header — the
            # header may span blocks; decide against the full portal
        if body_size is None:
            if portal.size < HEADER_SIZE:
                head = portal.peek_bytes(min(4, portal.size))
                if self.MAGIC[:len(head)] != head:
                    return PARSE_TRY_OTHERS, None
                return PARSE_NOT_ENOUGH_DATA, None
            magic, body_size, meta_size = _HDR.unpack(
                portal.peek_bytes(HEADER_SIZE))
            if magic != self.MAGIC:
                return PARSE_TRY_OTHERS, None
            if meta_size > body_size:
                return PARSE_TRY_OTHERS, None
        if body_size > 16 << 20:
            # only rare giant frames pay the flag lookup; a body_size
            # beyond max_body_size would otherwise buffer unbounded
            # toward a u32 claim that may never arrive (the reference
            # checks the same limit in ParseRpcMessage)
            from brpc_tpu.butil.flags import flag as _flagf
            if body_size > _flagf("max_body_size"):
                socket.set_failed(ConnectionError(
                    f"frame body {body_size} exceeds max_body_size"))
                return PARSE_NOT_ENOUGH_DATA, None
        if portal.size < HEADER_SIZE + body_size:
            # let the input loop skip re-probing until the frame is here
            socket.input_need = HEADER_SIZE + body_size
            return PARSE_NOT_ENOUGH_DATA, None
        meta = pb.RpcMeta()
        if meta_bytes is not None:
            meta.ParseFromString(meta_bytes)
            portal.pop_front(HEADER_SIZE + meta_size)
        else:
            portal.pop_front(HEADER_SIZE)
            meta.ParseFromString(portal.cut(meta_size).to_bytes())
        att_size = meta.attachment_size
        if att_size < 0 or meta_size + att_size > body_size:
            # a lying attachment_size would eat the next frame's bytes and
            # desync the whole connection: fail it instead
            socket.set_failed(ConnectionError(
                f"frame attachment_size {att_size} exceeds body"))
            return PARSE_NOT_ENOUGH_DATA, None
        payload = portal.cut(body_size - meta_size - att_size)
        attachment = portal.cut(att_size) if att_size else IOBuf()
        return PARSE_OK, cut_message(meta, payload, attachment, socket)

    # ------------------------------------------------------- batch parse
    # frames above this body size take the classic per-frame path (their
    # payloads should stay zero-copy IOBuf refs, not batch copies)
    BATCH_MAX_BODY = 16384

    def batch_parse(self, portal, socket, max_frames: int = 64):
        """Native burst path: one ``bt_trpc_scan`` over the portal's
        contiguous head cuts every complete small frame at once,
        replacing per-message peek/unpack/cut iterations (the
        reference's ProcessNewMessage loop is C++ end to end).

        MEASURED HONESTLY (64-deep pipelined 4B echo, interleaved A/B):
        ~4.2k qps with this path vs ~4.4k without — the ctypes boundary
        plus per-frame Python assembly costs what the scan saves, since
        the per-frame header work it eliminates was already cheap
        (struct.unpack + upb protobuf are C). Default OFF via the
        ``tpu_std_batch_parse`` flag; kept as the wired, tested
        substrate a future C-API (non-ctypes) loop can extend.

        Returns a list of RpcMessage (never empty) when the fast path
        applied, else None — the caller falls back to parse(). Payload
        bytes are COPIED out of the window (small frames only), so the
        read block recycles safely."""
        from brpc_tpu.butil.flags import flag
        if not flag("tpu_std_batch_parse"):
            return None
        if self.MAGIC != MAGIC:
            # subclasses (hulu/sofa) inherit this method but the native
            # scanner only knows the TRPC magic — don't pay a doomed
            # scan + ValueError on every loop iteration for them
            return None
        from brpc_tpu import native
        win = portal.first_host_view()
        if win is None or len(win) < HEADER_SIZE:
            return None
        try:
            res = native.trpc_scan(win, max_frames)
        except ValueError:
            return None          # not (cleanly) TRPC: classic path decides
        if res is None:
            return None          # native lib unavailable
        frames, _consumed, _need = res
        if len(frames) < 2:
            return None          # no burst: classic path is just as fast
        msgs = []
        processed = 0
        for off, total in frames:
            body_size = total - HEADER_SIZE
            if body_size > self.BATCH_MAX_BODY:
                break            # big frame: classic zero-copy path
            meta_size = int.from_bytes(win[off + 8:off + 12], "big")
            meta = pb.RpcMeta()
            meta.ParseFromString(bytes(
                win[off + HEADER_SIZE:off + HEADER_SIZE + meta_size]))
            att_size = meta.attachment_size
            if att_size < 0 or meta_size + att_size > body_size:
                socket.set_failed(ConnectionError(
                    f"frame attachment_size {att_size} exceeds body"))
                break
            p0 = off + HEADER_SIZE + meta_size
            p1 = off + total - att_size
            payload = IOBuf()
            payload.append(bytes(win[p0:p1]))
            attachment = IOBuf()
            if att_size:
                attachment.append(bytes(win[p1:off + total]))
            msgs.append(cut_message(meta, payload, attachment, socket))
            processed = off + total
        if not msgs:
            return None
        portal.pop_front(processed)
        return msgs

    # --------------------------------------------------------- turbo lane
    def turbo_scan(self, portal, socket):
        """The native per-call loop's front half: ONE C call
        (fastcore.cc scan_frames) cuts every complete small fast frame
        out of the portal's contiguous head AND decodes the RpcMeta
        subset dispatch needs — replacing the per-message
        peek/parse_head/upb/cut span (the reference's compiled
        ProcessNewMessage + ParseRpcMessage loop,
        input_messenger.cpp:219-331). Returns dispatch records or None
        (fall back to the classic path). Payload/attachment bytes are
        sliced out before the portal pops, so read blocks recycle
        safely."""
        if type(self) is not TpuStdProtocol:
            return None      # re-magic'd variants keep classic semantics
        scan = self._scan_fn
        if scan is False:
            fc = _fc
            if fc is False:
                fc = _resolve_fc()
            # None when the extension is missing or prebuilt-stale —
            # including one too old for the materialize arg (probed
            # once here, not per drain)
            scan = getattr(fc, "scan_frames", None)
            if scan is not None:
                try:
                    scan(b"", MAGIC, 0, 0, 0, 1)
                except TypeError:
                    scan = None
            self._scan_fn = scan
        if scan is None:
            return None
        win = portal.first_host_view()
        if win is None or len(win) < HEADER_SIZE:
            return None
        # materialize=1: the whole batch's payload/attachment slices
        # happen inside the ONE native call — the records come back
        # dispatch-ready (no per-frame Python slicing), already in
        # turbo_dispatch's field order. Bytes are copied out before
        # the portal pops, so read blocks recycle safely.
        consumed, recs = scan(win, MAGIC, SMALL_FRAME_MAX, 128,
                              STREAM_SCAN_MAX, 1)
        if not recs:
            return None
        # cut-time stamp for the whole scanned run: records that defer
        # to the classic path (rpcz on, timeout-bearing metas) carry it
        # into the synthesized RpcMessage, so the server deadline budget
        # and the span's received_us anchor at the real frame cut
        socket.user_data["_turbo_cut_ns"] = time.monotonic_ns()
        portal.pop_front(consumed)
        return recs

    def native_serve(self, portal, socket) -> bool:
        """Serve the front run of small echo-class requests entirely in
        C (fastcore serve_scan): one native call parses, dispatches and
        prebuilds the response frames; one socket write sends them.
        Applies only to a server's ``native="echo"`` method under the
        same eligibility gates as the turbo lane. Returns True when a
        batch was served (caller loops)."""
        server = socket.user_data.get("server")
        if server is None:
            return False
        tgt = server._native_echo
        if tgt is None or type(self) is not TpuStdProtocol:
            return False
        serve = self._serve_fn
        if serve is False:
            fcm = _fc if _fc is not False else _resolve_fc()
            serve = self._serve_fn = getattr(fcm, "serve_scan", None)
        if serve is None:
            return False     # extension missing or prebuilt-stale
        global _turbo_ok, _flag, _cap_active, _recording
        if _turbo_ok is None:
            from brpc_tpu.butil.flags import flag as _flag
            from brpc_tpu.rpc.span import recording as _recording
            from brpc_tpu.rpc.server_dispatch import (
                _server_turbo_ok as _turbo_ok,
                capture_active as _cap_active)
        if not _turbo_ok(server) or _recording() \
                or _cap_active():
            # capture stands the all-C loop down: serve_scan never
            # crosses the interpreter, so it cannot record — requests
            # fall to the turbo/classic lanes, which capture in-line
            return False
        win = portal.first_host_view()
        if win is None or len(win) < HEADER_SIZE:
            return False
        t0 = time.monotonic_ns()
        consumed, out, n = serve(win, MAGIC, tgt[0], tgt[1],
                                 SMALL_FRAME_MAX)
        if not n:
            return False
        portal.pop_front(consumed)
        socket.write_small(out)
        server.account_native_batch(tgt[2], n,
                                    (time.monotonic_ns() - t0) / 1e3)
        return True

    # ------------------------------------------------------- cut-through
    def try_cut_through(self, portal, socket) -> bool:
        """Large-frame echo serving without assembly: when the portal's
        front is a (possibly partial) LARGE request frame addressed to
        the server's ``native="echo"`` method, the response header+meta
        go out as soon as the request meta parses, and the body forwards
        chunk-by-chunk as it arrives — zero-copy ref moves, every block
        still cache-hot when it leaves (the store-and-forward assembly
        an RPC server normally pays is what separates the raw
        stream-echo ceiling from the raw message-echo ceiling on this
        box). Classic cut-through switching; the reference's RDMA path
        gets the same effect from SGEs posted per block
        (rdma_endpoint.h:82 CutFromIOBufList).

        Frame-safety gate: only while NO other response can interleave
        (pending_responses == 0, no streams bound, write path idle
        frame-wise is guaranteed because responses and this forward all
        run in the input context). Returns True when cut-through mode
        was entered (state lives on the socket; the input loop forwards
        until drained)."""
        server = socket.user_data.get("server")
        if server is None:
            return False
        tgt = server._native_echo
        if tgt is None or type(self) is not TpuStdProtocol:
            return False
        if socket.pending_responses != 0 or \
                socket.user_data.get("bound_streams"):
            return False
        global _turbo_ok, _flag, _cap_active, _recording
        if _turbo_ok is None:
            from brpc_tpu.butil.flags import flag as _flag
            from brpc_tpu.rpc.span import recording as _recording
            from brpc_tpu.rpc.server_dispatch import (
                _server_turbo_ok as _turbo_ok,
                capture_active as _cap_active)
        if not _turbo_ok(server) or _recording() \
                or _cap_active() \
                or not _flag("tpu_std_cut_through"):
            return False
        if portal.size < HEADER_SIZE:
            return False
        magic, body_size, meta_size = _HDR.unpack(
            portal.peek_bytes(HEADER_SIZE))
        if magic != MAGIC or meta_size > body_size:
            return False
        if body_size <= SMALL_FRAME_MAX:
            return False         # small frames: serve_scan territory
        if body_size > 16 << 20:
            from brpc_tpu.butil.flags import flag as _f
            if body_size > _f("max_body_size"):
                return False     # classic path rejects it
        if portal.size < HEADER_SIZE + meta_size:
            return False         # wait for the full meta
        meta = pb.RpcMeta()
        try:
            meta.ParseFromString(
                portal.peek_bytes(HEADER_SIZE + meta_size)[HEADER_SIZE:])
        except Exception:
            return False
        req = meta.request
        if not meta.HasField("request") or meta.HasField("response") \
                or meta.HasField("stream_settings") or meta.device_payloads \
                or meta.compress_type or meta.trace_id \
                or req.auth_token \
                or req.service_name.encode() != tgt[0] \
                or req.method_name.encode() != tgt[1]:
            return False
        att = meta.attachment_size
        pa_len = body_size - meta_size           # payload + attachment
        if att < 0 or att > pa_len:
            return False         # lying size: classic path fails it
        portal.pop_front(HEADER_SIZE + meta_size)
        state = {"remaining": pa_len, "key": tgt[2],
                 "t0": time.monotonic_ns(), "server": server}
        socket.user_data["_cut_forward"] = state
        # response header+meta in ONE native allocation (no Python
        # varint joins), and header + already-arrived body leave in ONE
        # write (a separate header write is its own packet under
        # TCP_NODELAY — an extra syscall here and an extra wakeup on
        # the client)
        head = pack_frame_head(b"", meta.correlation_id, att, pa_len - att)
        self.cut_forward(portal, socket, state, prefix=head)
        return True

    def cut_forward(self, portal, socket, state, prefix=b"") -> bool:
        """Forward arrived body bytes out the response; True when the
        frame completed (mode exits)."""
        n = state["remaining"]
        if portal.size < n:
            n = portal.size
        if n or prefix:
            if n:
                out = portal.cut(n)              # zero-copy ref move
                if prefix:
                    head = IOBuf()
                    head.append(prefix)
                    head.append_buf(out)
                    out = head
            else:
                out = prefix
            socket.write(out)
            state["remaining"] -= n
        if state["remaining"] == 0:
            socket.user_data["_cut_forward"] = None
            state["server"].account_native_batch(
                state["key"], 1,
                (time.monotonic_ns() - state["t0"]) / 1e3)
            return True
        return False

    def turbo_dispatch(self, recs, socket):
        """Dispatch turbo_scan records in parse order; returns an
        optional pending coroutine (a classic-path fallback tail) under
        the same contract as process()."""
        from brpc_tpu.rpc.client_dispatch import process_response_fast
        from brpc_tpu.rpc.server_dispatch import process_request_fast
        from brpc_tpu.rpc.stream import process_stream_frame_fast
        server = socket.user_data.get("server")
        pending = []
        last = len(recs) - 1
        cut_ns = socket.user_data.get("_turbo_cut_ns", 0)
        for i, rec in enumerate(recs):
            if rec[0] == 1:
                process_response_fast(rec[1], rec[2], rec[3], rec[4],
                                      rec[5], socket)
            elif rec[0] == 2:
                # stream frames are order-critical: dispatched here in
                # parse order, like the classic process_inline path
                process_stream_frame_fast(rec[1], rec[2], rec[3],
                                          rec[4], rec[5], rec[6])
            else:
                r = process_request_fast(self, socket, server, rec[1],
                                         rec[2], rec[3], rec[4], rec[5],
                                         rec[6], is_last=(i == last),
                                         arrival_ns=cut_ns)
                if r is not None:
                    pending.append(r)
        if not pending:
            return None
        # same discipline as the classic loop: earlier fallbacks get
        # fresh fibers (under pending_responses claims, so the
        # cut-through gate sees them before any fiber starts) with ONE
        # amortized wake for the whole spill, the last runs in place
        from brpc_tpu.transport.input_messenger import counted_spawn_many
        if len(pending) > 1:
            counted_spawn_many(socket._control, socket, pending[:-1],
                               "process_tpu_std")
        return pending[-1]

    # -------------------------------------------------------------- process
    def process(self, msg: RpcMessage, socket):
        # dispatch to server/client/stream side, like ProcessRpcRequest /
        # ProcessRpcResponse / the streaming_rpc policy; imported lazily to
        # keep layering acyclic
        if msg.meta.HasField("request"):
            from brpc_tpu.rpc.server_dispatch import process_request
            return process_request(self, msg, socket)
        else:
            # pure stream frames never reach here: process_inline consumes
            # them in parse order
            from brpc_tpu.rpc.client_dispatch import process_response
            return process_response(self, msg, socket)

    def process_inline(self, msg: RpcMessage, socket) -> bool:
        meta = msg.meta
        if (meta.HasField("stream_settings") and not meta.HasField("request")
                and not meta.HasField("response") and not meta.correlation_id):
            from brpc_tpu.rpc.stream import process_stream_frame
            # processed here, in parse order, inside the input pass's
            # cut: the event loop's sums are told (as the pass tells
            # them of every other message's processing)
            loop = _event_dispatcher.stamping
            if loop is not None:
                loop.lap(_event_dispatcher.PROCESS)
            process_stream_frame(msg, socket)
            return True
        return False


_turbo_ok = None    # lazily bound server_dispatch._server_turbo_ok
_flag = None        # lazily bound butil.flags.flag
_recording = None   # lazily bound rpc.span.recording
_cap_active = None  # lazily bound server_dispatch.capture_active

_instance: Optional[TpuStdProtocol] = None


def ensure_registered() -> TpuStdProtocol:
    global _instance
    if _instance is None:
        _instance = TpuStdProtocol()
        register_protocol(_instance)
    return _instance
