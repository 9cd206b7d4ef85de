"""Streaming RPC (brpc/stream.h:103-120, stream_impl.h, SURVEY.md §2.6).

Stream setup piggybacks on a normal RPC (stream ids ride RpcMeta's
stream_settings on the request and response), after which STREAM frames —
meta with stream_settings but neither request nor response — flow both
ways on the same socket with credit-based flow control:

  - each side starts with ``initial_credits`` frames of send budget
  - the receiver returns credits in batches (piggybacked on its own
    frames or as bare credit grants) after delivering frames
  - a writer with no credits parks on a butex until a grant arrives

Device arrays stream over the same device lane as unary RPC. Ordered
delivery comes from the socket's FIFO write queue + per-stream
ExecutionQueue on the receive side (the reference's per-stream
ExecutionQueue write path, SURVEY.md §2.6).
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional

from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.butil.resource_pool import ResourcePool
from brpc_tpu.fiber import ExecutionQueue, global_control
from brpc_tpu.fiber.butex import Butex, WAIT_TIMEOUT
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.tpu_std import (_HDR, MAGIC, _varint, pack_message)

_stream_pool: ResourcePool = ResourcePool()
_stream_pool.insert(None)  # stream id 0 = invalid (proto3 zero default)

DEFAULT_CREDITS = 64
CREDIT_BATCH = 16  # grant credits back every K delivered frames


def _release_stream_slot(sock) -> None:
    """Undo one bind_socket count (stream closed or rebound away)."""
    n = sock.user_data.get("bound_streams", 0)
    if n > 0:
        sock.user_data["bound_streams"] = n - 1


class StreamOptions:
    def __init__(self, on_received: Optional[Callable] = None,
                 initial_credits: int = DEFAULT_CREDITS):
        self.on_received = on_received
        self.initial_credits = initial_credits


class Stream:
    def __init__(self, options: Optional[StreamOptions] = None):
        self.options = options or StreamOptions()
        self.id: int = _stream_pool.insert(self)
        self.peer_id: int = 0
        self.socket = None
        self.closed = False
        self.remote_closed = False
        self._frame_seq = 0
        self._credits = Butex(self.options.initial_credits)
        self._pending_grants = 0
        self._grant_lock = threading.Lock()
        self._recv_q = ExecutionQueue(self._deliver, name=f"stream_{self.id}")
        self._close_cbs: List[Callable] = []
        self._close_lock = threading.Lock()
        from brpc_tpu.fiber.sync import FiberEvent
        self._established = FiberEvent()

    def _on_established(self) -> None:
        """Peer id bound (client: response arrived; server: accept).
        Flush any credit grants deferred while peer_id was unknown."""
        self._established.set()
        with self._grant_lock:
            grant = 0
            if self._pending_grants >= CREDIT_BATCH:
                grant, self._pending_grants = self._pending_grants, 0
        if grant and not self.closed:
            self._send_frame(b"", None, credits=grant, data=False)

    # --------------------------------------------------------------- write
    async def write(self, payload: bytes | IOBuf = b"",
                    device_arrays: Optional[List] = None,
                    timeout_s: Optional[float] = 10.0) -> bool:
        """Send one frame; parks on the credit butex when the window is
        exhausted. Returns False if the stream closed."""
        if self.closed or self.remote_closed:
            return False
        if self.peer_id == 0:
            # establishment still in flight: a frame to stream id 0 would
            # be dropped and its credit lost
            if not await self._established.wait(timeout_s):
                return False
            if self.closed or self.remote_closed:
                return False
        while True:
            # closed check BEFORE acquiring: closure bumps the credit
            # word with a sentinel (so parks short-circuit) — acquiring
            # first would "spend" sentinel credits on a dead stream
            if self.closed or self.remote_closed:
                return False
            v = self._credits.value
            if v > 0 and self._credits.compare_exchange(v, v - 1):
                break
            r = await self._credits.wait(expected=0, timeout_s=timeout_s)
            if r == WAIT_TIMEOUT:
                return False
        self._send_frame(payload, device_arrays)
        return True

    def write_nowait(self, payload: bytes | IOBuf = b"",
                     device_arrays: Optional[List] = None) -> bool:
        """Non-blocking write: fails immediately when out of credits or
        before the stream is established."""
        if self.closed or self.remote_closed or self.peer_id == 0:
            return False
        while True:
            v = self._credits.value
            if v <= 0:
                return False
            if self._credits.compare_exchange(v, v - 1):
                break
        self._send_frame(payload, device_arrays)
        return True

    def _send_frame(self, payload, device_arrays, close: bool = False,
                    credits: int = 0, data: bool = True) -> None:
        if not device_arrays and \
                isinstance(payload, (bytes, bytearray, memoryview)):
            if not isinstance(payload, bytes):
                # normalize ONCE: len(memoryview) counts elements, not
                # bytes, for itemsize > 1 — sizing the header off it
                # would desync the wire
                payload = bytes(payload)
            # fast pack: the meta is fully determined by four small
            # fields — hand-encode it (bit-identical to the pb
            # serializer: ascending field numbers, minimal varints;
            # golden-pinned by tests) instead of building an RpcMeta
            # per frame. stream_id=1, frame_seq=3, credits=4, close=5
            # inside stream_settings (RpcMeta field 6); payload bytes
            # ride zero-copy for big frames.
            inner = b"\x08" + _varint(self.peer_id)
            if data:
                self._frame_seq += 1
                inner += b"\x18" + _varint(self._frame_seq)
            if credits:
                inner += b"\x20" + _varint(credits)
            if close:
                inner += b"\x28\x01"
            meta_bytes = b"\x32" + _varint(len(inner)) + inner
            pl = len(payload)
            hdr = _HDR.pack(MAGIC, len(meta_bytes) + pl,
                            len(meta_bytes)) + meta_bytes
            if pl <= 65536:
                # graftlint: disable=callback-under-lock -- callers may
                # hold their own sender lock for token ORDER (the
                # serving _StreamSender does); Socket.write only queues
                # — it never parks, and failure paths flip flags
                self.socket.write(hdr + payload)
            else:
                wire = IOBuf()
                wire.append(hdr)
                wire.append_user_data(payload)
                # graftlint: disable=callback-under-lock -- see the
                # small-frame branch above: write only queues
                self.socket.write(wire)
            return
        meta = pb.RpcMeta()
        ss = meta.stream_settings
        ss.stream_id = self.peer_id
        if data:
            # frame_seq marks DATA frames (they consume a credit and must
            # be delivered, even with an empty payload); bare credit grants
            # and close frames leave it 0
            self._frame_seq += 1
            ss.frame_seq = self._frame_seq
        if close:
            ss.close = True
        if credits:
            ss.credits = credits
        use_lane = bool(device_arrays) and self.socket.conn.supports_device_lane
        wire, lane = pack_message(meta, payload, device_arrays=device_arrays,
                                  device_lane=use_lane)
        # graftlint: disable=callback-under-lock -- see _send_frame's
        # raw-frame branch: write only queues, sender locks order tokens
        self.socket.write(wire, device_arrays=lane)

    # -------------------------------------------------------------- receive
    def _on_frame(self, msg) -> None:
        ss = msg.meta.stream_settings
        if ss.credits:
            self._credits.fetch_add(ss.credits)
            self._credits.wake_all()
        if ss.close:
            self._remote_close_once()
            return
        if ss.frame_seq:  # DATA frame (possibly empty payload)
            self._recv_q.execute(("frame", msg))

    async def _deliver(self, batch) -> None:
        import inspect
        for kind, msg in batch:
            if kind == "close":
                for cb in self._close_cbs:
                    try:
                        cb(self)
                    except Exception:
                        pass
                continue
            if self.options.on_received is not None:
                try:
                    r = self.options.on_received(self, msg)
                    if inspect.isawaitable(r):
                        await r  # runs in the drainer fiber: stays serial
                except Exception:
                    import logging
                    logging.getLogger("brpc_tpu.rpc").exception(
                        "stream on_received failed")
            with self._grant_lock:
                self._pending_grants += 1
                grant = 0
                if self._pending_grants >= CREDIT_BATCH and self.peer_id:
                    grant, self._pending_grants = self._pending_grants, 0
            if grant and not self.closed:
                self._send_frame(b"", None, credits=grant, data=False)

    # ------------------------------------------------------ socket binding
    def bind_socket(self, sock) -> None:
        """Attach the ESTABLISHED stream's transport socket and
        subscribe to its failure: a peer dying mid-stream must CLOSE
        the stream (fire on_close, wake blocked writers) — the
        reference fails the stream when its connection breaks
        (stream.cpp on the socket's SetFailed path). Only called once
        the stream is established on this socket (server accept /
        client response) — binding on SEND attempts would let a failed
        first attempt kill a stream whose retried setup then succeeds.
        Idempotent per socket; a previous socket's subscription is
        dropped so a long-lived multiplexed socket doesn't accumulate
        dead streams."""
        # track the SUBSCRIBED socket separately from self.socket: the
        # send path plain-assigns self.socket before establishment, so
        # comparing against it would skip the subscription entirely
        prev = getattr(self, "_subscribed_sock", None)
        # streams write frames independently of the response path: the
        # cut-through serving gate must know this socket can interleave.
        # Counted per bound stream and released on close/unbind, so a
        # connection that once carried a stream isn't degraded forever.
        if prev is not sock:
            sock.user_data["bound_streams"] = \
                sock.user_data.get("bound_streams", 0) + 1
            if prev is not None and \
                    not getattr(self, "_slot_released", False):
                _release_stream_slot(prev)
            self._slot_released = False   # the new sock holds a slot
        if prev is sock:
            self.socket = sock
            return
        if prev is not None:
            try:
                prev.off_failed(self._on_socket_failed)
            except AttributeError:
                pass
        self.socket = sock
        self._subscribed_sock = sock
        sock.on_failed(self._on_socket_failed)

    def _on_socket_failed(self, sock) -> None:
        if sock is not self.socket:
            return  # a previous attempt's socket: the stream moved on
        self._remote_close_once()

    def _remote_close_once(self) -> None:
        """Exactly-once remote-closure path shared by the peer's close
        frame and socket failure (they race on shutdown: close frame
        then connection drop is the normal sequence — on_close must not
        double-fire)."""
        with self._close_lock:
            if self.closed or self.remote_closed:
                return
            self.remote_closed = True
        # a remotely-closed stream interleaves no further frames: give
        # back the cut-through slot now — close() releases via the
        # _subscribed_sock pop, which this leaves intact for the
        # failure-subscription cleanup (release and unsubscribe are
        # separate concerns; the pop below guards double release)
        sub = getattr(self, "_subscribed_sock", None)
        if sub is not None and not getattr(self, "_slot_released", False):
            self._slot_released = True
            _release_stream_slot(sub)
        # a nonzero sentinel makes every credit park short-circuit
        # (butex value_changed), so a writer racing this close cannot
        # sleep out its full timeout on a dead stream
        self._credits.fetch_add(1 << 20)
        self._credits.wake_all()
        self._established.set()        # unblock pre-establish waiters
        self._recv_q.execute(("close", None))   # fire on_close callbacks

    # ---------------------------------------------------------------- close
    def close(self) -> None:
        with self._close_lock:
            if self.closed:
                return
            self.closed = True
        if self.socket is not None and self.peer_id and not self.remote_closed:
            try:
                self._send_frame(b"", None, close=True, data=False)
            except Exception:
                pass
        # drop the failure subscription: a long-lived multiplexed socket
        # must not keep dead streams reachable. The subscription lives
        # on _subscribed_sock, which can lag self.socket when the send
        # path plain-assigned a newer socket after binding.
        sub = getattr(self, "_subscribed_sock", None)
        if sub is not None:
            self._subscribed_sock = None
            if not getattr(self, "_slot_released", False):
                self._slot_released = True
                _release_stream_slot(sub)
            try:
                sub.off_failed(self._on_socket_failed)
            except AttributeError:
                pass
        _stream_pool.remove(self.id)
        self._credits.fetch_add(1 << 20)   # short-circuit pending parks
        self._credits.wake_all()

    def on_close(self, cb: Callable) -> None:
        self._close_cbs.append(cb)

    def join_drained(self, timeout_s: float = 5.0) -> bool:
        return self._recv_q.join(timeout_s)


def address_stream(stream_id: int) -> Optional[Stream]:
    return _stream_pool.address(stream_id)


def process_stream_frame(msg, socket) -> None:
    """Dispatch a STREAM frame (called from tpu_std.process)."""
    stream = _stream_pool.address(msg.meta.stream_settings.stream_id)
    if stream is None:
        return  # stream already closed; drop (reference drops too)
    stream._on_frame(msg)


_payload_bytes = None   # client_dispatch.PayloadBytes, bound on first use


class FastStreamMsg:
    """The turbo lane's stream-frame message: payload/attachment are
    plain bytes wearing the documented read surface (to_bytes/size via
    PayloadBytes) — no RpcMeta object, no IOBuf. ``meta`` materializes
    a pb view lazily for the rare consumer that wants it, carrying
    EVERY StreamSettings field the frame had (the classic lane's
    msg.meta does — the lanes must not observably diverge). The
    scanner upholds that contract by DEFERRING any frame whose
    StreamSettings carries a field outside this record's vocabulary
    (need_feedback=true, credits past INT32_MAX): such frames reach
    the classic lane only, so a materialized meta here is always
    faithful (fastcore.cc walk_stream_meta; pinned by
    test_stream.py::TestScannerLaneParity)."""

    __slots__ = ("payload", "attachment", "device_arrays", "_ss")

    def __init__(self, payload, attachment, sid: int, seq: int,
                 credits: int = 0, close: int = 0):
        global _payload_bytes
        if _payload_bytes is None:
            from brpc_tpu.rpc.client_dispatch import PayloadBytes
            _payload_bytes = PayloadBytes
        self.payload = _payload_bytes(payload)
        ab = IOBuf()
        if attachment:
            ab.append(attachment)
        self.attachment = ab
        # frames carrying device payloads always take the classic path
        # (the scanner defers them), so this lane's is empty by contract
        self.device_arrays: list = []
        self._ss = (sid, seq, credits, close)

    @property
    def meta(self):
        m = pb.RpcMeta()
        ss = m.stream_settings
        ss.stream_id = self._ss[0]
        if self._ss[1]:
            ss.frame_seq = self._ss[1]
        if self._ss[2]:
            ss.credits = self._ss[2]
        if self._ss[3]:
            ss.close = True
        return m


def process_stream_frame_fast(sid: int, seq: int, credits: int, close: int,
                              payload: bytes, att: bytes) -> None:
    """Dispatch a scan_frames stream record (turbo lane): the inlined
    twin of Stream._on_frame — keep their semantics in lockstep."""
    stream = _stream_pool.address(sid)
    if stream is None:
        return  # stream already closed; drop (reference drops too)
    if credits:
        stream._credits.fetch_add(credits)
        stream._credits.wake_all()
    if close:
        stream._remote_close_once()
        return
    if seq:  # DATA frame (possibly empty payload)
        stream._recv_q.execute(("frame", FastStreamMsg(payload, att, sid,
                                                       seq, credits,
                                                       close)))


# ------------------------------------------------------------- establishment
def stream_accept(cntl, options: Optional[StreamOptions] = None) -> Optional[Stream]:
    """Server side: accept the stream the client attached to this RPC
    (StreamAccept). Must be called inside the handler."""
    peer_id = getattr(cntl, "_peer_stream_id", 0)
    if not peer_id:
        return None
    s = Stream(options)
    s.peer_id = peer_id
    s.bind_socket(cntl._server_socket)
    s._on_established()
    cntl._accepted_stream = s
    return s
