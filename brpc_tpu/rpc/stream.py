"""Streaming RPC (brpc/stream.h:103-120, stream_impl.h, SURVEY.md §2.6).

Stream setup piggybacks on a normal RPC (stream ids ride RpcMeta's
stream_settings on the request and response), after which STREAM frames —
meta with stream_settings but neither request nor response — flow both
ways on the same socket with credit-based flow control:

  - each side starts with ``initial_credits`` frames of send budget
    (frames, whatever a frame weighs; upstream counts bytes)
  - the receiver returns credits as bare grant frames, one after every
    ``CREDIT_BATCH`` frames delivered; nothing rides on its data frames
  - the frame that takes a writer's last credit says ``need_feedback``
    (upstream's name for a writer that wants to hear of consumption),
    and the receiver grants what it holds once that frame is delivered:
    a window smaller than ``CREDIT_BATCH`` would never be granted back
  - a writer with no credits parks on a butex until a grant arrives

Device arrays stream over the same device lane as unary RPC. Ordered
delivery comes from the socket's FIFO write queue + per-stream
ExecutionQueue on the receive side (the reference's per-stream
ExecutionQueue write path, SURVEY.md §2.6).

Always-on counts per stream (``Stream.counters()``) and summed over the
process as ``stream_*`` bvars; while ``span.recording()`` every data
frame leaves one rpcz span a side (``span.FrameSpan``), joined by the
receiving stream's id and ``frame_seq``.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.butil.resource_pool import ResourcePool
from brpc_tpu.bvar.reducer import PassiveStatus
from brpc_tpu.fiber import ExecutionQueue, global_control
from brpc_tpu.fiber.butex import Butex, WAIT_TIMEOUT
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.tpu_std import (_HDR, MAGIC, _varint, pack_message)
from brpc_tpu.rpc import span as _span
from brpc_tpu.transport import event_dispatcher as _event_dispatcher

_stream_pool: ResourcePool = ResourcePool()
_stream_pool.insert(None)  # stream id 0 = invalid (proto3 zero default)

DEFAULT_CREDITS = 64
CREDIT_BATCH = 16  # grant credits back every K delivered frames

# what a stream counts, always on. ``ctrl`` frames carry no data: bare
# credit grants, and one close frame a stream. A credit park is a writer
# that found no credit and waited for a grant; ``ungranted_frames_max``
# is the most frames a writer had out with their credits not yet granted
# back, ``recv_queue_depth_max`` the most frames cut and not yet through
# ``on_received``
COUNTS = ("data_frames_out", "data_frames_in", "host_bytes_out",
          "host_bytes_in", "device_bytes_out", "device_bytes_in",
          "ctrl_frames_out", "ctrl_frames_in", "credit_parks",
          "credit_park_us")
MAXIMA = ("ungranted_frames_max", "recv_queue_depth_max")


class StreamCounters:
    """One stream's counts. Each field has one writer at a time (the
    stream's writer, its socket's reader or the drainer fiber), as
    ``_frame_seq`` has, so plain ints do; a frame pays a few slot
    increments and nothing process-wide."""

    __slots__ = COUNTS + MAXIMA + ("delivered",)

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, 0)

    def fold(self, other: "StreamCounters") -> None:
        for name in COUNTS:
            setattr(self, name, getattr(self, name) + getattr(other, name))
        for name in MAXIMA:
            setattr(self, name, max(getattr(self, name),
                                    getattr(other, name)))


# the process-wide sums: the open streams' counts, read when asked for,
# over those of the streams closed since the process began
_open_counts: dict = {}         # stream id -> its StreamCounters
_closed_counts = StreamCounters()
_counts_lock = threading.Lock()


def counters_snapshot() -> dict:
    """The process-wide sums since start, by the names of COUNTS and
    MAXIMA."""
    total = StreamCounters()
    with _counts_lock:
        total.fold(_closed_counts)
        for counts in _open_counts.values():
            total.fold(counts)
    return {name: getattr(total, name) for name in COUNTS + MAXIMA}


_vars = {name: PassiveStatus(lambda name=name: counters_snapshot()[name])
         for name in COUNTS + MAXIMA}


def expose_stream_vars() -> None:
    """(Re-)expose the process-wide ``stream_*`` sums (import, and again
    from Server.start: they outlive a test fixture's unexpose_all)."""
    for name, var in _vars.items():
        var.expose(f"stream_{name}")


expose_stream_vars()


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
        self.counts = StreamCounters()
        with _counts_lock:
            _open_counts[self.id] = self.counts
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
            # whatever is held: a frame that asked for feedback may be
            # among those delivered before the peer was known
            grant, self._pending_grants = self._pending_grants, 0
        if grant and not self.closed:
            self._send_frame(b"", None, credits=grant, data=False)

    def counters(self) -> dict:
        """This stream's counts, by the names of COUNTS and MAXIMA."""
        return {name: getattr(self.counts, name)
                for name in COUNTS + MAXIMA}

    # --------------------------------------------------------------- write
    async def write(self, payload: bytes | IOBuf = b"",
                    device_arrays: Optional[List] = None,
                    timeout_s: Optional[float] = 10.0) -> bool:
        """Send one frame; parks on the credit butex when the window is
        exhausted. Returns False if the stream closed."""
        if self.closed or self.remote_closed:
            return False
        span = _span.start_frame_span("stream-send") \
            if _span.recording() else None
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
            if v > 0:
                if self._credits.compare_exchange(v, v - 1):
                    break
                continue        # another writer took it: look again
            t0 = time.monotonic_ns()
            r = await self._credits.wait(expected=0, timeout_s=timeout_s)
            self.counts.credit_parks += 1
            self.counts.credit_park_us += (time.monotonic_ns() - t0) // 1000
            if r == WAIT_TIMEOUT:
                return False
        self._send_frame(payload, device_arrays, span=span,
                         credits_left=v - 1)
        return True

    def write_nowait(self, payload: bytes | IOBuf = b"",
                     device_arrays: Optional[List] = None) -> bool:
        """Non-blocking write: fails immediately when out of credits or
        before the stream is established."""
        if self.closed or self.remote_closed or self.peer_id == 0:
            return False
        span = _span.start_frame_span("stream-send") \
            if _span.recording() else None
        while True:
            v = self._credits.value
            if v <= 0:
                return False
            if self._credits.compare_exchange(v, v - 1):
                break
        self._send_frame(payload, device_arrays, span=span,
                         credits_left=v - 1)
        return True

    def _send_frame(self, payload, device_arrays, close: bool = False,
                    credits: int = 0, data: bool = True, span=None,
                    credits_left: Optional[int] = None) -> None:
        """``credits_left``: what the writer of a data frame has after
        taking this frame's credit; at 0 the frame asks for feedback."""
        c = self.counts
        feedback = credits_left == 0
        if data:
            # frame_seq marks DATA frames (they consume a credit and must
            # be delivered, even with an empty payload); bare credit grants
            # and close frames leave it 0
            self._frame_seq += 1
            c.data_frames_out += 1
            c.host_bytes_out += payload.nbytes \
                if isinstance(payload, memoryview) else len(payload)
            if credits_left is not None:
                # that many short of the window are out, not granted back
                out = self.options.initial_credits - credits_left
                if out > c.ungranted_frames_max:
                    c.ungranted_frames_max = out
        else:
            c.ctrl_frames_out += 1
        on_done = None
        if span is not None:
            # the credit is held; the socket's writer stamps the flush
            span.credit_us = time.monotonic_ns() // 1000
            span.stream_id = self.peer_id
            span.frame_seq = self._frame_seq
            on_done = span.write_done
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
            # per frame. stream_id=1, need_feedback=2, frame_seq=3,
            # credits=4, close=5 inside stream_settings (RpcMeta field
            # 6); payload bytes ride zero-copy for big frames.
            inner = b"\x08" + _varint(self.peer_id)
            if feedback:
                inner += b"\x10\x01"
            if data:
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
                self.socket.write(hdr + payload, on_done)
            else:
                wire = IOBuf()
                wire.append(hdr)
                wire.append_user_data(payload)
                # graftlint: disable=callback-under-lock -- see the
                # small-frame branch above: write only queues
                self.socket.write(wire, on_done)
            return
        meta = pb.RpcMeta()
        ss = meta.stream_settings
        ss.stream_id = self.peer_id
        if feedback:
            ss.need_feedback = True
        if data:
            ss.frame_seq = self._frame_seq
        if close:
            ss.close = True
        if credits:
            ss.credits = credits
        use_lane = bool(device_arrays) and self.socket.conn.supports_device_lane
        wire, lane = pack_message(meta, payload, device_arrays=device_arrays,
                                  device_lane=use_lane)
        if data:
            for dp in meta.device_payloads:     # sized by pack_message
                c.device_bytes_out += dp.nbytes
        # graftlint: disable=callback-under-lock -- see _send_frame's
        # raw-frame branch: write only queues, sender locks order tokens
        self.socket.write(wire, on_done, device_arrays=lane, span=span)

    # -------------------------------------------------------------- receive
    def _on_frame(self, msg) -> None:
        ss = msg.meta.stream_settings
        self._accept(ss.credits, ss.close, ss.frame_seq, msg,
                     ss.need_feedback)

    def _accept(self, credits: int, close, seq: int, msg,
                feedback: bool = False) -> None:
        """One frame off the socket, in parse order (both lanes: the
        classic ``_on_frame`` and the scanner's
        ``process_stream_frame_fast``)."""
        c = self.counts
        if credits:
            self._credits.fetch_add(credits)
            self._credits.wake_all()
        if close or not seq:
            c.ctrl_frames_in += 1
            if close:
                self._remote_close_once()
            return
        # DATA frame (possibly empty payload)
        c.data_frames_in += 1
        c.host_bytes_in += msg.payload.size
        if msg.device_arrays:
            for dp in msg.meta.device_payloads:     # as the sender sized them
                c.device_bytes_in += dp.nbytes
        depth = c.data_frames_in - c.delivered
        if depth > c.recv_queue_depth_max:
            c.recv_queue_depth_max = depth
        span = _span.start_frame_span("stream-recv", self.id, seq, msg) \
            if _span.recording() else None
        self._recv_q.execute(("asked" if feedback else "frame", msg, span))

    async def _deliver(self, batch) -> None:
        import inspect
        for kind, msg, span in batch:
            if kind == "close":
                for cb in self._close_cbs:
                    try:
                        cb(self)
                    except Exception:
                        pass
                continue
            if span is not None:
                span.deliver_start_us = time.monotonic_ns() // 1000
            if self.options.on_received is not None:
                try:
                    r = self.options.on_received(self, msg)
                    if inspect.isawaitable(r):
                        await r  # runs in the drainer fiber: stays serial
                except Exception:
                    import logging
                    logging.getLogger("brpc_tpu.rpc").exception(
                        "stream on_received failed")
            self.counts.delivered += 1
            if span is not None:
                span.delivered()
            with self._grant_lock:
                self._pending_grants += 1
                grant = 0
                if (self._pending_grants >= CREDIT_BATCH
                        or kind == "asked") and self.peer_id:
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
        self._recv_q.execute(("close", None, None))  # fire on_close callbacks

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
        with _counts_lock:
            if _open_counts.pop(self.id, None) is not None:
                _closed_counts.fold(self.counts)
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

    __slots__ = ("payload", "attachment", "device_arrays", "_ss", "wake")

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
        # as RpcMessage: the event loop's stamps where it cut this frame
        if _event_dispatcher.stamping is not None:
            self.wake = _event_dispatcher.wake_stamps()

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
    """Dispatch a scan_frames stream record (turbo lane)."""
    stream = _stream_pool.address(sid)
    if stream is None:
        return  # stream already closed; drop (reference drops too)
    msg = FastStreamMsg(payload, att, sid, seq, credits, close) \
        if seq and not close else None
    stream._accept(credits, close, seq, msg)


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
