"""tpud:// — the cross-host device transport (the DCN slot of SURVEY
§2.8: where tpu:// is the in-pod ICI lane, tpud carries the same Socket
contract between HOSTS over TCP).

One TCP stream carries enveloped frames:
    frame := type:u8 len:u32be payload
    type 0  app bytes        (delivered to the Socket's input portal)
    type 1  device batch     (staged arrays: count + per-array header+data)
    type 2  hello            (json handshake: the RDMA-style GID/QPN
                              exchange — device ordinal, process index,
                              local device count)

Ordering on the single stream guarantees the lane batch a message refers
to has arrived before the message bytes reach the parser (the sender
writes lane-then-frame, exactly like the in-process tpu:// transport).
A received batch is decoded and, in a process that has loaded jax,
``jax.device_put`` onto this host's target device at take time, inside
the take the Socket times (``recv_us_sum`` of the ``staged-dcn`` cell).
A process that never loaded jax (a host-only client) gets numpy arrays:
there that is the contract. With jax loaded a ``device_put`` that raises
is counted (``tpud_put_fallbacks``) and raised, which fails the
connection: a handler never gets numpy in a device array's place
unnoticed (docs/tpu_transport.md has the copy chain and the counters).

``TpudConn`` copies no staged batch in Python: an array goes to
``sendmsg`` by reference behind its header (one snapshot where it is a
writeable numpy array, which the caller could change under the send),
and a received batch is read into a buffer of its own and decoded in
place (``tpud_copied_bytes_out|in`` count what was copied all the same).
"""

from __future__ import annotations

import json
import struct
import sys
import threading
import time
from collections import deque
from itertools import islice
from typing import Callable, Deque, List, Optional, Tuple

import numpy as np

from brpc_tpu.butil.endpoint import EndPoint, str2endpoint
from brpc_tpu.transport import syscall_stats as _stats
from brpc_tpu.transport.base import Conn, Listener, Transport
from brpc_tpu.transport.tcp import TcpConn, TcpTransport

_F_BYTES = 0
_F_DEVICE = 1
_F_HELLO = 2
_HDR = struct.Struct(">BI")
_MAX_FRAME = 256 << 20
_MAX_OUT = 64 << 20          # backpressure cap on the staged out-buffer
_READ = 256 << 10            # one read into the conn's scratch
_IOV = 64                    # segments a sendmsg hands the kernel at most


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def _hello_payload(device_ordinal: Optional[int]) -> bytes:
    info = {"device": device_ordinal or 0}
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            info["process_index"] = jax.process_index()
            info["local_device_count"] = jax.local_device_count()
        except Exception:
            pass
    return json.dumps(info).encode()


def _batch_segments(arrays, keep: bool = False) -> Tuple[list, int, int]:
    """One batch in wire order as segments: each array's fields as
    ``bytes`` (the count ahead of the first), each array's data as a
    ``uint8`` view of its C-contiguous host copy. The one function that
    knows the layout: joined, the segments are the encoded batch.
    Returns the segments, their length and the array bytes copied. A
    non-contiguous array is made contiguous; with ``keep`` a writeable
    one is snapshotted too (its owner could change it before the kernel
    has it), and one that is not writeable goes by reference."""
    fields = [struct.pack(">H", len(arrays))]
    segs, length, copied = [], 2, 0
    for arr in arrays:
        host = np.asarray(arr)
        if not host.flags.c_contiguous or (keep and host.flags.writeable):
            host = np.array(host, order="C")
            copied += host.nbytes
        dt = str(host.dtype).encode()
        fields += [struct.pack(f">B{len(dt)}sB{host.ndim}qQ", len(dt), dt,
                               host.ndim, *host.shape, host.nbytes)]
        length += 1 + len(dt) + 1 + 8 * host.ndim + 8 + host.nbytes
        if host.nbytes:
            segs += [b"".join(fields),
                     memoryview(host.reshape(-1).view(np.uint8))]
            fields = []
    if fields:
        segs.append(b"".join(fields))
    return segs, length, copied


def _encode_device_batch(arrays) -> bytes:
    return b"".join(_batch_segments(arrays)[0])


def _decode_device_batch(data) -> List[np.ndarray]:
    """The arrays of one encoded batch, as views of ``data`` (``bytes``
    or any buffer). An array whose offset in ``data`` is not aligned for
    its dtype (float32 at rank 2 starts at byte 35 of the batch) is
    copied to an aligned buffer of its own; the caller tells the two
    apart by ``flags.owndata``."""
    view = memoryview(data)
    (count,) = struct.unpack_from(">H", view, 0)
    pos = 2
    out = []
    for _ in range(count):
        (dtlen,) = struct.unpack_from(">B", view, pos)
        pos += 1
        dtype = _np_dtype(bytes(view[pos:pos + dtlen]).decode())
        pos += dtlen
        (rank,) = struct.unpack_from(">B", view, pos)
        pos += 1
        shape = struct.unpack_from(f">{rank}q", view, pos) if rank else ()
        pos += 8 * rank
        (nbytes,) = struct.unpack_from(">Q", view, pos)
        pos += 8
        arr = np.frombuffer(view[pos:pos + nbytes],
                            dtype=dtype).reshape(shape)
        if not arr.flags.aligned:
            arr = arr.copy()    # its offset in the frame misaligns it
        pos += nbytes
        out.append(arr)
    return out


class TpudConn(Conn):
    """One tpud:// connection. What it says of itself under
    ``transport/base.py::Conn``, name by name:

    - ``supports_device_lane``, ``supports_device_tracker``: batches go
      out of band as type-1 frames, and ``write_device_payload`` takes
      the batch's tracker: ``stage`` ends when the batch is encoded (the
      wait for the device, D2H, encode), ``wire`` when TCP has taken the
      frame's last byte; no peer ACK exists, so ``ack`` is 0.
    - NOT ``inline_write_ok`` and NOT ``flush`` with ``write(mv,
      flush=False)``, though it could (``write`` only appends to
      ``_out`` and pushes what TCP takes now): built and measured in
      PR 37, the gathered write in the claiming context LOST 32% of the
      rate at 2 MB a batch on the chip (PERF.md section 6). A batch
      larger than TCP's buffer leaves a remainder in ``_out``, and with
      no writer fiber to wait for the writable event it was pushed,
      copies and all, by the event thread, which is the thread that
      reads. So every claim of writership spawns a ``keep_write`` fiber
      that waits and pushes on a worker, as before.
    - ``level_triggered``, ``pause_read_events``, ``resume_read_events``:
      the inner TCP conn's own answers, handed over: its fd is this
      conn's event source. ``peek_closed``: the inner conn's, and
      nothing read but undelivered.
    - NOT ``stream_fd``: the fd's bytes are enveloped frames, not the
      application's stream. NOT ``pluck_fd``: nothing is known against
      it (``read_into`` pumps to EAGAIN and hands all of ``_appbuf``
      over, as ici:// does), but no test and no chip run has plucked a
      tpud conn and a joiner would then decode MB batches on the
      caller's thread: it waits for a measurement. So
      ``awaits_peer_frame`` (read only beside a pluck) stays off too.
    - NOT ``short_read_drained``: ``read_into`` hands de-enveloped bytes
      over, a short read says nothing of the kernel. NOT ``writev`` /
      ``read_into_v``: every byte has to pass the envelope. NOT
      ``read_chunks`` / ``pending_bytes`` / ``drain_all_reads``: no
      notification a write (mem:// alone has one)."""

    supports_device_lane = True
    supports_device_tracker = True
    lane_kind = "staged-dcn"     # /device cell label (device_stats)

    def __init__(self, inner: TcpConn, local: EndPoint, remote: EndPoint,
                 device_ordinal: Optional[int]):
        self._inner = inner
        self.level_triggered = inner.level_triggered
        self.pause_read_events = inner.pause_read_events
        self.resume_read_events = inner.resume_read_events
        self._local = local
        self._remote = remote
        self._device_ordinal = device_ordinal
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()   # single-flight TCP pushes
        # one pump at a time, and peek_closed looks at what a pump in
        # flight has read only after it ended
        self._pump_lock = threading.Lock()
        # staged enveloped output: frames as segments for sendmsg (a
        # batch's arrays by reference), and their length in bytes
        self._out: Deque = deque()
        self._out_bytes = 0
        # flush-stamp bookkeeping (under _lock): bytes TCP has taken, and
        # (end offset in the stream, tracker) of every device frame whose
        # last byte TCP has not taken yet
        self._written = 0
        self._marks: Deque[Tuple[int, object]] = deque()
        self._scratch = memoryview(bytearray(_READ))  # what a read lands in
        self._inbuf = bytearray()          # a frame's head a read cut off
        self._frame: Optional[memoryview] = None  # a device frame filling
        self._frame_pos = 0                # ... and its bytes read so far
        self._appbuf = bytearray()         # de-enveloped app bytes
        self._lane: Deque[memoryview] = deque()  # inbound batches, encoded
        self._closed_read = False
        self._closed = False
        # the device inbound batches are put on, resolved at the first
        # take of a process that has jax
        self._recv_dev = None
        self.peer_info: Optional[dict] = None
        self._send_frame(_F_HELLO, _hello_payload(device_ordinal))

    # ----------------------------------------------------------- outbound
    def _out_full(self) -> bool:
        if self._out_bytes > _MAX_OUT:
            _stats.tpud_out_full.add(1)
            return True
        return False

    def _stage(self, segs, nbytes: int, flush: bool, tracker=None) -> None:
        with self._lock:
            if self._closed:
                raise ConnectionError("tpud conn closed")
            self._out.extend(segs)
            self._out_bytes += nbytes
            if tracker is not None:
                self._marks.append((self._written + self._out_bytes, tracker))
        if flush:
            self._flush()

    def _send_frame(self, ftype: int, payload: bytes) -> None:
        frame = _HDR.pack(ftype, len(payload)) + payload
        self._stage((frame,), len(frame), True)

    def _flush(self) -> bool:
        """Push staged bytes into the TCP socket; True if fully drained.
        Single-flight a ``sendmsg``: two concurrent flushers would send
        the same segments twice, corrupting the stream. A partial send
        leaves the rest of its last segment as a view of it. The
        trackers of the batches a send completed are stamped with no
        lock of this conn held (their cell's lock is a leaf)."""
        while True:
            sent = []
            with self._flush_lock:
                with self._lock:
                    if not self._out:
                        return True
                    segs = list(islice(self._out, _IOV))
                try:
                    n = self._inner.writev(segs)
                except BlockingIOError:
                    self._inner.request_writable_event()
                    return False
                with self._lock:
                    self._out_bytes -= n
                    self._written += n
                    for seg in segs:
                        if len(seg) > n:
                            self._out[0] = memoryview(seg)[n:]
                            break
                        n -= len(seg)
                        self._out.popleft()
                    while self._marks and \
                            self._marks[0][0] <= self._written:
                        sent.append(self._marks.popleft()[1])
            for tracker in sent:
                # TCP has this batch's last byte: wire ends, and with
                # no peer ACK the transfer completes here
                tracker.lane_flushed()
                tracker.lane_acked()

    def write(self, mv: memoryview) -> int:
        # accept the whole chunk into the envelope buffer (bounded by
        # _MAX_OUT); partial TCP writes must never split our framing
        if self._out_full():
            raise BlockingIOError("tpud out-buffer full")
        data = bytes(mv)
        self._send_frame(_F_BYTES, data)
        return len(data)

    def write_device_payload(self, arrays, tracker=None,
                             flush: bool = True) -> bool:
        """Stage a batch: wait for the device, copy to the host, queue
        the frame's segments (the arrays by reference), push (``flush``
        False leaves the push to a later write). ``tracker``: the
        batch's device_stats timeline (or None). A full out-buffer
        raises BlockingIOError BEFORE anything is staged, with the
        tracker still open: the Socket settles it, fails that call and
        keeps its envelope home, so no batch is left without its
        envelope."""
        if self._out_full():
            raise BlockingIOError("tpud out-buffer full")
        t0 = time.monotonic_ns()
        segs, length, copied = _batch_segments(arrays, keep=True)
        segs[0] = _HDR.pack(_F_DEVICE, length) + segs[0]
        _stats.tpud_encode_us.add((time.monotonic_ns() - t0) // 1000)
        if tracker is not None:
            tracker.lane_encoded()
        self._stage(segs, _HDR.size + length, flush, tracker)
        _stats.tpud_batches_out.add(1)
        _stats.tpud_bytes_out.add(length)
        _stats.tpud_copied_bytes_out.add(copied)
        return True

    # ------------------------------------------------------------ inbound
    def _pump(self) -> None:
        """Drain the TCP socket and de-envelope complete frames. Once a
        device frame's header is parsed the rest of the frame is read
        straight into a buffer of its own; all else lands in the
        scratch."""
        with self._pump_lock:
            while True:
                frame = self._frame
                try:
                    n = self._inner.read_into(
                        self._scratch if frame is None
                        else frame[self._frame_pos:])
                except BlockingIOError:
                    break
                if n == 0:
                    self._closed_read = True
                    break
                if frame is None:
                    self._deframe(n)
                    continue
                self._frame_pos += n
                if self._frame_pos == len(frame):
                    self._lane.append(frame)
                    self._frame = None

    def _deframe(self, n: int) -> None:
        """De-envelope the scratch's first ``n`` bytes behind what an
        earlier read left in ``_inbuf``; what no whole frame holds stays
        there for the next read."""
        if not self._inbuf:
            data = self._scratch[:n]
            self._inbuf += data[self._cut(data):]
            return
        self._inbuf += self._scratch[:n]
        data = memoryview(self._inbuf)
        pos = self._cut(data)
        data.release()
        del self._inbuf[:pos]

    def _cut(self, data: memoryview) -> int:
        """File the whole frames at the head of ``data``; returns the
        bytes they took. A device frame is filed in ``_lane`` before the
        bytes behind it reach ``_appbuf``; one the data's end cuts off
        becomes ``_frame``, to be filled by the reads that follow."""
        pos, end = 0, len(data)
        while end - pos >= _HDR.size:
            ftype, length = _HDR.unpack_from(data, pos)
            if length > _MAX_FRAME:
                raise ConnectionError(f"tpud frame of {length}B exceeds max")
            body = pos + _HDR.size
            if ftype == _F_DEVICE:
                # the frame's own buffer, the head read so far moved in
                have = min(length, end - body)
                frame = memoryview(np.empty(length, np.uint8))
                frame[:have] = data[body:body + have]
                _stats.tpud_copied_bytes_in.add(have)
                if have < length:
                    self._frame, self._frame_pos = frame, have
                    return end
                self._lane.append(frame)    # decoded at the take
            elif end - body < length:
                break
            elif ftype == _F_BYTES:
                self._appbuf += data[body:body + length]
            elif ftype == _F_HELLO:
                try:
                    self.peer_info = json.loads(
                        bytes(data[body:body + length]).decode())
                except ValueError:
                    raise ConnectionError("tpud: bad hello")
            else:
                raise ConnectionError(f"tpud: unknown frame type {ftype}")
            pos = body + length
        return pos

    def read_into(self, mv: memoryview) -> int:
        self._pump()
        if self._appbuf:
            n = min(len(mv), len(self._appbuf))
            mv[:n] = self._appbuf[:n]
            del self._appbuf[:n]
            return n
        if self._closed_read:
            return 0
        raise BlockingIOError

    def take_device_payload(self):
        # no TCP pump: the lane frame precedes its message's byte frames,
        # so the batch has arrived by the time the parser asks for it —
        # and pumping from the parse path would consume the readable
        # edge while leaving de-enveloped bytes nobody ever processes
        if not self._lane:
            return None
        payload = self._lane.popleft()
        t0 = time.monotonic_ns()
        batch = _decode_device_batch(payload)
        t1 = time.monotonic_ns()
        _stats.tpud_batches_in.add(1)
        _stats.tpud_bytes_in.add(len(payload))
        _stats.tpud_copied_bytes_in.add(
            sum(a.nbytes for a in batch if a.flags.owndata))
        _stats.tpud_decode_us.add((t1 - t0) // 1000)
        jax = sys.modules.get("jax")
        if jax is None:
            return batch        # a process without jax: numpy, by contract
        try:
            target = self._recv_dev
            if target is None:
                devs = jax.devices()
                ordinal = self._device_ordinal or 0
                target = self._recv_dev = \
                    devs[ordinal] if ordinal < len(devs) else devs[0]
            out = [jax.device_put(a, target) for a in batch]
        except Exception:
            # never numpy in a device array's place unnoticed: counted,
            # and the raise fails the connection (Socket._input_error)
            _stats.tpud_put_fallbacks.add(1)
            raise
        _stats.tpud_put_us.add((time.monotonic_ns() - t1) // 1000)
        return out

    # ----------------------------------------------------------- plumbing
    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            unsent = [m[1] for m in self._marks]
            self._marks.clear()
        self._inner.close()
        for tracker in unsent:
            # staged and never wholly taken by TCP: the cell must still
            # balance
            tracker.lane_failed("tpud conn closed before the batch left")

    def start_events(self, on_readable: Callable[[], None],
                     on_writable: Callable[[], None]) -> None:
        def writable():
            if self._flush():
                on_writable()

        self._on_writable_cb = writable
        self._inner.start_events(on_readable, writable)

    def request_writable_event(self) -> None:
        self._inner.request_writable_event()

    def peek_closed(self) -> bool:
        """True only when the peer's FIN has arrived, the kernel holds
        no byte more and nothing this conn has read waits to be
        delivered (a pump in flight ends first: its lock)."""
        if not self._inner.peek_closed():
            return False
        with self._pump_lock:
            return not (self._inbuf or self._appbuf or self._lane
                        or self._frame is not None)

    @property
    def local_endpoint(self):
        return self._local

    @property
    def remote_endpoint(self):
        return self._remote


class _TpudListener(Listener):
    def __init__(self, inner: Listener, ep: EndPoint):
        self._inner = inner
        self._ep = ep

    def stop(self) -> None:
        self._inner.stop()

    @property
    def endpoint(self) -> EndPoint:
        return self._ep


class TpudTransport(Transport):
    scheme = "tpud"

    def __init__(self):
        self._tcp = TcpTransport()

    @staticmethod
    def _ordinal(ep: EndPoint) -> Optional[int]:
        return ep.device or 0

    def listen(self, ep: EndPoint, on_new_conn) -> Listener:
        ordinal = self._ordinal(ep)
        tcp_ep = EndPoint("tcp", ep.host or "127.0.0.1", ep.port, ep.extras)
        ready = threading.Event()   # accepts can fire before `bound` is set

        def wrap(conn: TcpConn):
            ready.wait(5)
            on_new_conn(TpudConn(conn, bound, conn.remote_endpoint, ordinal))

        inner = self._tcp.listen(tcp_ep, wrap)
        bound = EndPoint("tpud", inner.endpoint.host, inner.endpoint.port,
                         ep.extras)
        ready.set()
        return _TpudListener(inner, bound)

    def connect(self, ep: EndPoint) -> Conn:
        tcp_ep = EndPoint("tcp", ep.host, ep.port, ep.extras)
        inner = self._tcp.connect(tcp_ep)
        return TpudConn(inner, inner.local_endpoint, ep, self._ordinal(ep))
