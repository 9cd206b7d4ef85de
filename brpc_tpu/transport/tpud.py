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
to is decoded before the message bytes reach the parser (the sender
writes lane-then-frame, exactly like the in-process tpu:// transport).
Received arrays are materialized with ``jax.device_put`` onto this
host's target device at take time."""

from __future__ import annotations

import json
import struct
import sys
import threading
from collections import deque
from typing import Callable, Deque, List, Optional

import numpy as np

from brpc_tpu.butil.endpoint import EndPoint, str2endpoint
from brpc_tpu.transport.base import Conn, Listener, Transport
from brpc_tpu.transport.tcp import TcpConn, TcpTransport

_F_BYTES = 0
_F_DEVICE = 1
_F_HELLO = 2
_HDR = struct.Struct(">BI")
_MAX_FRAME = 256 << 20
_MAX_OUT = 64 << 20          # backpressure cap on the staged out-buffer


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


def _encode_device_batch(arrays) -> bytes:
    parts = [struct.pack(">H", len(arrays))]
    for arr in arrays:
        host = np.asarray(arr)
        dt = str(host.dtype).encode()
        parts.append(struct.pack(">B", len(dt)))
        parts.append(dt)
        parts.append(struct.pack(">B", host.ndim))
        parts.append(struct.pack(f">{host.ndim}q", *host.shape)
                     if host.ndim else b"")
        raw = host.tobytes()
        parts.append(struct.pack(">Q", len(raw)))
        parts.append(raw)
    return b"".join(parts)


def _decode_device_batch(data: bytes) -> List[np.ndarray]:
    (count,) = struct.unpack_from(">H", data, 0)
    pos = 2
    out = []
    for _ in range(count):
        (dtlen,) = struct.unpack_from(">B", data, pos)
        pos += 1
        dtype = _np_dtype(data[pos:pos + dtlen].decode())
        pos += dtlen
        (rank,) = struct.unpack_from(">B", data, pos)
        pos += 1
        shape = struct.unpack_from(f">{rank}q", data, pos) if rank else ()
        pos += 8 * rank
        (nbytes,) = struct.unpack_from(">Q", data, pos)
        pos += 8
        arr = np.frombuffer(data[pos:pos + nbytes],
                            dtype=dtype).reshape(shape)
        pos += nbytes
        out.append(arr)
    return out


class TpudConn(Conn):
    supports_device_lane = True
    lane_kind = "staged-dcn"     # /device cell label (device_stats)

    def __init__(self, inner: TcpConn, local: EndPoint, remote: EndPoint,
                 device_ordinal: Optional[int]):
        self._inner = inner
        self._local = local
        self._remote = remote
        self._device_ordinal = device_ordinal
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()   # single-flight TCP pushes
        self._out = bytearray()            # staged enveloped output
        self._inbuf = bytearray()          # raw inbound, pre-envelope
        self._appbuf = bytearray()         # de-enveloped app bytes
        self._lane: Deque[List] = deque()
        self._closed_read = False
        self.peer_info: Optional[dict] = None
        self._send_frame(_F_HELLO, _hello_payload(device_ordinal))

    # ----------------------------------------------------------- outbound
    def _send_frame(self, ftype: int, payload: bytes) -> None:
        with self._lock:
            if len(self._out) > _MAX_OUT:
                raise BlockingIOError("tpud out-buffer full")
            self._out += _HDR.pack(ftype, len(payload))
            self._out += payload
        self._flush()

    def _flush(self) -> bool:
        """Push staged bytes into the TCP socket; True if fully drained.
        Single-flight: two concurrent flushers would snapshot and send
        the same prefix twice, corrupting the stream."""
        with self._flush_lock:
            while True:
                with self._lock:
                    if not self._out:
                        return True
                    chunk = bytes(self._out[:256 << 10])
                try:
                    n = self._inner.write(memoryview(chunk))
                except BlockingIOError:
                    self._inner.request_writable_event()
                    return False
                with self._lock:
                    del self._out[:n]

    def write(self, mv: memoryview) -> int:
        # accept the whole chunk into the envelope buffer (bounded by
        # _MAX_OUT); partial TCP writes must never split our framing
        data = bytes(mv)
        self._send_frame(_F_BYTES, data)
        return len(data)

    def write_device_payload(self, arrays) -> bool:
        self._send_frame(_F_DEVICE, _encode_device_batch(arrays))
        return True

    # ------------------------------------------------------------ inbound
    def _pump(self) -> None:
        """Drain the TCP socket and de-envelope complete frames."""
        buf = bytearray(256 << 10)
        while True:
            try:
                n = self._inner.read_into(memoryview(buf))
            except BlockingIOError:
                break
            if n == 0:
                self._closed_read = True
                break
            self._inbuf += buf[:n]
        while len(self._inbuf) >= _HDR.size:
            ftype, length = _HDR.unpack_from(self._inbuf, 0)
            if length > _MAX_FRAME:
                raise ConnectionError(f"tpud frame of {length}B exceeds max")
            if len(self._inbuf) < _HDR.size + length:
                break
            payload = bytes(self._inbuf[_HDR.size:_HDR.size + length])
            del self._inbuf[:_HDR.size + length]
            if ftype == _F_BYTES:
                self._appbuf += payload
            elif ftype == _F_DEVICE:
                self._lane.append(_decode_device_batch(payload))
            elif ftype == _F_HELLO:
                try:
                    self.peer_info = json.loads(payload.decode())
                except ValueError:
                    raise ConnectionError("tpud: bad hello")
            else:
                raise ConnectionError(f"tpud: unknown frame type {ftype}")

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
        # so the batch is already decoded by the time the parser asks for
        # it — and pumping from the parse path would consume the readable
        # edge while leaving de-enveloped bytes nobody ever processes
        if not self._lane:
            return None
        batch = self._lane.popleft()
        jax = sys.modules.get("jax")
        if jax is None:
            return batch                    # numpy-only consumer
        try:
            devs = jax.devices()
            target = devs[self._device_ordinal or 0] \
                if (self._device_ordinal or 0) < len(devs) else devs[0]
            return [jax.device_put(a, target) for a in batch]
        except Exception:
            return batch

    # ----------------------------------------------------------- plumbing
    def close(self) -> None:
        self._inner.close()

    def start_events(self, on_readable: Callable[[], None],
                     on_writable: Callable[[], None]) -> None:
        def writable():
            if self._flush():
                on_writable()

        self._on_writable_cb = writable
        self._inner.start_events(on_readable, writable)

    def request_writable_event(self) -> None:
        self._inner.request_writable_event()

    def resume_read_events(self) -> None:
        resume = self._inner.resume_read_events
        if resume is not None:
            resume()

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
