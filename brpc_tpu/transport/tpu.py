"""tpu:// — the IN-PROCESS LOOPBACK device transport (the test fabric).

This is the fake the reference's test strategy demands (SURVEY.md §4:
everything testable over 127.0.0.1 without a cluster): host metadata
rides in-process mem pipes, device payloads hand off by reference (or a
`jax.device_put` D2D copy when src/dst ordinals differ). Both ends MUST
live in one process — there is no wire and no flow control here by
design, which also makes it the zero-overhead fixture for scheduler and
protocol tests.

The REAL device data plane is ``ici://`` (transport/ici.py): TCP
bootstrap handshake, PjRt pull-DMA lane, sliding-window + piggyback-ACK
flow control, recv-pool admission — use it for anything that crosses a
process or host boundary, and for honest performance numbers.

Endpoint form: ``tpu://name:port#device=K`` — K is the receiver's local
device ordinal.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.transport.base import Conn, Listener, Transport
from brpc_tpu.transport.mem import MemConn, _MemPipe, _MemListener


def _device_for(ordinal: Optional[int], what: str):
    from brpc_tpu.butil.jax_runtime import (ensure_compile_cache,
                                            local_device)
    ensure_compile_cache()
    return local_device(ordinal, what)


class TpuConn(MemConn):
    """Host stream = mem pipes; device lane = device_put to the peer's
    device (the PjRt Send/Recv slot)."""

    supports_device_lane = True
    lane_kind = "loopback-d2d"   # /device cell label (device_stats)

    def __init__(self, rx, tx, local, remote, peer_device_ordinal: Optional[int],
                 what: str):
        super().__init__(rx, tx, local, remote)
        # an explicit ordinal is checked NOW (connect time): one this
        # process does not have is an error, not device 0. The default
        # resolves at the first payload, so host-only users of this
        # fixture never touch the backend.
        self._peer_device = (None if peer_device_ordinal is None
                             else _device_for(peer_device_ordinal, what))

    def write_device_payload(self, arrays) -> bool:
        import jax
        target = self._peer_device
        if target is None:
            target = self._peer_device = _device_for(None, "tpu://")
        moved = []
        for arr in arrays:
            if getattr(arr, "devices", None) is not None and callable(arr.devices) \
                    and target in arr.devices():
                moved.append(arr)  # already resident: zero-copy hand-off
            else:
                moved.append(jax.device_put(arr, target))
        return super().write_device_payload(moved)


class TpuTransport(Transport):
    scheme = "tpu"

    def __init__(self):
        self._listeners: Dict[str, _MemListener] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _key(ep: EndPoint) -> str:
        return f"{ep.host}:{ep.port}"

    def listen(self, ep: EndPoint, on_new_conn) -> Listener:
        if ep.device is not None:
            _device_for(ep.device, f"{ep} #device")
        with self._lock:
            key = self._key(ep)
            if key in self._listeners:
                raise OSError(f"tpu://{key} already listening")
            lst = _MemListener(self, ep, on_new_conn)
            self._listeners[key] = lst
            # _MemListener.stop() pops by ep.host; patch key-based removal
            lst.stop = lambda: self._listeners.pop(key, None)  # type: ignore
            return lst

    def connect(self, ep: EndPoint) -> Conn:
        with self._lock:
            lst = self._listeners.get(self._key(ep))
        if lst is None:
            raise ConnectionRefusedError(f"no listener at tpu://{self._key(ep)}")
        a2b, b2a = _MemPipe(), _MemPipe()
        server_ep = lst.endpoint
        client_ep = EndPoint("tpu", f"client-{id(a2b):x}", 0)
        # requests land on the server's device; responses land on the
        # client's reply device (the `reply_device` extra, default dev 0)
        client = TpuConn(rx=b2a, tx=a2b, local=client_ep, remote=ep,
                         peer_device_ordinal=ep.device,
                         what=f"{ep} #device")
        server = TpuConn(rx=a2b, tx=b2a, local=server_ep, remote=client_ep,
                         peer_device_ordinal=ep.reply_device,
                         what=f"{ep} #reply_device")
        client.peer = server
        server.peer = client
        lst.on_new_conn(server)
        return client
