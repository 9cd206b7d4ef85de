"""hulu_pbrpc + sofa_pbrpc framing variants
(policy/hulu_pbrpc_protocol.cpp, policy/sofa_pbrpc_protocol.cpp): the
baidu-family interop protocols. Both carry the same meta+payload model
as tpu_std behind different wire headers, exactly as the reference's
variants all funnel into the shared Controller/Server machinery.

hulu: "HULU" | body_size:u32be | meta_size:u32be | meta | payload
      (the 12-byte baidu_std-shaped header with hulu's magic)
sofa: "SOFA" | meta_size:u32be | body_size:u32be | reserved:u32be |
      meta | payload  (16-byte header)

The meta schema is our RpcMeta (the reference uses per-family metas;
re-designed here to one schema — cross-implementation interop with
legacy baidu services is out of scope, the capability is the framing +
dispatch plumbing selectable via ChannelOptions.protocol)."""

from __future__ import annotations

import struct
from typing import Optional, Tuple

from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.registry import (
    PARSE_NOT_ENOUGH_DATA, PARSE_OK, PARSE_TRY_OTHERS, register_protocol,
)
from brpc_tpu.protocol.tpu_std import (TpuStdProtocol, cut_message,
                                       pack_message)

_SOFA_HDR = struct.Struct(">4sIII")
_SOFA_HEADER_SIZE = 16


class HuluPbrpcProtocol(TpuStdProtocol):
    """Same 12-byte header layout as tpu_std, hulu magic — everything
    else (parse body, dispatch, response path) is inherited."""

    name = "hulu_pbrpc"
    MAGIC = b"HULU"


class SofaPbrpcProtocol(TpuStdProtocol):
    name = "sofa_pbrpc"
    MAGIC = b"SOFA"

    def frame(self, meta, payload, attachment=None, device_arrays=None,
              device_lane=False):
        # reuse tpu_std body building (device payload inlining included),
        # then swap the 12-byte header for sofa's 16-byte one — header
        # only, never flattening the body (zero-copy preserved)
        wire, lane = pack_message(meta, payload, attachment=attachment,
                                  device_arrays=device_arrays,
                                  device_lane=device_lane, magic=b"\x00\x00\x00\x00")
        _magic, body_size, meta_size = struct.unpack(
            ">4sII", wire.peek_bytes(12))
        wire.pop_front(12)
        out = IOBuf()
        out.append(_SOFA_HDR.pack(self.MAGIC, meta_size,
                                  body_size - meta_size, 0))
        out.append_buf(wire)
        return out, lane

    def parse(self, portal, socket) -> Tuple[str, object]:
        if portal.size < _SOFA_HEADER_SIZE:
            head = portal.peek_bytes(min(4, portal.size))
            if self.MAGIC[:len(head)] != head:
                return PARSE_TRY_OTHERS, None
            return PARSE_NOT_ENOUGH_DATA, None
        magic, meta_size, data_size, _reserved = _SOFA_HDR.unpack(
            portal.peek_bytes(_SOFA_HEADER_SIZE))
        if magic != self.MAGIC:
            return PARSE_TRY_OTHERS, None
        total = meta_size + data_size
        if portal.size < _SOFA_HEADER_SIZE + total:
            return PARSE_NOT_ENOUGH_DATA, None
        portal.pop_front(_SOFA_HEADER_SIZE)
        meta = pb.RpcMeta()
        meta.ParseFromString(portal.cut(meta_size).to_bytes())
        att_size = meta.attachment_size
        if att_size < 0 or att_size > data_size:
            # a lying attachment_size would eat the next frame's bytes and
            # desync the whole connection: fail it instead
            socket.set_failed(ConnectionError(
                f"sofa frame attachment_size {att_size} > data {data_size}"))
            return PARSE_NOT_ENOUGH_DATA, None
        payload = portal.cut(data_size - att_size)
        attachment = portal.cut(att_size)
        return PARSE_OK, cut_message(meta, payload, attachment, socket)


_hulu: Optional[HuluPbrpcProtocol] = None
_sofa: Optional[SofaPbrpcProtocol] = None


def ensure_registered() -> Tuple[HuluPbrpcProtocol, SofaPbrpcProtocol]:
    global _hulu, _sofa
    if _hulu is None:
        _hulu = HuluPbrpcProtocol()
        register_protocol(_hulu)
    if _sofa is None:
        _sofa = SofaPbrpcProtocol()
        register_protocol(_sofa)
    return _hulu, _sofa
