"""EndPoint: where a peer lives.

Generalizes the reference's ip:port EndPoint (butil/endpoint.h:87) to a
{scheme, host, port, extras} tuple so one value type addresses TCP peers,
in-memory test transports, and TPU device endpoints:

  tcp://10.0.0.1:8000          classic socket peer (DCN / control plane)
  mem://server-a               in-process loopback (the test fabric, §4)
  tpu://host:port#device=3     a device on a pod worker; ``device`` is the
                               local device ordinal, mesh coords go in extras

Plain "ip:port" strings parse as tcp for reference-compatibility.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class EndPoint:
    scheme: str = "tcp"
    host: str = ""
    port: int = 0
    extras: Tuple[Tuple[str, str], ...] = ()

    def extra(self, key: str, default: Optional[str] = None) -> Optional[str]:
        for k, v in self.extras:
            if k == key:
                return v
        return default

    @property
    def device(self) -> Optional[int]:
        d = self.extra("device")
        return int(d) if d is not None else None

    @property
    def reply_device(self) -> Optional[int]:
        """``#reply_device=K``: the dialer's local device that replies'
        device arrays land on (None: it names none, which is device 0)."""
        d = self.extra("reply_device")
        return int(d) if d else None

    def with_extras(self, **kv) -> "EndPoint":
        merged: Dict[str, str] = dict(self.extras)
        merged.update({k: str(v) for k, v in kv.items()})
        return EndPoint(self.scheme, self.host, self.port, tuple(sorted(merged.items())))

    def __str__(self) -> str:
        s = f"{self.scheme}://{self.host}"
        if self.port:
            s += f":{self.port}"
        if self.extras:
            s += "#" + "&".join(f"{k}={v}" for k, v in self.extras)
        return s


def str2endpoint(s: str, default_scheme: str = "tcp") -> EndPoint:
    """Parse "scheme://host:port#k=v&k2=v2"; bare "host:port" or "host"
    gets ``default_scheme`` (butil/endpoint.cpp str2endpoint)."""
    extras: Tuple[Tuple[str, str], ...] = ()
    if "#" in s:
        s, frag = s.split("#", 1)
        pairs = []
        for item in frag.split("&"):
            if not item:
                continue
            k, _, v = item.partition("=")
            pairs.append((k, v))
        extras = tuple(sorted(pairs))
    if "://" in s:
        scheme, rest = s.split("://", 1)
    else:
        scheme, rest = default_scheme, s
    host, port = rest, 0
    if rest.startswith("["):  # [v6]:port
        close = rest.index("]")
        host = rest[1:close]
        tail = rest[close + 1:]
        if tail.startswith(":"):
            port = int(tail[1:])
    elif ":" in rest:
        host, p = rest.rsplit(":", 1)
        if p:
            port = int(p)
    return EndPoint(scheme, host, port, extras)
