"""Runtime flags: the gflags-equivalent config system (SURVEY.md §5 —
every tunable in the reference is a DEFINE_* gflag, runtime-mutable via
/flags with registered validators).

define_flag at import time, read with flag(), set at runtime (validated);
the /flags builtin page lists and mutates them.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple


class _Flag:
    __slots__ = ("name", "value", "default", "help", "validator", "ftype")

    def __init__(self, name, default, help_, validator):
        self.name = name
        self.value = default
        self.default = default
        self.help = help_
        self.validator = validator
        self.ftype = type(default)


_flags: Dict[str, _Flag] = {}
_lock = threading.Lock()


def define_flag(name: str, default: Any, help_: str = "",
                validator: Optional[Callable[[Any], bool]] = None) -> None:
    with _lock:
        if name in _flags:
            raise ValueError(f"flag {name!r} already defined")
        _flags[name] = _Flag(name, default, help_, validator)
    # environment override at definition (the reference gets this from
    # gflags' --flag=... argv; subprocess tooling needs the env form):
    # BRPC_TPU_FLAG_<NAME>=value, parsed with set_flag's type rules
    env = os.environ.get(f"BRPC_TPU_FLAG_{name.upper()}")
    if env is not None and not set_flag(name, env):
        # a silently-dropped override would leave the operator running
        # defaults while believing the env applied
        import logging
        logging.getLogger("brpc_tpu.flags").warning(
            "env override BRPC_TPU_FLAG_%s=%r rejected (bad value or "
            "validator); keeping default %r", name.upper(), env, default)


def flag(name: str) -> Any:
    f = _flags.get(name)
    if f is None:
        raise KeyError(f"undefined flag {name!r}")
    return f.value


def set_flag(name: str, value: Any) -> bool:
    """Parses strings to the flag's type; runs the validator. Returns
    False (and leaves the flag untouched) on bad value."""
    f = _flags.get(name)
    if f is None:
        return False
    if isinstance(value, str) and f.ftype is not str:
        try:
            if f.ftype is bool:
                value = value.lower() in ("1", "true", "yes", "on")
            else:
                value = f.ftype(value)
        except (TypeError, ValueError):
            return False
    if not isinstance(value, f.ftype) and f.ftype is not type(None):
        return False
    if f.validator is not None and not f.validator(value):
        return False
    f.value = value
    return True


def list_flags() -> List[Tuple[str, Any, Any, str]]:
    with _lock:
        return sorted((f.name, f.value, f.default, f.help)
                      for f in _flags.values())


# core knobs (the reference defines these as gflags in socket.cpp etc.)
define_flag("max_body_size", 64 * 1024 * 1024,
            "largest allowed request/response body",
            validator=lambda v: v > 0)
define_flag("graceful_quit_on_sigterm", True,
            "drain in-flight requests before exiting on SIGTERM")
define_flag("rpcz_enabled", False,
            "collect per-RPC spans for /rpcz (off by default like the "
            "reference's rpcz — enable at runtime via /flags; span "
            "creation + trace propagation cost sits on every call). "
            "Spans also record, whatever this flag says, while a JAX "
            "profile runs in the process (jax.profiler.start_trace): "
            "rpc/span.recording(). Either way the all-C and turbo "
            "dispatch lanes and the channel's small-call fast path "
            "stand down meanwhile (they cannot stamp)")
define_flag("rpcz_max_spans", 16384,
            "span ring-buffer capacity (a 2 s profile at 550 calls/s "
            "makes about 6,600 spans)",
            validator=lambda v: v >= 16)
define_flag("tpu_std_cut_through", True,
            "stream large native-echo frames through the server without "
            "assembly (response header leaves when the request meta "
            "parses; body forwards as it arrives)")
define_flag("tpu_std_batch_parse", False,
            "cut pipelined tpu_std bursts with the native frame scanner "
            "(measured ~parity with the per-frame path under CPython; "
            "see protocol/tpu_std.py batch_parse)")
define_flag("rpcz_dir", "",
            "directory for on-disk rpcz persistence (empty = memory only)")
define_flag("rpcz_db_max_bytes", 16 << 20,
            "rotate the rpcz span file at this size; one old file is kept",
            validator=lambda v: v >= 1 << 20)
