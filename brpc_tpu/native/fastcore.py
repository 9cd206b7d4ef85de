"""Loader for the _brpc_fastcore CPython extension.

``get()`` returns the extension module or None (no compiler, build
failure, or BRPC_TPU_NO_NATIVE set) — every consumer keeps a pure-Python
fallback, mirroring how the ctypes library is loaded
(brpc_tpu/native/__init__.py). The extension puts the native cores on
the per-call hot path: see src/fastcore.cc for what maps where.
"""

from __future__ import annotations

import importlib.util
import os
import threading

_lock = threading.Lock()
_mod = None
_tried = False
# BRPC_TPU_SANITIZE value the cache was latched under: a change after
# latching must raise, not silently serve the mismatched artifact
_latched_san = None


def get():
    global _mod, _tried, _latched_san
    if _mod is not None or _tried:
        if os.environ.get("BRPC_TPU_SANITIZE", "") != _latched_san:
            from brpc_tpu.native.build import sanitize_changed_error
            raise sanitize_changed_error(_latched_san)
        return _mod
    with _lock:
        if _mod is not None or _tried:
            return _mod
        # validate BRPC_TPU_SANITIZE before latching _tried, before the
        # broad except, and before the BRPC_TPU_NO_NATIVE short-circuit:
        # a typo must raise — on EVERY call, not just the first — never
        # silently drop both native and sanitizer coverage via the
        # pure-Python fallback
        from brpc_tpu.native.build import (build_fastcore,
                                           check_no_native_conflict,
                                           sanitize_mode,
                                           sanitized_load_failure)
        san = sanitize_mode()
        if os.environ.get("BRPC_TPU_NO_NATIVE"):
            check_no_native_conflict(san)
            _latched_san = ""
            _tried = True
            return None
        try:
            path = build_fastcore()
            spec = importlib.util.spec_from_file_location(
                "_brpc_fastcore", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _mod = mod
        except Exception as e:
            _mod = None
            if san:
                # a VALID sanitize mode whose artifact fails to
                # build/load must be just as loud as a typo, and must
                # not latch _tried: proceeding on pure Python would
                # pass the run off as sanitized with zero coverage
                raise sanitized_load_failure(
                    san, "fastcore extension") from e
            import logging
            logging.getLogger("brpc_tpu.native").warning(
                "fastcore extension unavailable, running pure Python: "
                "%s", str(e)[-400:])
        _latched_san = os.environ.get("BRPC_TPU_SANITIZE", "")
        _tried = True
    return _mod


def available() -> bool:
    return get() is not None
