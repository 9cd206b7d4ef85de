"""Builds libbrpc_tpu_native.so from src/*.cc with g++.

Invoked automatically on first import of brpc_tpu.native. An artifact is
current when its ``.tag`` sidecar holds the hash of the compiler, the
flags and the source BYTES it was built from — not when it is newer than
the sources: the ``.so`` files are ignored by git, so one left on disk by
an earlier checkout has an mtime that says nothing about the files git
would commit. Can also be run directly:
    python -m brpc_tpu.native.build

Sanitizer lane: with BRPC_TPU_SANITIZE set (e.g. "address,undefined"),
both artifacts build under the requested sanitizers into SEPARATE
``.san.so`` files with their own staleness cache, so the fast lane's
plain artifacts are never clobbered by an instrumented build (and vice
versa). Loading an ASan-instrumented extension requires the sanitizer
runtime to be FIRST in the link order, which a stock CPython is not —
run the interpreter with the env from ``sanitizer_env()`` (LD_PRELOAD
of libasan/libubsan + leak detection off for CPython's arena leaks).
The tier-2 test lane (tests/test_sanitizer_lane.py) and the preflight
gate (tools/preflight.py --gate) both drive this path.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Optional, Sequence, Tuple

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(_DIR, "src")
LIB_PATH = os.path.join(_DIR, "libbrpc_tpu_native.so")

CXX = os.environ.get("CXX", "g++")
CXXFLAGS = ["-O2", "-g", "-std=c++17", "-fPIC", "-shared", "-pthread",
            "-Wall", "-Wextra", "-fno-exceptions"]

# supported BRPC_TPU_SANITIZE tokens -> compiler flag groups
_SANITIZERS = {
    "address": ["-fsanitize=address"],
    "undefined": ["-fsanitize=undefined"],
    "thread": ["-fsanitize=thread"],
}
_SAN_COMMON = ["-fno-omit-frame-pointer", "-fno-sanitize-recover=all"]
# sanitizer token -> runtime library the host interpreter must preload
_SAN_RUNTIMES = {"address": "libasan.so", "undefined": "libubsan.so",
                 "thread": "libtsan.so"}


# fastcore.cc is a CPython extension module (needs Python headers,
# exports PyInit__brpc_fastcore) — built separately from the C-ABI lib.
FASTCORE_SRCS = ("fastcore.cc", "respool.cc", "queues.cc", "httpparse.cc")
FASTCORE_PATH = os.path.join(_DIR, "_brpc_fastcore.so")


def sanitize_mode(env: Optional[str] = None) -> Tuple[str, ...]:
    """Parse BRPC_TPU_SANITIZE (or the given string) into a normalized
    sanitizer tuple; unknown tokens raise so a typo can't silently run
    the uninstrumented lane while claiming sanitizer coverage."""
    raw = os.environ.get("BRPC_TPU_SANITIZE", "") if env is None else env
    out = []
    for tok in raw.replace(";", ",").split(","):
        tok = tok.strip().lower()
        if not tok:
            continue
        if tok not in _SANITIZERS:
            raise ValueError(
                f"BRPC_TPU_SANITIZE: unknown sanitizer {tok!r} "
                f"(known: {', '.join(sorted(_SANITIZERS))})")
        if tok not in out:
            out.append(tok)
    return tuple(out)


def check_no_native_conflict(san: Sequence[str]) -> None:
    """Raise when BRPC_TPU_NO_NATIVE would silently drop an active
    sanitize mode: disabling the native lane runs pure Python while
    the env claims sanitizer coverage."""
    if san:
        raise RuntimeError(
            "BRPC_TPU_SANITIZE=%s conflicts with BRPC_TPU_NO_NATIVE: "
            "disabling the native lane would run pure Python while "
            "the env claims sanitizer coverage" % ",".join(san))


def sanitized_load_failure(san: Sequence[str],
                           what: str) -> RuntimeError:
    """The error for a sanitized artifact that failed to build or
    load — raised instead of the silent uninstrumented fallback."""
    return RuntimeError(
        "BRPC_TPU_SANITIZE=%s is set but the sanitized %s failed to "
        "build or load; refusing the uninstrumented pure-Python "
        "fallback. Run the interpreter with the env from "
        "brpc_tpu.native.build.sanitizer_env() (LD_PRELOAD of the "
        "sanitizer runtimes)." % (",".join(san), what))


def sanitize_changed_error(latched: Optional[str]) -> RuntimeError:
    """The error for BRPC_TPU_SANITIZE changing AFTER a native loader
    latched its cache: the cached artifact no longer matches the
    requested instrumentation."""
    cur = os.environ.get("BRPC_TPU_SANITIZE", "")
    return RuntimeError(
        "BRPC_TPU_SANITIZE changed to %r after the native loader "
        "latched under %r: the cached artifact no longer matches the "
        "requested instrumentation — set the env before the first "
        "native use, or restart the process" % (cur, latched or ""))


def _san_path(base: str, san: Sequence[str]) -> str:
    """Artifact path for a sanitizer combo: foo.so -> foo.san.so (one
    cache per combo would be overkill; the flags are part of the
    artifact's tag, so a different combo forces a rebuild)."""
    if not san:
        return base
    root, ext = os.path.splitext(base)
    return f"{root}.san{ext}"


def _cxxflags(san: Sequence[str]) -> List[str]:
    """Base flags + sanitizer instrumentation for a build."""
    flags = list(CXXFLAGS)
    for tok in san:
        flags.extend(_SANITIZERS[tok])
    if san:
        flags.extend(_SAN_COMMON)
    return flags


def _tag_path(out_path: str) -> str:
    return out_path + ".tag"


def _build_key(cmd: Sequence[str], srcs: Sequence[str]) -> str:
    """Hash of what the artifact is made from: the command line short
    of the output path (compiler, flags incl. sanitizers, include dir)
    and every source's name and bytes."""
    import hashlib
    h = hashlib.sha256("\0".join(cmd).encode())
    for src in srcs:
        h.update(b"\0" + os.path.basename(src).encode() + b"\0")
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _stale(out_path: str, key: str) -> bool:
    if not os.path.exists(out_path):
        return True
    try:
        with open(_tag_path(out_path)) as f:
            return f.read().strip() != key
    except OSError:
        return True


def _compile(what: str, cmd: List[str], srcs: Sequence[str], out: str,
             force: bool) -> str:
    """Run ``cmd -o out srcs`` unless ``out`` is current. The compiler
    writes beside ``out`` and the result is renamed into place, so a
    concurrent importer never loads a half-written library."""
    key = _build_key(cmd, srcs)
    if not force and not _stale(out, key):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"
    full = [*cmd, "-o", tmp, *srcs]
    # graftlint: disable=blocking-under-lock -- the loader latch lock IS
    # the single-flight compile guard: a concurrent importer must wait
    # for the one compiler run, not race a second cc1plus at the cache
    proc = subprocess.run(full, capture_output=True, text=True)
    if proc.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise RuntimeError(
            f"{what} build failed:\n$ {' '.join(full)}\n{proc.stderr}")
    os.replace(tmp, out)
    with open(_tag_path(out), "w") as f:
        f.write(key)
    return out


def sources() -> list:
    # fastcore.cc + httpparse.cc need Python headers: they belong to
    # the extension module build only
    return sorted(
        os.path.join(SRC_DIR, f) for f in os.listdir(SRC_DIR)
        if f.endswith(".cc") and f not in ("fastcore.cc", "httpparse.cc")
    )


def build(force: bool = False,
          sanitize: Optional[Sequence[str]] = None) -> str:
    """Compile if stale; returns the library path. Raises on failure.
    ``sanitize`` defaults to the BRPC_TPU_SANITIZE env setting."""
    san = sanitize_mode() if sanitize is None else tuple(sanitize)
    return _compile("native", [CXX, *_cxxflags(san)], sources(),
                    _san_path(LIB_PATH, san), force)


def build_fastcore(force: bool = False,
                   sanitize: Optional[Sequence[str]] = None) -> str:
    """Compile the _brpc_fastcore CPython extension if stale."""
    import sysconfig
    san = sanitize_mode() if sanitize is None else tuple(sanitize)
    include = sysconfig.get_paths()["include"]
    return _compile("fastcore", [CXX, *_cxxflags(san), f"-I{include}"],
                    [os.path.join(SRC_DIR, f) for f in FASTCORE_SRCS],
                    _san_path(FASTCORE_PATH, san), force)


def _runtime_lib(name: str) -> Optional[str]:
    """Absolute path of a sanitizer runtime (libasan.so / libubsan.so)
    via the compiler, or None when the toolchain lacks it."""
    try:
        proc = subprocess.run([CXX, f"-print-file-name={name}"],
                              capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    path = proc.stdout.strip()
    return path if path and os.path.isabs(path) and os.path.exists(path) \
        else None


def sanitizer_toolchain_missing(
        san: Sequence[str] = ("address", "undefined")) -> List[str]:
    """Names of the toolchain pieces missing for an instrumented build
    (empty list = ready): the compiler plus each requested sanitizer's
    runtime. The single probe authority for the preflight gate and the
    tier-2 test lane."""
    import shutil
    missing = []
    if shutil.which(CXX) is None:
        missing.append(CXX)
    for tok in san:
        lib = _SAN_RUNTIMES.get(tok)
        if lib and _runtime_lib(lib) is None:
            missing.append(lib)
    return missing


def sanitizer_env(san: Optional[Sequence[str]] = None) -> dict:
    """Environment overlay for RUNNING python against .san artifacts:
    LD_PRELOAD of the sanitizer runtimes (they must initialize before
    the interpreter) and options tuned for a CPython host process
    (leak detection off — the interpreter's arenas never fully free;
    abort on any real ASan/UBSan diagnosis so tests fail loudly).
    Returns {} when no sanitizer is configured."""
    san = sanitize_mode() if san is None else tuple(san)
    if not san:
        return {}
    preload = []
    for tok in san:
        lib = _SAN_RUNTIMES.get(tok)
        p = _runtime_lib(lib) if lib else None
        if p:
            preload.append(p)
    env = {
        "BRPC_TPU_SANITIZE": ",".join(san),
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1:"
                        "allocator_may_return_null=1",
        "UBSAN_OPTIONS": "halt_on_error=1:abort_on_error=1:"
                         "print_stacktrace=1",
    }
    if preload:
        prior = os.environ.get("LD_PRELOAD", "")
        env["LD_PRELOAD"] = " ".join(preload + ([prior] if prior else []))
    return env


if __name__ == "__main__":
    force = "--force" in sys.argv
    path = build(force=force)
    print(path)
    print(build_fastcore(force=force))
    if sanitize_mode():
        print("sanitizers:", ",".join(sanitize_mode()))
