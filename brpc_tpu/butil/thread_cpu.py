"""CPU time by thread role: who burns the host's CPU behind one
interpreter lock.

Every thread the fabric starts says what it is where it starts
(``set_role``): ``dispatcher`` (the event thread), ``worker`` (a fiber
worker), ``timer``, ``device_wait`` (a waiter parked in PjRt),
``probe`` (``butil/interp_probe.py``). A Python thread that said
nothing is the application's: ``caller``. ``snapshot()`` walks
``threading.enumerate()``, reads each live thread's CPU clock and sums
by role; nothing is stamped on any call's path, a reading costs one
``clock_gettime`` a thread.

Threads die (a waiter a wait, an application's callers with their
loop), and a dead thread has no clock. A thread's first ``set_role``,
or its first write to any bvar reducer (``watch_exit``, from the
branch that makes the thread's agent), leaves an object in the
thread's locals; CPython drops a thread's locals on that thread as it
ends, and the object's finalizer adds the thread's own CPU to its
role's total. So a role's total never falls, and an application thread
that used the fabric and ended is still in ``caller``. One that never
touched a bvar and ended is nobody's: it falls to what the reader
calls native (process CPU minus ``cpu_us_python``), with the threads
Python did not start (PjRt, libtpu, XLA), which are skipped here even
where they once ran a callback.
"""

from __future__ import annotations

import os
import threading
import time

from brpc_tpu.butil import postfork

ROLES = ("dispatcher", "worker", "timer", "device_wait", "probe", "caller")

_tls = threading.local()
_lock = threading.Lock()
_roles: dict = {}                       # thread ident -> role, live threads
_retired = dict.fromkeys(ROLES, 0)      # us of threads that ended, by role
# where another thread's CPU is read, decided at the first snapshot:
# "cpuclock" (clock_gettime on the thread's CPU clock), "proc" (the
# thread's /proc/self/task/<tid>/stat), "" (neither: no keys at all)
_source = None


class _ExitWatch:
    """Lives in its thread's locals and dies with them."""

    __slots__ = ("ident", "role")

    def __init__(self):
        self.ident = threading.get_ident()
        self.role = "caller"

    def __del__(self):
        # the thread's own clock: only on the thread itself (a watch
        # dropped elsewhere, at interpreter exit, counts nothing)
        try:
            if threading.get_ident() != self.ident:
                return
            us = time.thread_time_ns() // 1000
            with _lock:
                _retired[self.role] += us
                _roles.pop(self.ident, None)
        except Exception:  # noqa: BLE001 - teardown: globals may be gone
            pass


def watch_exit() -> _ExitWatch:
    """Have this thread's CPU counted after it ends (once a thread)."""
    w = getattr(_tls, "watch", None)
    if w is None:
        w = _tls.watch = _ExitWatch()
    return w


def set_role(role: str) -> None:
    """Called by a fabric thread as it starts."""
    w = watch_exit()
    w.role = role
    _roles[w.ident] = role


def _clock_us(tid: int) -> int:
    # the id glibc's pthread_getcpuclockid() makes, built from the
    # kernel's thread id: a thread that ended since enumerate() is an
    # OSError here, not a read through a stale pthread_t
    return time.clock_gettime_ns((~tid << 3) | 6) // 1000


_TICK_US = 1_000_000 // os.sysconf("SC_CLK_TCK")


def _proc_us(tid: int) -> int:
    with open(f"/proc/self/task/{tid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) * _TICK_US   # utime+stime


_READERS = {"cpuclock": _clock_us, "proc": _proc_us}


def source() -> str:
    """Which reading this host allows, tried on the calling thread."""
    global _source
    if _source is None:
        tid = threading.get_native_id()
        for name, fn in _READERS.items():
            try:
                fn(tid)
            except (OSError, ValueError, IndexError, OverflowError):
                continue
            _source = name
            break
        else:
            _source = ""
    return _source


def by_role() -> dict:
    """{role: CPU us since process start}, live threads and ended ones;
    {} where the host allows no reading of another thread's clock."""
    read = _READERS.get(source())
    if read is None:
        return {}
    # ended threads first: one that ends between the two readings is
    # missed this once, never counted twice
    with _lock:
        out = dict(_retired)
    for t in threading.enumerate():
        tid = t.native_id
        if tid is None or isinstance(t, threading._DummyThread):
            continue
        try:
            us = read(tid)
        except (OSError, ValueError, IndexError):
            continue        # ended since enumerate()
        out[_roles.get(t.ident, "caller")] += us
    return out


_recent = (0.0, {})


def by_role_recent(max_age_s: float = 0.5) -> dict:
    """``by_role()`` no older than ``max_age_s``: the six ``/vars``
    readers of one scrape share one walk of the threads."""
    global _recent
    at, roles = _recent
    now = time.monotonic()
    if now - at > max_age_s:
        roles = by_role()
        _recent = (now, roles)
    return roles


def snapshot() -> dict:
    """``cpu_us_<role>`` and their sum ``cpu_us_python``."""
    roles = by_role()
    out = {f"cpu_us_{r}": us for r, us in roles.items()}
    if out:
        out["cpu_us_python"] = sum(roles.values())
    return out


def _postfork_reset() -> None:
    """The child's process clock starts at zero and it has one thread."""
    global _lock, _recent
    _lock = threading.Lock()
    _recent = (0.0, {})
    _roles.clear()
    for r in ROLES:
        _retired[r] = 0
    w = getattr(_tls, "watch", None)
    if w is not None:
        w.role = "caller"


postfork.register("butil.thread_cpu", _postfork_reset)
