"""Postfork-reset registry: fork-safety for process-global singletons.

Shard-group serving (rpc/shard_group.py) forks worker processes from a
supervisor that may already have live machinery: fiber workers, the
event-dispatcher thread, the timer thread, the bvar sampler, pooled
sockets, cached native pools. None of that survives ``os.fork()`` —
threads exist only in the forking parent, inherited locks may be held
by threads that no longer exist, and an inherited epoll fd is the SAME
kernel object as the parent's (mutating it from the child corrupts the
parent's poll set).

The registry makes the reset discipline explicit and lintable: every
module that caches a process-global singleton registers a reset
callback here at import time; the child side of ``os.register_at_fork``
runs them all, so the first post-fork use of each accessor rebuilds a
private instance with fresh threads and fresh locks. graftlint's
``postfork-reset`` rule enforces registration for any module that
grows a new singleton cache.

``subprocess.Popen`` is untouched: CPython's fork_exec does not run
``os.register_at_fork`` handlers, so spawned tools/tests keep their
exact semantics — only real ``os.fork()`` children (the shard workers)
pay the reset.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, List, Tuple

_lock = threading.Lock()
_resets: List[Tuple[str, Callable[[], None]]] = []
_installed = False
# bumped once per forked child, BEFORE the resets run: code that must
# detect "I crossed a fork" (debug accounting, cached pids) compares
# generations instead of re-deriving it from os.getpid()
_generation = 0
_reset_errors: List[str] = []


def register(name: str, fn: Callable[[], None]) -> None:
    """Register ``fn`` to run in every forked child. ``name`` is a
    stable identifier (module path) used for introspection and
    de-duplication — re-registering a name replaces its callback, so a
    reloaded module doesn't stack stale closures."""
    global _installed
    with _lock:
        for i, (n, _) in enumerate(_resets):
            if n == name:
                _resets[i] = (name, fn)
                break
        else:
            _resets.append((name, fn))
        if not _installed:
            _installed = True
            os.register_at_fork(after_in_child=reset_all)


def reset_all() -> None:
    """Run every registered reset (child side of fork). A failing
    reset must not stop the others — the remaining singletons still
    need their fresh state; failures are recorded for diagnostics
    (``reset_errors``) since logging itself may not be safe yet."""
    global _generation, _lock
    _generation += 1
    # the registry's own lock may have been held by a dead parent
    # thread at fork time: replace it first, so child-side register()
    # calls (fresh singletons re-registering) can't deadlock
    _lock = threading.Lock()
    _reset_errors.clear()
    # snapshot without the lock: the fork may have happened while some
    # other (now-dead) thread held _lock — taking it here would
    # deadlock the child on its first act
    for name, fn in list(_resets):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - must keep resetting
            _reset_errors.append(f"{name}: {type(e).__name__}: {e}")


def abandon(obj) -> None:
    """Child side: make an inherited device-runtime handle (a PjRt
    transfer server, its connections, arrays parked on a poller)
    immortal instead of dropping it. Its destructor would talk to
    runtime threads that exist only in the parent — under jaxlib 0.9
    the transfer server's crashes or hangs the child — so the reset
    takes one reference that is never given back; the memory is the
    parent's copy-on-write pages and dies with the child."""
    if obj is not None:
        import ctypes
        ctypes.pythonapi.Py_IncRef(ctypes.py_object(obj))


def registered_names() -> List[str]:
    return [n for n, _ in list(_resets)]


def generation() -> int:
    """0 in the original process, +1 per fork crossed."""
    return _generation


def reset_errors() -> List[str]:
    return list(_reset_errors)
