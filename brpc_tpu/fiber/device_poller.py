"""DeviceEventPoller: park fibers on device/async futures — event-driven.

The north-star twist on the fork's RingListener/EloqModule design
(bthread/ring_listener.h:115, eloq_module.h:60): instead of an io_uring
CQE pump per worker group, device completions wake fibers through real
blocking waits, not polling:

* concurrent.futures.Future → its own ``add_done_callback`` (zero cost);
* jax.Array (and anything with ``block_until_ready``) → a small pool of
  waiter threads each parks INSIDE PjRt's C++ future wait (the GIL is
  released), so the wake is the runtime's own completion signal — the
  io_uring CQE analog — with µs latency instead of a sleep-loop quantum;
* exotic objects with only ``is_ready()`` → the legacy spin-then-sleep
  pump, kept as a fallback.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional, Tuple

from brpc_tpu.butil import thread_cpu
from brpc_tpu.fiber.scheduler import Fiber, SchedAwaitable
# device-thread labels for the flight recorder: the pump thread and the
# per-wait PjRt waiter threads run OUTSIDE any fiber, so without these
# stamps their busy samples fall to thread-name leaves instead of the
# device lane. Bound at module load (transport/__init__ is empty — no
# cycle; and the sampler side reads only, per the PR 8 lazy-import rule)
from brpc_tpu.transport.device_stats import (stamp_device_thread,
                                             unstamp_device_thread)

# cap on concurrently-parked waiter threads; beyond it new waits fall
# back to the fair poll pump (a bounded executor QUEUE would let 32
# stalled waits starve a ready one behind them)
_MAX_WAITERS = 128


def _is_ready(obj: Any) -> bool:
    ready_fn = getattr(obj, "is_ready", None)
    if ready_fn is not None:
        return bool(ready_fn())
    done_fn = getattr(obj, "done", None)  # concurrent.futures.Future
    if done_fn is not None:
        return bool(done_fn())
    return True


class DeviceEventPoller:
    """Event-driven waits with a polling fallback pump."""

    def __init__(self, name: str = "device_poller"):
        self._cond = threading.Condition()
        self._pending: List[Tuple[Any, Callable[[], None]]] = []
        self._thread: Optional[threading.Thread] = None
        self._name = name
        self._stop = False
        self._active_waiters = 0
        self._waiter_lock = threading.Lock()

    def watch(self, obj: Any, on_ready: Callable[[], None]) -> None:
        """Call on_ready() once obj becomes ready. Prefers real
        completion signals (done-callback / blocking C++ wait) over
        polling."""
        add_cb = getattr(obj, "add_done_callback", None)
        if add_cb is not None:
            add_cb(lambda _f: on_ready())
            return
        if _is_ready(obj):
            on_ready()
            return
        block = getattr(obj, "block_until_ready", None)
        if block is not None:
            with self._waiter_lock:
                can_wait = self._active_waiters < _MAX_WAITERS
                if can_wait:
                    self._active_waiters += 1
            if can_wait:
                def wait_and_fire():
                    # the thread dies with its wait: its CPU stays in
                    # the role's total (thread_cpu's exit watch)
                    thread_cpu.set_role("device_wait")
                    stamp_device_thread("device:wait")
                    try:
                        block()       # parks in PjRt's future (GIL freed)
                    except Exception:
                        pass          # errors surface at use time
                    finally:
                        with self._waiter_lock:
                            self._active_waiters -= 1
                    try:
                        on_ready()
                    except Exception:
                        import logging
                        logging.getLogger("brpc_tpu.fiber").exception(
                            "device waiter callback failed")
                    finally:
                        # per-wait threads die here: an un-popped label
                        # would pin dict entries for dead tids
                        unstamp_device_thread()
                # one daemon thread per in-flight wait: a stalled wait
                # pins only its own thread (no executor queue to starve
                # ready objects behind it) and cannot hang interpreter
                # exit the way non-daemon pool threads would
                threading.Thread(target=wait_and_fire,
                                 name=f"{self._name}_wait",
                                 daemon=True).start()
                return
            # over the cap: fall through to the fair poll pump
        with self._cond:
            self._pending.append((obj, on_ready))
            self._ensure_thread()
            self._cond.notify()

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(target=self._run, name=self._name,
                                            daemon=True)
            self._thread.start()

    def _run(self):
        import time
        # the pump's busy samples (is_ready sweeps over pending device
        # objects) belong to the device lane on /hotspots; the unstamp
        # rides a finally — a pump killed by a throwing is_ready must
        # not leave a stale label for the OS to hand a reused tid
        thread_cpu.set_role("device_wait")
        stamp_device_thread(f"device:{self._name}")
        try:
            self._run_inner(time)
        finally:
            unstamp_device_thread()

    def _run_inner(self, time):
        idle_spins = 0
        while not self._stop:
            with self._cond:
                if not self._pending:
                    self._cond.wait(0.5)
                    continue
                pending = self._pending
                self._pending = []
            still = []
            fired = 0
            for obj, cb in pending:
                if _is_ready(obj):
                    fired += 1
                    try:
                        cb()
                    except Exception:
                        import logging
                        logging.getLogger("brpc_tpu.fiber").exception(
                            "device poller callback failed")
                else:
                    still.append((obj, cb))
            if still:
                with self._cond:
                    self._pending.extend(still)
            if fired:
                idle_spins = 0
            else:
                # adaptive backoff: spin a few rounds (device events complete
                # in µs), then sleep a little to spare the host
                idle_spins += 1
                if idle_spins > 64:
                    # graftlint: disable=event-wait-not-sleep -- 200µs
                    # adaptive backoff between device-event poll spins:
                    # stop() is a _cond notify away and a 200µs tail is
                    # noise; an Event.wait at this period would only add
                    # lock traffic to the µs-scale completion path
                    time.sleep(0.0002)

    def stop(self):
        self._stop = True
        with self._cond:
            self._cond.notify()


_global_poller: Optional[DeviceEventPoller] = None
_lock = threading.Lock()


def global_poller() -> DeviceEventPoller:
    global _global_poller
    if _global_poller is None:
        with _lock:
            if _global_poller is None:
                _global_poller = DeviceEventPoller()
    return _global_poller


def _postfork_reset() -> None:
    """Fork hygiene: the poller thread and its parked fibers belong to
    the parent's scheduler; a fresh child polls nothing yet. The old
    poller may hold device arrays it was watching — abandoned, so the
    child never runs a runtime destructor (postfork.abandon)."""
    global _global_poller, _lock
    postfork.abandon(_global_poller)
    _global_poller = None
    _lock = threading.Lock()


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("fiber.device_poller", _postfork_reset)


def device_ready(obj: Any) -> SchedAwaitable:
    """Awaitable: park the fiber until a jax.Array / Future is ready, then
    resume with the object itself (its result for Futures)."""

    class _Ready(SchedAwaitable):
        def _register(self, fiber: Fiber):
            def on_ready():
                result = obj
                res_fn = getattr(obj, "result", None)
                if res_fn is not None and hasattr(obj, "done"):
                    try:
                        result = res_fn()
                    except Exception:
                        result = obj
                fiber.control.schedule(fiber, result)
            global_poller().watch(obj, on_ready)
    return _Ready()
