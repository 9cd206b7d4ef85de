"""TimerThread: one dedicated thread, nearest-deadline sleep
(bthread/timer_thread.h:53). Backs fiber sleeps, RPC timeouts, butex wait
timeouts, and periodic tasks."""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from typing import Callable, Dict, Optional

from brpc_tpu.butil import thread_cpu
from brpc_tpu.fiber.scheduler import Fiber, SchedAwaitable


class TimerThread:
    def __init__(self, name: str = "fiber_timer"):
        self._cond = threading.Condition()
        self._heap: list = []          # (deadline, tid, [fn]) — fn boxed so
        #                                unschedule can drop it eagerly
        self._boxes: Dict[int, list] = {}
        self._ndead = 0                # cancelled entries still heaped
        self._seq = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._name = name

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(target=self._run, name=self._name,
                                            daemon=True)
            self._thread.start()

    def schedule_at(self, deadline: float, fn: Callable[[], None]) -> int:
        """deadline is time.monotonic() seconds; returns a timer id."""
        with self._cond:
            tid = next(self._seq)
            box = [fn]
            self._boxes[tid] = box
            # wake the timer thread only when this deadline BEATS the
            # current front: its ongoing sleep already covers any later
            # deadline, and an unconditional notify costs a thread wake
            # per armed RPC deadline (nearest-deadline discipline,
            # timer_thread.cpp)
            wake = not self._heap or deadline < self._heap[0][0]
            heapq.heappush(self._heap, (deadline, tid, box))
            self._ensure_thread()
            if wake:
                self._cond.notify()
        return tid

    def schedule_after(self, delay_s: float, fn: Callable[[], None]) -> int:
        return self.schedule_at(time.monotonic() + max(0.0, delay_s), fn)

    def unschedule(self, tid: int) -> None:
        """Cancel a timer and drop its callback NOW: an RPC deadline
        closure captures the Controller (and any device arrays it holds),
        so retaining it in the heap until the deadline would pin megabytes
        per completed call for the full timeout (seen as recv-pool
        exhaustion under pipelined load)."""
        with self._cond:
            box = self._boxes.pop(tid, None)
            if box is not None:
                box[0] = None
                self._ndead += 1
                # compact when dead entries dominate: without this, a
                # sync RPC stream arming+cancelling a 5s deadline per
                # call leaves thousands of dead fronts that expire
                # together later, and the timer thread's pop-storm
                # preempts the serving path it was protecting (measured
                # as p50 degrading run-over-run on one core)
                if self._ndead > 64 and self._ndead * 2 > len(self._heap):
                    self._heap = [e for e in self._heap
                                  if e[2][0] is not None]
                    heapq.heapify(self._heap)
                    self._ndead = 0

    def _run(self) -> None:
        thread_cpu.set_role("timer")
        while not self._stop:
            with self._cond:
                now = time.monotonic()
                while self._heap and self._heap[0][0] <= now:
                    deadline, tid, box = heapq.heappop(self._heap)
                    self._boxes.pop(tid, None)
                    fn = box[0]
                    if fn is None:
                        if self._ndead > 0:
                            self._ndead -= 1
                    else:
                        self._cond.release()
                        try:
                            fn()
                        except Exception:
                            import logging
                            logging.getLogger("brpc_tpu.fiber").exception(
                                "timer callback failed")
                        finally:
                            self._cond.acquire()
                        now = time.monotonic()
                wait = (self._heap[0][0] - now) if self._heap else 1.0
                self._cond.wait(min(max(wait, 0.0), 1.0))

    def pending(self) -> int:
        """Live (non-cancelled) timers in the heap — a per-connection
        timer leak is visible here long before the heap hurts."""
        with self._cond:
            return len(self._boxes)

    def stop(self) -> None:
        self._stop = True
        with self._cond:
            self._cond.notify()


_global_timer: Optional[TimerThread] = None
_lock = threading.Lock()


def global_timer() -> TimerThread:
    global _global_timer
    if _global_timer is None:
        with _lock:
            if _global_timer is None:
                _global_timer = TimerThread()
    return _global_timer


def _postfork_reset() -> None:
    """Fork hygiene: the timer thread died with the parent, and every
    heaped callback closes over parent-side state (RPC deadlines for
    calls the child never issued). Start from an empty heap."""
    global _global_timer, _lock
    _global_timer = None
    _lock = threading.Lock()


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("fiber.timer", _postfork_reset)

from brpc_tpu.butil import resource_census as _census  # noqa: E402
#   (census registration ships with the singleton it measures)

#   peek, never instantiate: a census scrape must not start the thread
_census.register("timers", lambda: {
    "count": _global_timer.pending() if _global_timer is not None else 0})


def sleep(seconds: float) -> SchedAwaitable:
    """Awaitable fiber sleep (bthread_usleep)."""

    class _Sleep(SchedAwaitable):
        def _register(self, fiber: Fiber):
            global_timer().schedule_after(
                seconds, lambda: fiber.control.schedule(fiber, None))
    return _Sleep()


def sleep_us(us: float) -> SchedAwaitable:
    return sleep(us / 1e6)


class PeriodicTask:
    """Re-arms itself after each run (brpc/periodic_task.*)."""

    def __init__(self, interval_s: float, fn: Callable[[], bool | None],
                 timer: Optional[TimerThread] = None):
        self._interval = interval_s
        self._fn = fn
        self._timer = timer or global_timer()
        self._stopped = False
        self._arm()

    def _arm(self):
        self._tid = self._timer.schedule_after(self._interval, self._tick)

    def _tick(self):
        if self._stopped:
            return
        keep = self._fn()
        if keep is not False and not self._stopped:
            self._arm()

    def stop(self):
        self._stopped = True
        self._timer.unschedule(self._tid)
