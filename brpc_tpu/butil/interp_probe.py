"""A probe of the wait for the interpreter, alive only while spans
record.

One daemon thread (role ``probe``) sleeps 1 ms at a time and measures
by how much each sleep overshoots: the time a thread that became
runnable waited for the host's scheduler and then for the interpreter
lock. A hand-over at a syscall costs it microseconds; a holder that
runs pure Python keeps it until the forced switch (5 ms,
``sys.getswitchinterval()``). The reading is raw: the host's own timer
slack (the probe in an idle process) is in it.

``rpc/span.recording()`` starts it when it first says yes and the
thread ends itself within a sleep of ``recording()`` saying no, so a
process that records nothing has no such thread and the four adders
stand still.
"""

from __future__ import annotations

import threading
import time
from typing import Callable

from brpc_tpu.butil import postfork, thread_cpu
from brpc_tpu.bvar.reducer import Adder

SLEEP_S = 0.001

probe_n = Adder()           # sleeps measured
probe_wait_us = Adder()     # sum of their overshoots
probe_over_1ms = Adder()    # sleeps that overshot by 1 ms or more
probe_over_4ms = Adder()    # ... by 4 ms or more: a forced switch

_start_lock = threading.Lock()
_running = False


def ensure_running(recording: Callable[[], bool]) -> None:
    """Start the probe unless it runs; ``recording`` is asked again
    before every sleep."""
    global _running
    if _running:
        return
    with _start_lock:
        if _running:
            return
        _running = True
    threading.Thread(target=_run, args=(recording,), name="interp_probe",
                     daemon=True).start()


def running() -> bool:
    return _running


def _run(recording: Callable[[], bool]) -> None:
    global _running
    thread_cpu.set_role("probe")
    clock = time.perf_counter_ns
    nominal_us = int(SLEEP_S * 1e6)
    try:
        while recording():
            t0 = clock()
            # graftlint: disable=event-wait-not-sleep -- the sleep is the
            # instrument: what is measured is this thread's way back
            # from a timed sleep to the interpreter; an Event.wait would
            # put a Condition's own lock traffic into every reading, and
            # the loop ends by itself within 1 ms of recording() going
            # false
            time.sleep(SLEEP_S)
            over = max(0, (clock() - t0) // 1000 - nominal_us)
            probe_n.add(1)
            probe_wait_us.add(over)
            if over >= 1000:
                probe_over_1ms.add(1)
                if over >= 4000:
                    probe_over_4ms.add(1)
    finally:
        with _start_lock:
            _running = False


def snapshot() -> dict:
    return {"interp_probe_n": probe_n.get_value(),
            "interp_probe_wait_us": probe_wait_us.get_value(),
            "interp_probe_over_1ms": probe_over_1ms.get_value(),
            "interp_probe_over_4ms": probe_over_4ms.get_value()}


def _postfork_reset() -> None:
    """The probe thread stayed in the parent."""
    global _running, _start_lock
    _running = False
    _start_lock = threading.Lock()


postfork.register("butil.interp_probe", _postfork_reset)
