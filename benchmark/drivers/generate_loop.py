"""Closed loop of generations that outlast the window: the deployment
keeps ``depth`` Generate calls in flight from its set-up on (each
completion issues the next call), so the slots are out of step with
each other before any window opens; this driver only marks a window on
that running loop.

A sample is a generation that COMPLETED inside the window, timed from
its issue (which may lie before the window) to its reply at the client:
the median generation time and the completions per second of a loop
that never drains. Samples are numbered from ``dep.first_seq`` in the
order they complete. Generations still running at the window's end are
counted in ``attempted`` and not awaited; what completes or fails after
it is not the window's. The deployment's loop (``dep.loop``) has
``open_window(stamps, first_sample)``, ``close_window() -> attempted``
and ``thread_cpu_s()``."""

from __future__ import annotations

import time

from benchmark.drivers.closed_loop import Window
from benchmark.lib.stamps import now_ns


def run(dep, traffic: dict, seconds: float, stamps, at_offsets=()) -> Window:
    win = Window()
    pending = sorted(at_offsets, key=lambda p: p[0])
    loop = dep.loop
    win.start_ns = now_ns()
    loop.open_window(stamps, dep.first_seq)
    t_end = time.monotonic() + seconds
    while True:
        left = t_end - time.monotonic()
        if left <= 0:
            break
        if pending and seconds - left >= pending[0][0]:
            pending.pop(0)[1]()
            continue
        time.sleep(min(left, 0.02))
    win.end_ns = now_ns()
    win.attempted = loop.close_window()
    win.thread_cpu_s = loop.thread_cpu_s()
    return win
