"""Closed loop THROUGH a client in its own process: the window runs in
the deployment's child (``benchmark/drivers/remote_child.py``), which
keeps ``depth`` calls in flight on its one connection through ``done=``
chains from one issuing thread (upstream's rdma_performance client at
``queue_depth`` 8), stamps every call on ``time.monotonic_ns``
(CLOCK_MONOTONIC: the clock this process's stamps read too), and holds
every response to the reference off the timed path. This process only
tells the child to run the window, calls the ``at_offsets`` (the
profiler's start) on its own main thread meanwhile, and takes the
child's stamps and failures into ``stamps`` when the window is over.

A call runs from the child's issue stamp to its ``done=`` stamp, when
the reply's bytes are in the child's host memory. The window is the
child's: ``start_ns`` when it issued the first call, ``end_ns``
``seconds`` later; calls in flight then are awaited, stamped and
verified, and count as completed outside the window.

Traffic parameters: ``depth``, and the deployment's own keys. Where the
run is traced (``at_offsets`` holds the profiler's start) the child's
rpcz spans record from the earliest offset on, by the flag
``rpcz_enabled``: no profile runs in a process without jax."""

from __future__ import annotations

import time

from benchmark.drivers.closed_loop import Window


def run(dep, traffic: dict, seconds: float, stamps, at_offsets=()) -> Window:
    win = Window()
    pending = sorted(at_offsets, key=lambda p: p[0])
    spans_from_s = pending[0][0] if pending else None
    t0 = time.monotonic()

    def while_waiting() -> None:
        while pending and time.monotonic() - t0 >= pending[0][0]:
            pending.pop(0)[1]()

    reply = dep.run_window(seconds, int(traffic["depth"]), spans_from_s,
                           while_waiting)
    while pending:              # a window that ended early: still started
        pending.pop(0)[1]()
    win.start_ns, win.end_ns = reply["start_ns"], reply["end_ns"]
    win.attempted = reply["attempted"]
    stamps.calls.extend(tuple(c) for c in reply["calls"])
    for seq, reason in reply["failures"]:
        stamps.fail(seq, reason)
    win.thread_cpu_s = {"client-issue": reply["issue_cpu_s"],
                        "client-verify": reply["verify_cpu_s"]}
    return win
