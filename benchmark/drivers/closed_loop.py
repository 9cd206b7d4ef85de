"""Closed loop: every caller waits for its reply before its next call
(upstream's rdma_performance and multi_threaded_echo clients). Traffic
parameters: ``style`` ("sync": ``callers`` threads each in a call_sync
loop, upstream's multi_threaded_echo; "callback": ``depth`` calls kept
in flight on the deployment's channel through ``done=`` chains with one
completion thread, upstream's rdma_performance) and the deployment's
own keys.

A call completes when its response is delivered and its payload is
ready on the reply device. A sync caller waits for that on its own
thread. A ``done=`` callback, on the fabric's thread, only polls the
payload's readiness (it never blocks there), stamps and hands the call
over: ready at that moment, the call completed then; else it completes
when ``block_until_ready`` returns on the completion thread. The time a
finished call waits for that thread is the generator's own and is
stamped apart (``Stamps.handovers``). The slot issues its next call
after the verification."""

from __future__ import annotations

import queue
import threading
import time

from benchmark.lib.stamps import now_ns


class Window:
    """What the driver hands back: when the window ran and how busy the
    benchmark's own threads were in it."""

    def __init__(self):
        self.start_ns = 0
        self.end_ns = 0
        self.attempted = 0
        self.thread_cpu_s: dict = {}


def run(dep, traffic: dict, seconds: float, stamps, at_offsets=()) -> Window:
    """Measure for ``seconds``. ``at_offsets`` is [(offset_s, fn)]: the
    waiting main thread calls ``fn`` once the window is that old (the
    profiler starts this way)."""
    style = traffic.get("style", "callback")
    win = Window()
    stop = threading.Event()
    seq_lock = threading.Lock()
    next_seq = [dep.first_seq]

    def take_seq() -> int:
        with seq_lock:
            s = next_seq[0]
            next_seq[0] = s + 1
        return s

    def complete(seq, t_issue, cntl, t_done=None) -> None:
        """On a benchmark thread: check, wait for the payload unless the
        callback saw it ready at ``t_done``, stamp, verify."""
        try:
            with stamps.span("bench.wait_ready"):
                arrs = dep.response_arrays(seq, cntl)
                if t_done is None:
                    for a in arrs:
                        a.block_until_ready()
                    t_done = now_ns()
            with stamps.span("bench.verify"):
                dep.verify(seq, cntl, arrs)
            stamps.calls.append((seq, t_issue, t_done))
        except Exception as e:  # noqa: BLE001 - a failed call, counted
            stamps.fail(seq, f"{type(e).__name__}: {e}"[:300])

    threads = []
    if style == "sync":
        def caller(idx: int) -> None:
            c0 = time.thread_time()
            while not stop.is_set():
                seq = take_seq()
                t_issue = now_ns()
                with stamps.span("bench.issue"):
                    cntl = dep.call_sync(seq)
                complete(seq, t_issue, cntl)
            win.thread_cpu_s[f"caller-{idx}"] = time.thread_time() - c0
        for i in range(int(traffic.get("callers", 1))):
            threads.append(threading.Thread(target=caller, args=(i,),
                                            name=f"bench-caller-{i}"))
    elif style == "callback":
        depth = int(traffic["depth"])
        done_q: queue.SimpleQueue = queue.SimpleQueue()

        def issue() -> None:
            seq = take_seq()
            t_issue = now_ns()

            def on_done(cntl) -> None:
                # the fabric's thread: poll, stamp, hand over
                try:
                    ready = dep.ready_now(cntl)
                except Exception:  # noqa: BLE001 - the completer reports
                    ready = False
                done_q.put((seq, t_issue, cntl, now_ns(), ready))
            with stamps.span("bench.issue"):
                dep.call(seq, on_done)

        def completer() -> None:
            c0 = time.thread_time()
            live = depth
            for _ in range(depth):
                issue()
            while live:
                seq, t_issue, cntl, t_done, ready = done_q.get()
                stamps.handovers.append((seq, t_done, now_ns(), ready))
                complete(seq, t_issue, cntl, t_done if ready else None)
                if stop.is_set():
                    live -= 1
                    continue
                issue()
            win.thread_cpu_s["issue+complete"] = time.thread_time() - c0
        threads.append(threading.Thread(target=completer,
                                        name="bench-completer"))
    else:
        raise ValueError(f"closed_loop: unknown style {style!r}")

    pending = sorted(at_offsets, key=lambda p: p[0])
    win.start_ns = now_ns()
    t_end = time.monotonic() + seconds
    for t in threads:
        t.start()
    while True:
        left = t_end - time.monotonic()
        if left <= 0:
            break
        if pending and seconds - left >= pending[0][0]:
            pending.pop(0)[1]()
            continue
        time.sleep(min(left, 0.02))
    stop.set()
    win.end_ns = now_ns()
    for t in threads:
        t.join(60)
        if t.is_alive():
            raise RuntimeError(f"{t.name} did not finish within 60 s of "
                               "the window's end")
    win.attempted = next_seq[0] - dep.first_seq
    return win
