"""Open loop: calls are issued on a schedule, whether or not earlier ones
have come back (upstream's benchmark at a fixed QPS, docs/cn/benchmark.md;
independent callers send on THEIR clock). Traffic parameters:
``rate_calls_per_s`` (``rehearse_rate_calls_per_s`` in a CPU rehearsal)
and the deployment's own keys.

The deployment owns the schedule: ``dep.plan(rate, seconds)`` gives the
window's arrivals (each with ``at_s`` and ``long``) as a pure function
of the seed, and arrival i is the call ``dep.first_seq + i``. One
generator thread sleeps to each arrival and issues ``dep.call(seq,
done)``; where it runs late it issues at once and drops or merges
nothing, and it ends only when every arrival inside the window is
issued. Completion is ``closed_loop``'s callback style: the ``done=``
callback, on the fabric's thread, polls the payload's readiness, stamps
and hands over; one completion thread waits, verifies, stamps.

A call's time runs from its SCHEDULED arrival to its payload ready on
the reply device, so a late generator or a queue in front of the server
lengthens it (no coordinated omission). As upstream, whose long-tail
requests' own latencies are left out "because what is examined is
whether the normal requests are handled in time": ``stamps.calls`` holds
the SHORT calls only; long calls are verified and counted in
``attempted`` and the failures, and their times go to the ``open_loop``
info line, beside the issue lateness (issued minus scheduled)."""

from __future__ import annotations

import json
import queue
import threading
import time

from benchmark.drivers.closed_loop import Window
from benchmark.lib.stamps import now_ns
from benchmark.lib.stats import median, tail

LATE_NS = 5_000_000         # an issue this late is counted apart
JOIN_S = 60


def _by_second(offsets_s, seconds: float) -> list:
    counts = [0] * max(1, int(seconds))
    for t in offsets_s:
        counts[min(int(t), len(counts) - 1)] += 1
    return counts


def run(dep, traffic: dict, seconds: float, stamps, at_offsets=()) -> Window:
    """Measure for ``seconds``. ``at_offsets`` is [(offset_s, fn)]: the
    waiting main thread calls ``fn`` once the window is that old."""
    rate = float(traffic["rehearse_rate_calls_per_s"]
                 if dep.ctx.cell.rehearse else traffic["rate_calls_per_s"])
    arrivals = dep.plan(rate, seconds)
    first_seq = dep.first_seq
    win = Window()
    done_q: queue.SimpleQueue = queue.SimpleQueue()
    lateness_ns: list = []
    long_calls: list = []       # (seq, scheduled_ns, ready_ns)
    completed_in_window = [0]

    def issue(seq: int, t_sched: int) -> None:
        def on_done(cntl) -> None:
            # the fabric's thread: poll, stamp, hand over
            try:
                ready = dep.ready_now(cntl)
            except Exception:  # noqa: BLE001 - the completer reports
                ready = False
            done_q.put((seq, t_sched, cntl, now_ns(), ready))
        with stamps.span("bench.issue"):
            dep.call(seq, on_done)

    def generator() -> None:
        c0 = time.thread_time()
        for i, a in enumerate(arrivals):
            t_sched = win.start_ns + int(a.at_s * 1e9)
            wait = t_sched - now_ns()
            if wait > 0:
                time.sleep(wait / 1e9)
            lateness_ns.append(now_ns() - t_sched)
            try:
                issue(first_seq + i, t_sched)
            except Exception as e:  # noqa: BLE001 - a refused call, counted
                stamps.fail(first_seq + i, f"{type(e).__name__}: {e}"[:300])
                done_q.put(None)
        win.thread_cpu_s["generator"] = time.thread_time() - c0

    def completer() -> None:
        c0 = time.thread_time()
        for _ in range(len(arrivals)):
            item = done_q.get()
            if item is None:        # the issue itself failed
                continue
            seq, t_sched, cntl, t_done, ready = item
            stamps.handovers.append((seq, t_done, now_ns(), ready))
            try:
                with stamps.span("bench.wait_ready"):
                    arrs = dep.response_arrays(seq, cntl)
                    if not ready:
                        for arr in arrs:
                            arr.block_until_ready()
                        t_done = now_ns()
                with stamps.span("bench.verify"):
                    dep.verify(seq, cntl, arrs)
            except Exception as e:  # noqa: BLE001 - a failed call, counted
                stamps.fail(seq, f"{type(e).__name__}: {e}"[:300])
                continue
            if arrivals[seq - first_seq].long:
                long_calls.append((seq, t_sched, t_done))
            else:
                stamps.calls.append((seq, t_sched, t_done))
            if t_done <= win.start_ns + int(seconds * 1e9):
                completed_in_window[0] += 1
        win.thread_cpu_s["completer"] = time.thread_time() - c0

    threads = [threading.Thread(target=generator, name="bench-generator"),
               threading.Thread(target=completer, name="bench-completer")]
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
    win.end_ns = now_ns()
    for t in threads:
        t.join(JOIN_S)
        if t.is_alive():
            raise RuntimeError(f"{t.name} did not finish within {JOIN_S} s "
                               "of the window's end")
    win.attempted = len(arrivals)
    late_us = [ns / 1e3 for ns in lateness_ns]
    long_us = [(r - s) / 1e3 for _q, s, r in long_calls]
    print(json.dumps({"info": {"open_loop": {
        "seconds": seconds, "rate_calls_per_s": rate,
        "scheduled": len(arrivals),
        "scheduled_long": sum(1 for a in arrivals if a.long),
        "completed_in_window": completed_in_window[0],
        "issue_lateness_us": {
            "p50": median(late_us) if late_us else None,
            "p99": tail(late_us, 0.99),
            "max": max(late_us, default=None),
            "share_over_5ms": (sum(1 for ns in lateness_ns if ns > LATE_NS)
                               / len(lateness_ns) if lateness_ns else None),
            # when the generator ran late: issues over 5 ms late in each
            # whole second of the window (a stall of the host shows as
            # one burst, a saturated generator as a rising count)
            "over_5ms_by_second": _by_second(
                [a.at_s for a, ns in zip(arrivals, lateness_ns)
                 if ns > LATE_NS], seconds)},
        # the long calls' own client times: in no end-to-end metric
        "long_call_us": {"n": len(long_us),
                         "p50": median(long_us) if long_us else None,
                         "max": max(long_us, default=None)},
    }}}), flush=True)
    return win
