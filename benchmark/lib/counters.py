"""The program's always-on counters, read as deltas over the window:
``/device`` cells, syscall counts, and the process's CPU time."""

from __future__ import annotations

import resource
import time

_CELL_SUMS = ("transfers", "completed", "failed", "bytes_out", "bytes_in",
              "leaked_bytes", "recv_transfers", "staged_fallbacks",
              "stage_us_sum", "wire_us_sum", "ack_us_sum", "recv_us_sum")


def snapshot() -> dict:
    from brpc_tpu.transport import device_stats, syscall_stats

    page = device_stats.device_page_payload(samples=0)
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {
        "cells": {k: {f: row.get(f, 0) for f in _CELL_SUMS}
                  for k, row in page["cells"].items()},
        "conns": page.get("conns", []),
        "syscalls": syscall_stats.snapshot(),
        "cpu_s": ru.ru_utime + ru.ru_stime,
        "wall_s": time.monotonic(),
    }


def delta(before: dict, after: dict) -> dict:
    cells = {}
    for key, row in after["cells"].items():
        old = before["cells"].get(key, {})
        cells[key] = {f: row[f] - old.get(f, 0) for f in _CELL_SUMS}
    lane = {f: sum(c[f] for c in cells.values()) for f in _CELL_SUMS}
    return {
        "cells": cells,
        "lane": lane,
        # lane state per connection at the window's end: window, grant,
        # coalesced frames, idle ACKs (cumulative since the dial)
        "conns": after["conns"],
        "syscalls": {k: after["syscalls"][k] - before["syscalls"].get(k, 0)
                     for k in after["syscalls"]},
        "cpu_s": after["cpu_s"] - before["cpu_s"],
        "wall_s": after["wall_s"] - before["wall_s"],
    }


def settle_and_check(before: dict, timeout_s: float = 10.0) -> list:
    """After the window every (peer, lane) cell the window used must
    balance: every transfer completed, none failed, nothing leaked since
    ``before`` (the snapshot at the window's start). Returns the problems
    (empty where it balances); acks may trail the last response, so it
    waits a bounded time for them. A cell the window never touched (a
    connection closed during set-up) is not the window's to answer for."""
    from brpc_tpu.transport import device_stats

    deadline = time.monotonic() + timeout_s
    while True:
        page = device_stats.device_page_payload(samples=0)
        bad, used = [], 0
        for key, row in page["cells"].items():
            old = before["cells"].get(key, {})
            if row["transfers"] == old.get("transfers", 0):
                continue
            used += 1
            open_ = row["transfers"] - row["completed"] - row["failed"]
            failed = row["failed"] - old.get("failed", 0)
            leaked = row["leaked_bytes"] - old.get("leaked_bytes", 0)
            if open_ or failed or leaked:
                bad.append(f"{key}: transfers={row['transfers']} "
                           f"completed={row['completed']} failed in the "
                           f"window={failed} leaked bytes={leaked}")
        if not bad or time.monotonic() >= deadline:
            break
        time.sleep(0.02)
    if not used:
        bad.append("no device transfer was counted")
    return bad
