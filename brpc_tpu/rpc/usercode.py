"""Usercode backup pool (details/usercode_backup_pool.* +
usercode_in_pthread in the reference): run blocking user handlers on a
reserve pthread pool so fiber workers stay free to pump IO.

Enable with ``ServerOptions(usercode_in_pthread=True)`` — sync handlers
then run on the pool while the dispatch fiber awaits completion; async
handlers keep running on fibers (they are cooperative already)."""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from brpc_tpu.butil.flags import define_flag, flag
from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.fiber.sync import FiberEvent

define_flag("usercode_backup_threads", 16,
            "reserve pthreads for usercode_in_pthread handlers")

# how long sync handlers hold the thread they run on (a fiber worker, or
# a pool thread under usercode_in_pthread): wall us summed, handlers
# run, and those that held over 1 ms. Always on; stamped where the
# handler returns by tpu_std's two request paths and the pool below
held_us = Adder()
runs = Adder()
over_1ms = Adder()


def note_held(ns: int) -> None:
    """One sync handler held its thread ``ns`` nanoseconds."""
    held_us.add(ns // 1000)
    runs.add(1)
    if ns > 1_000_000:
        over_1ms.add(1)


def counters() -> dict:
    return {"usercode_held_us": held_us.get_value() or 0,
            "usercode_runs": runs.get_value() or 0,
            "usercode_over_1ms": over_1ms.get_value() or 0}


def expose_usercode_vars() -> None:
    """(Re-)expose the three on ``/vars``: at import and again from
    ``Server.start``, like the other counters that must outlive a test
    fixture's unexpose_all."""
    held_us.expose("usercode_held_us")
    runs.expose("usercode_runs")
    over_1ms.expose("usercode_over_1ms")


expose_usercode_vars()


_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _get_pool() -> ThreadPoolExecutor:
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=flag("usercode_backup_threads"),
                thread_name_prefix="usercode")
        return _pool


def _postfork_reset() -> None:
    """Fork hygiene: the executor's pthreads exist only in the parent
    — submitting to the inherited pool would queue work nobody runs."""
    global _pool, _pool_lock
    _pool = None
    _pool_lock = threading.Lock()


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("rpc.usercode", _postfork_reset)


async def run_usercode(fn, *args):
    """Run ``fn(*args)`` on the backup pool; the calling fiber suspends
    (not its worker thread) until done."""
    done = FiberEvent()
    box: list = [None, None]

    def run():
        t0 = time.monotonic_ns()
        try:
            box[0] = fn(*args)
        except BaseException as e:
            box[1] = e
        note_held(time.monotonic_ns() - t0)
        done.set()

    _get_pool().submit(run)
    await done.wait()
    if box[1] is not None:
        raise box[1]
    return box[0]
