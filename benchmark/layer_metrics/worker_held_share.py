"""Entry and dispatch: the share of the fiber workers' time that sync
handlers held them over the window, in %: ``usercode_held_us`` (delta
over the window, ``syscall_stats.snapshot()``) over window x
``fiber_workers`` (the pool's size, a gauge read now: it is fixed when
the pool starts). Nothing under a program without the counters."""


def read(run):
    held_us = run.counters["syscalls"].get("usercode_held_us")
    if held_us is None or run.window_s <= 0:
        return None
    from brpc_tpu.transport import syscall_stats
    workers = syscall_stats.snapshot().get("fiber_workers")
    if not workers:
        return None
    return 100.0 * held_us / (run.window_s * 1e6 * workers)
