"""Entry and dispatch: CPU time of the fabric's own threads other than
the event thread over the window per verified call: the fiber workers
(``cpu_us_worker``: handlers, parses, response writes), the timer
thread (``cpu_us_timer``) and the device waiters (``cpu_us_device_wait``,
threads that park in PjRt and die with their wait; their CPU is kept by
role after they end). All from ``syscall_stats.snapshot()``; a program
that does not read its threads' clocks reports nothing."""

_ROLES = ("cpu_us_worker", "cpu_us_timer", "cpu_us_device_wait")


def read(run):
    s = run.counters["syscalls"]
    if any(k not in s for k in _ROLES) or not run.verified_calls:
        return None
    return sum(s[k] for k in _ROLES) / run.verified_calls
