"""Device lane: the process's CPU time over the window (``getrusage``,
the reading ``host_cpu_us_per_call`` uses) minus that of every Python
thread (``cpu_us_python`` of ``syscall_stats.snapshot()``), per verified
call: threads Python did not start (PjRt, libtpu, the XLA runtime),
which hold no interpreter. A program that does not read its threads'
clocks reports nothing."""


def read(run):
    s = run.counters["syscalls"]
    if "cpu_us_python" not in s or not run.verified_calls:
        return None
    native_us = run.counters["cpu_s"] * 1e6 - s["cpu_us_python"]
    return native_us / run.verified_calls
