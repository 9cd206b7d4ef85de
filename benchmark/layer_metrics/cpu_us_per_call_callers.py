"""Entry and dispatch: CPU time over the window, per verified call, of
the Python threads the fabric did not start (``cpu_us_caller`` of
``syscall_stats.snapshot()``): the application's, here the benchmark's
sync callers, its issue-and-complete thread, the verifier and the main
thread, inside ``Channel.call`` and outside it. Threads that ended
before the window's second reading are in it (their CPU is kept as they
end). A program that does not read its threads' clocks reports
nothing."""


def read(run):
    s = run.counters["syscalls"]
    if "cpu_us_caller" not in s or not run.verified_calls:
        return None
    return s["cpu_us_caller"] / run.verified_calls
