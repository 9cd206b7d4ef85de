"""Socket and framing: CPU time of the event thread over the window per
verified call (``cpu_us_dispatcher`` of ``syscall_stats.snapshot()``,
the thread's own CPU clock read at the window's two edges;
``butil/thread_cpu.py``). With ``cpu_us_per_call_workers``,
``cpu_us_per_call_callers`` and ``cpu_us_per_call_native`` it splits
``host_cpu_us_per_call`` by who burned it (the probe's own thread, in a
traced run, is in none of the four). A program that does not read its
threads' clocks reports nothing."""


def read(run):
    s = run.counters["syscalls"]
    if "cpu_us_dispatcher" not in s or not run.verified_calls:
        return None
    return s["cpu_us_dispatcher"] / run.verified_calls
