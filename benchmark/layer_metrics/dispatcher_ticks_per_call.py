"""Socket and framing: wakeups of the process's event thread that fired
a callback, per verified call (``dispatcher_ticks`` of
``syscall_stats.snapshot()``, the dispatcher's ``_tick_seq``). A
level-triggered fd nobody pauses through a busy period shows here as
tens of ticks a call; a program that does not count them reports
nothing."""


def read(run):
    s = run.counters["syscalls"]
    if "dispatcher_ticks" not in s or not run.verified_calls:
        return None
    return s["dispatcher_ticks"] / run.verified_calls
