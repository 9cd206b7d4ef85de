"""Entry and dispatch: the mean time a thread that became runnable
waited for the host's scheduler and the interpreter lock, as the
program's probe saw it: a thread that sleeps 1 ms at a time while spans
record (the traced part of the window) and counts by how much each
sleep overshoots (``interp_probe_wait_us`` / ``interp_probe_n`` of
``syscall_stats.snapshot()``; ``butil/interp_probe.py``). Raw: the
host's own timer slack is in it. Nothing under a program without the
probe, or where it measured no sleep."""


def read(run):
    s = run.counters["syscalls"]
    if not s.get("interp_probe_n"):
        return None
    return s["interp_probe_wait_us"] / s["interp_probe_n"]
