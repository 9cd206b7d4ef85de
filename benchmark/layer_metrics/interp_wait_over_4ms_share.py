"""Entry and dispatch: of the probe's sleeps (``interp_wait_us``), the
share that overshot by 4 ms or more (``interp_probe_over_4ms`` /
``interp_probe_n``): a wait that ended with the interpreter's forced
switch (5 ms) shows here, a hand-over at a syscall does not. A share
that reads 0 is reported as 0. Nothing under a program without the
probe, or where it measured no sleep."""


def read(run):
    s = run.counters["syscalls"]
    if not s.get("interp_probe_n"):
        return None
    return 100.0 * s["interp_probe_over_4ms"] / s["interp_probe_n"]
