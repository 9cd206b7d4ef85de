"""Entry and dispatch: the process's CPU time over the window (server,
client and the benchmark's own threads share it) per verified call."""


def read(run):
    if not run.verified_calls:
        return None
    return run.counters["cpu_s"] * 1e6 / run.verified_calls
