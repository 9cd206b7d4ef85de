"""Entry and dispatch: the SERVER process's CPU time over the window
(``getrusage`` of the process that holds the chip: the event thread, the
workers, the staged lane's copies, PjRt) per verified call, in a cell
whose client is another process and is not in it
(``remote_client_cpu_us_per_call`` has its side). The quantity is
``host_cpu_us_per_call``'s; that entry moves ``calls_per_s``, which this
cell does not report (PERF.md section 2 says why), so it cannot list the
cell, and the reading stands here beside the client's."""


def read(run):
    if not run.verified_calls or not run.counters["cpu_s"]:
        return None
    return run.counters["cpu_s"] * 1e6 / run.verified_calls
