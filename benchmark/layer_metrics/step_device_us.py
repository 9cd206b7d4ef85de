"""Kernels: median device duration of the ``Step`` program
(``jit_perf_step``) in the trace."""

from benchmark.lib.stats import median

PROGRAM = "perf_step"


def read(run):
    if run.trace is None:
        return None
    durs = run.trace.program_durations_us(PROGRAM, run.trace_devices)
    return median(durs) if durs else None
