"""Kernels: median device duration of the Moonlight decode program
(``jit_moonlight_decode``: one token for all 192 slots) in the trace."""

from benchmark.lib.stats import median

PROGRAM = "moonlight_decode"


def read(run):
    if run.trace is None:
        return None
    durs = run.trace.program_durations_us(PROGRAM, run.trace_devices)
    return median(durs) if durs else None
