"""Combo channel: of the window's calls for which a lowering was tried,
the share that ran as one collective, in percent: the program's
``parallel_collective_fused`` / (``fused`` + ``parallel_collective_
fallbacks``) between the service's window marks
(``lib/collective_calls.py``). 100, or the run is not ``correct``."""

from benchmark.lib.collective_calls import window_counters


def read(run):
    c = window_counters()
    if not c or not c["fused"] + c["fallbacks"]:
        return None
    return 100.0 * c["fused"] / (c["fused"] + c["fallbacks"])
