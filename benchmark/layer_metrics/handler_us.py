"""Service: median of the handler's own time, stamped around it by the
benchmark (the enqueue it does, not the device work it launches)."""

from benchmark.lib.stats import median


def read(run):
    durs = [(t1 - t0) / 1e3 for hs in run.handlers.values()
            for _s, t0, t1 in hs]
    return median(durs) if durs else None
