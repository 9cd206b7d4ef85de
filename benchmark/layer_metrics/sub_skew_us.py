"""Combo channel: median over calls of the last shard handler's start
minus the first's (the slowest of N parts sets the call)."""

from benchmark.lib.stats import median


def read(run):
    skews = [(max(t0 for _s, t0, _t1 in hs) - min(t0 for _s, t0, _t1 in hs))
             / 1e3 for hs in run.handlers.values() if len(hs) > 1]
    return median(skews) if skews else None
