"""Median client-side time from issue to response ready on the device."""

from benchmark.lib.stats import median


def read(run):
    return median(run.latencies_us) if run.latencies_us else None
