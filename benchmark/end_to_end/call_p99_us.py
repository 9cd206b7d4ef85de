"""99th percentile of the client-side call time; reported only where at
least ten samples lie beyond it."""

from benchmark.lib.stats import tail


def read(run):
    return tail(run.latencies_us, 0.99)
