"""The yardstick's own arithmetic: samples to percentiles, counters to
deltas, a profiler trace to busy time, and the device's peaks."""
