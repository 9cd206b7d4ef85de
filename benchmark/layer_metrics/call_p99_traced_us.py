"""Entry and dispatch: the 99th percentile of the client-side call time,
in the cells where it swings too much from run to run to carry a bound
(PERF.md section 2 says which, and the spread seen)."""

from benchmark.end_to_end.call_p99_us import read  # noqa: F401
