"""Socket and framing: b1 to server ``received_us`` (the frame cut):
loopback, the event dispatcher's wake, read.
Median over the window's calls that have spans (``lib/rpc_spans.py``)."""

from benchmark.lib.rpc_spans import stage_median


def read(run):
    return stage_median(run, "request_wake")
