"""Socket and framing: b5 to client ``first_byte_us`` (the frame cut):
loopback, the client reader's wake, read.
Median over the window's calls that have spans (``lib/rpc_spans.py``)."""

from benchmark.lib.rpc_spans import stage_median


def read(run):
    return stage_median(run, "response_wake")
