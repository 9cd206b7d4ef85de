"""Socket and framing: server ``handler_end_us`` to b5 = min(server
``flushed_us``, client ``first_byte_us``): ``_send_response`` (pack, lane
hand-off, envelope write).
Median over the window's calls that have spans (``lib/rpc_spans.py``)."""

from benchmark.lib.rpc_spans import stage_median


def read(run):
    return stage_median(run, "response_write")
