"""Entry and dispatch: server ``received_us`` to ``handler_start_us``:
the request's device take, dispatch queue, parse, hop to a worker.
Median over the window's calls that have spans (``lib/rpc_spans.py``)."""

from benchmark.lib.rpc_spans import stage_median


def read(run):
    return stage_median(run, "server_queue")
