"""Entry and dispatch: client ``start_us`` to b1 = min(client
``write_done_us``, server ``received_us``): ``Channel._issue_rpc`` (meta,
framing, ``lane_lock``, lane hand-off, envelope write) and any wait at the
lane's window gate.
Median over the window's calls that have spans (``lib/rpc_spans.py``)."""

from benchmark.lib.rpc_spans import stage_median


def read(run):
    return stage_median(run, "issue")
