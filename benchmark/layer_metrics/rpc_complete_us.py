"""Entry and dispatch: client ``first_byte_us`` to ``end_us``: the
response's device take, ``_fill_response``, completion hooks.
Median over the window's calls that have spans (``lib/rpc_spans.py``)."""

from benchmark.lib.rpc_spans import stage_median


def read(run):
    return stage_median(run, "complete")
