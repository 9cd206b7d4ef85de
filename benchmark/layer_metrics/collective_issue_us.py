"""Combo channel: a lowered call from its entry (span ``start_us``) to
the program dispatched (``dispatch_us``: ``jit`` returned): the
qualification, the scatter's hand-off and the launch of one program on
four chips, which is the host's whole cost of the call. Median over the
window's lowered calls that have spans (``lib/collective_calls.py``)."""

from benchmark.lib.collective_calls import stage_median


def read(run):
    return stage_median(run, "issue")
