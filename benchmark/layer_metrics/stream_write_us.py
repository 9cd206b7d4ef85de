"""Streaming: a data frame from the entry to ``Stream.write`` (sender
``write_start_us``) to b = min(sender ``write_done_us``, receiver
``received_us``): the wait for a credit, meta, pack, lane hand-off and
the socket's gather write. Median over the frames and hops of the window
that have both spans (``lib/stream_frames.py``)."""

from benchmark.lib.stream_frames import stage_median


def read(run):
    return stage_median(run, "write")
