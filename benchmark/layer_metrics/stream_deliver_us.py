"""Streaming: the receiver's ``received_us`` (the frame cut) to
``deliver_start_us`` (entry to ``on_received``): the frame's device take
and the hop through the stream's ExecutionQueue. Median over the frames
and hops of the window that have both spans (``lib/stream_frames.py``)."""

from benchmark.lib.stream_frames import stage_median


def read(run):
    return stage_median(run, "deliver")
