"""Streaming: b = min(sender ``write_done_us``, receiver ``received_us``)
to the receiver's ``received_us`` (the frame cut): loopback, the
dispatcher's wake, read, cut; 0 where the receiver had the frame before
the writer stamped. Median over the frames and hops of the window that
have both spans (``lib/stream_frames.py``)."""

from benchmark.lib.stream_frames import stage_median


def read(run):
    return stage_median(run, "wire")
