"""Streaming: bare control frames (credit grants; a close frame would
count too, none is sent in a window) per data frame over the window,
from the program's ``stream_ctrl_frames_out`` and
``stream_data_frames_out`` (``lib/stream_frames.py`` takes the delta).
Grants ride on no data frame today, so every one is a frame of its own
on the reverse direction."""

from benchmark.lib.stream_frames import window_counters


def read(run):
    c = window_counters()
    if not c or not c["data_frames_out"] or not c["ctrl_frames_out"]:
        return None
    return c["ctrl_frames_out"] / c["data_frames_out"]
