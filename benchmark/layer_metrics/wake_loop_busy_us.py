"""Socket and framing: of a frame's wake (its flush returned -> it was
cut), the part in which the event loop was still at work in an earlier
tick: from the wake's start to the loop's ``wake_sleep_us`` (it went to
``select``); 0 where the loop slept when the bytes were written. Mean
over the window's wakes whose frame the loop cut (a call's request and
response wakes, a stream frame's wire: ``lib/wake_split.py``). Nothing
under a program whose spans lack the loop's stamps, or untraced."""

from benchmark.lib.wake_split import part_mean


def read(run):
    return part_mean(run, "loop_busy")
