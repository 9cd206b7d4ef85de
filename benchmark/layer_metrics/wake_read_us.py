"""Socket and framing: of a frame's wake, the part from the start of its
socket's callback on the event thread to the frame's cut: the ``recv``s,
the lane's pump, the frames cut (and, where the pass loops, processed)
ahead of this one. Mean over the window's wakes whose frame the loop
cut (``lib/wake_split.py``). Nothing under a program whose spans lack
the loop's stamps, or untraced."""

from benchmark.lib.wake_split import part_mean


def read(run):
    return part_mean(run, "read")
