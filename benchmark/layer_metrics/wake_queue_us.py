"""Socket and framing: of a frame's wake, the part from the tick's
first reading to the start of this socket's callback
(``wake_callback_us``): other sockets' callbacks ahead of it in the same
tick of the one event thread. Mean over the window's wakes whose frame
the loop cut (``lib/wake_split.py``). Nothing under a program whose
spans lack the loop's stamps, or untraced."""

from benchmark.lib.wake_split import part_mean


def read(run):
    return part_mean(run, "queue")
