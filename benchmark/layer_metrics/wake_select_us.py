"""Socket and framing: of a frame's wake, the part from the later of
"the bytes were written" and "the event loop went to ``select``" to the
tick's first reading (``wake_tick_us``): the kernel's wake of the
loop's thread, its wait for the interpreter, the event batch's resolve.
Mean over the window's wakes whose frame the loop cut
(``lib/wake_split.py``). Nothing under a program whose spans lack the
loop's stamps, or untraced."""

from benchmark.lib.wake_split import part_mean


def read(run):
    return part_mean(run, "select")
