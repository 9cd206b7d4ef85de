"""Socket and framing: the one event thread's utilisation, in %: of the
wall time of its loop (``dispatcher_loop_us``: asleep in ``select`` +
awake) the part it was awake (``dispatcher_awake_us``: every tick from
its first reading to its last, and the duties that ran). Both sums move only while spans record (the
traced part of the window), so the ratio is of like with like; wall,
not CPU: awake less ``cpu_us_per_call_dispatcher``'s CPU is the loop
waiting inside a tick for the interpreter or a syscall
(``lib/wake_split.py``). Nothing under a program without the sums, or
untraced."""

from benchmark.lib.wake_split import share


def read(run):
    return share(run, "dispatcher_awake_us", "dispatcher_loop_us")
