"""Socket and framing: of the event thread's awake time
(``dispatcher_awake_us``), the share its input passes spent reading, in
%: a read callback's start to the end of ``Socket._drain_readable`` (the
``recv``s; for ``ici://`` the
lane's pump, its frame decode, ACK handling and the flush an opened
window triggers). Sums of ``syscall_stats.snapshot()`` that move only
while spans record (``lib/wake_split.py``). Nothing under a program
without them, or untraced."""

from benchmark.lib.wake_split import share


def read(run):
    return share(run, "dispatcher_read_us", "dispatcher_awake_us")
