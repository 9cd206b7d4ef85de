"""Socket and framing: of the event thread's awake time
(``dispatcher_awake_us``), the share its input passes spent cutting
frames, in %: the protocol's ``parse``, ``turbo_scan`` or
``batch_parse`` (the cut, the meta decode, ``take_device_payload``).
Sums of ``syscall_stats.snapshot()`` that move only while spans record
(``lib/wake_split.py``). Nothing under a program without them, or
untraced."""

from benchmark.lib.wake_split import share


def read(run):
    return share(run, "dispatcher_cut_us", "dispatcher_awake_us")
