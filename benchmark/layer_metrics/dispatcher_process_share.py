"""Socket and framing: of the event thread's awake time
(``dispatcher_awake_us``), the share its input passes spent processing
the messages they cut, in %: from where a pass turns to ``process`` to
its callback's end, or to where it cuts or reads on (a request up to its
hop to a fiber worker; a response through ``_fill_response``, the
completion hooks and a ``done=`` callback with whatever it issues). Sums of
``syscall_stats.snapshot()`` that move only while spans record
(``lib/wake_split.py``). Nothing under a program without them, or
untraced."""

from benchmark.lib.wake_split import share


def read(run):
    return share(run, "dispatcher_process_us", "dispatcher_awake_us")
