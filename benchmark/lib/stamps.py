"""The benchmark's own stamps around its calls into the program: one
record a call on the client side, one a handler invocation, keyed by
the sequence tag the request bytes carry. Appends only; read after the
window."""

from __future__ import annotations

import contextlib
import struct
import time

now_ns = time.perf_counter_ns
_TAG = struct.Struct("<Q")


def tag_of(seq: int) -> bytes:
    return _TAG.pack(seq)


def seq_of(request) -> int:
    return _TAG.unpack_from(bytes(request), 0)[0]


class Stamps:
    def __init__(self, trace: bool):
        self.calls: list = []       # (seq, issue_ns, ready_ns)
        self.handlers: list = []    # (seq, shard, start_ns, end_ns)
        self.failures: list = []    # (seq, reason)
        # a done= callback's hand-over to the benchmark's completion
        # thread: (seq, done_ns, picked_up_ns, payload ready at done)
        self.handovers: list = []
        if trace:
            # host spans on the profiler's clock, for the gap attribution
            from jax.profiler import TraceAnnotation
            self.span = TraceAnnotation
        else:
            self.span = _no_span

    def fail(self, seq: int, reason: str) -> None:
        self.failures.append((seq, reason))

    def wrap_handler(self, fn, shard: int = 0):
        """``fn(cntl, request)`` with its own time stamped: the enqueue
        the handler does, not the device work it launches."""
        handlers, span = self.handlers, self.span

        def handler(cntl, request):
            t0 = now_ns()
            with span("bench.handler"):
                out = fn(cntl, request)
            handlers.append((seq_of(request), shard, t0, now_ns()))
            return out
        return handler


@contextlib.contextmanager
def _no_span(_name):
    yield
