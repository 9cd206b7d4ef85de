"""Entry and dispatch: how long a normal request waits for a fiber
worker. The 95th percentile, over the traced window's short calls, of
the server span's ``worker_us - received_us`` (frame cut to the
request's fiber first running on a fiber worker: the device take, the
dispatch queue, parse, the hop off the event thread, the steal), from
the program's rpcz spans joined client to server as
``benchmark/lib/rpc_spans.py`` joins them. Nothing with fewer than ten
samples beyond it, under a program whose spans have no ``worker_us``,
or in a run without spans."""

from benchmark.lib.rpc_spans import program_spans
from benchmark.lib.stats import tail

SHORT_METHOD = "Echo"


def waits(spans, start_us=None, end_us=None) -> list:
    """``worker_us - received_us`` of the short calls whose client span
    started inside [start_us, end_us], without error on either side."""
    clients = {(s.trace_id, s.span_id): s for s in spans
               if s.side == "client" and s.method == SHORT_METHOD}
    out = []
    for s in spans:
        if s.side != "server" or s.method != SHORT_METHOD:
            continue
        c = clients.get((s.trace_id, s.parent_span_id))
        if c is None or c.error_code or s.error_code:
            continue
        if (start_us is not None and c.start_us < start_us) or \
                (end_us is not None and c.start_us > end_us):
            continue
        worker_us = getattr(s, "worker_us", 0)
        if worker_us and s.received_us and worker_us >= s.received_us:
            out.append(worker_us - s.received_us)
    return out


def read(run):
    t0 = run._win_start_ns // 1000
    return tail(waits(program_spans(), t0, t0 + int(run.window_s * 1e6)),
                0.95)
