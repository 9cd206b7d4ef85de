"""Socket and framing: a request's way between the two processes, from
the call's two rpcz spans: the client span of the CHILD process and the
server span of this one, joined by the ids that travel in ``RpcMeta``
(the server span's ``parent_span_id`` is the client span's ``span_id``
within one ``trace_id``), both on CLOCK_MONOTONIC of one host. Median
over the joined pairs of client ``write_done_us`` (the batch and its
envelope handed to the conn and flushed) -> server ``received_us`` (the
request's frame cut): TCP taking the rest of 2 MB, loopback, the
server's event thread reading and de-enveloping them. Nothing without
both halves, with fewer than ``MIN_PAIRS`` pairs, or where the two
clocks disagreed in set-up."""

import sys

from benchmark.lib.stats import median

MIN_PAIRS = 20
SERVER_STAMPS = ("received_us", "flushed_us")
CLIENT_STAMPS = ("start_us", "write_done_us", "first_byte_us", "end_us")


def pairs(client_spans, server_spans) -> list:
    """[(client dict, server dict)] of the calls that have both halves,
    every stamp and no error. ``server_spans`` are the program's Span
    objects or their ``to_dict``; ids compare as ``to_dict`` writes
    them."""
    servers = {}
    for s in server_spans:
        d = s if isinstance(s, dict) else s.to_dict()
        if d.get("side", "server") == "server":
            servers[(d["trace_id"], d["parent_span_id"])] = d
    out = []
    for c in client_spans:
        s = servers.get((c["trace_id"], c["span_id"]))
        if s is None or c["error_code"] or s["error_code"] \
                or not all(c[k] for k in CLIENT_STAMPS) \
                or not all(s[k] for k in SERVER_STAMPS):
            continue
        out.append((c, s))
    return out


def window_pairs() -> list:
    """The measured window's joined pairs; [] without a client process
    that reported spans or with clocks that disagree."""
    last = getattr(sys.modules.get("benchmark.services.remote_caller"),
                   "LAST", {})
    spans = (last.get("report") or {}).get("spans")
    if not spans or not last.get("clocks_agree"):
        return []
    try:
        from brpc_tpu.rpc.span import global_collector
    except ImportError:
        return []
    method = spans[0]["method"]
    mine = [s for s in global_collector.recent(1 << 30)
            if s.side == "server" and s.method == method]
    return pairs(spans, mine)


def read(run):
    joined = window_pairs()
    if len(joined) < MIN_PAIRS:
        return None
    value = median([s["received_us"] - c["write_done_us"]
                    for c, s in joined])
    return value if value > 0 else None
