"""The call's timeline from inside the program: the window's calls cut
into seven stages from the stamps of the program's own rpcz spans
(``brpc_tpu/rpc/span.py``), which record while the profile of a
``--trace 1`` run is on. A program without such spans (an older commit)
or a run without a profile gives nothing, and the readers leave their
metrics out.

Client and server of every cell share a process, so the client span
and the server span of one call (joined by ``parent_span_id``) lie on
one clock, the one the benchmark's own stamps use, and telescope:

    start_us -> b1 -> received_us -> handler_start_us -> handler_end_us
             -> b5 -> first_byte_us -> end_us

b1 = min(client write_done_us, server received_us) and b5 = min(server
flushed_us, client first_byte_us): a boundary that two threads stamp is
taken at the earlier stamp, so no stage is negative and the seven sum
exactly to ``end_us - start_us``.
"""

from __future__ import annotations

import json

from benchmark.lib.stats import median, tail

STAGES = ("issue", "request_wake", "server_queue", "handler",
          "response_write", "response_wake", "complete")
CLIENT_STAMPS = ("start_us", "write_done_us", "first_byte_us", "end_us")
SERVER_STAMPS = ("received_us", "handler_start_us", "handler_end_us",
                 "flushed_us")
MAX_DROPPED_SHARE = 0.10
MIN_CALLS = 20


def stages_of(client, server) -> tuple:
    """The seven stages of one call, in us, in the order of STAGES."""
    b1 = min(client.write_done_us, server.received_us)
    b5 = min(server.flushed_us, client.first_byte_us)
    marks = (client.start_us, b1, server.received_us,
             server.handler_start_us, server.handler_end_us, b5,
             client.first_byte_us, client.end_us)
    stages = tuple(b - a for a, b in zip(marks, marks[1:]))
    assert sum(stages) == client.end_us - client.start_us, (marks, stages)
    return stages


def join_calls(spans, method=None, start_us=None, end_us=None):
    """``(kept, dropped)``: the stage tuples of the calls whose client
    span started inside [start_us, end_us] (all, where a bound is None),
    and the count of such calls dropped for a missing server half, a
    missing stamp, an error or stamps out of order (a negative stage:
    none is known to occur). ``method`` None keeps every method. A
    server span without its client span (a call under way when the
    profile began) is no call here."""
    clients, servers = [], {}
    for s in spans:
        if method is not None and s.method != method:
            continue
        if s.side == "client":
            clients.append(s)
        elif s.side == "server":
            servers[(s.trace_id, s.parent_span_id)] = s
    client_ids = {(c.trace_id, c.span_id) for c in clients}
    kept, dropped = [], 0
    for c in clients:
        if (c.trace_id, c.parent_span_id) in client_ids:
            continue        # one attempt of a retried call, not a call
        if (start_us is not None and c.start_us < start_us) or \
                (end_us is not None and c.start_us > end_us):
            continue
        s = servers.get((c.trace_id, c.span_id))
        if s is None or c.error_code or s.error_code \
                or not all(getattr(c, k) for k in CLIENT_STAMPS) \
                or not all(getattr(s, k) for k in SERVER_STAMPS):
            dropped += 1
            continue
        stages = stages_of(c, s)
        if min(stages) < 0:
            dropped += 1
            continue
        kept.append(stages)
    return kept, dropped


def summarize(kept, dropped) -> dict:
    """Per stage the median, the mean (the means add up to the mean span
    latency) and the p99 where 10 samples lie beyond it."""
    out = {"calls": len(kept), "dropped": dropped, "stages": {}}
    for i, name in enumerate(STAGES):
        col = [k[i] for k in kept]
        out["stages"][name] = {"p50": median(col),
                               "mean": sum(col) / len(col),
                               "p99": tail(col, 0.99)}
    lat = [sum(k) for k in kept]
    out["span_latency_us"] = {"p50": median(lat),
                              "mean": sum(lat) / len(lat),
                              "p99": tail(lat, 0.99)}
    return out


def program_spans() -> list:
    """Every span the program's ring holds, or nothing where the program
    has no ring."""
    try:
        from brpc_tpu.rpc.span import global_collector
    except ImportError:         # an older program: no metric
        return []
    return global_collector.recent(1 << 30)


def table(run):
    """The window's stage columns ``{stage: [us, ...]}``, or None: no
    spans, more than MAX_DROPPED_SHARE of the calls dropped, or fewer
    than MIN_CALLS left. Computed once a run; the summary goes to an
    earlier line of stdout."""
    if hasattr(run, "_rpc_stage_table"):
        return run._rpc_stage_table
    run._rpc_stage_table = None
    t0 = run._win_start_ns // 1000
    spans = program_spans()
    kept, dropped = join_calls(spans, run.cell.traffic.get("method"), t0,
                               t0 + int(run.window_s * 1e6))
    if not kept and not dropped:
        return None
    summary = summarize(kept, dropped) if kept else \
        {"calls": 0, "dropped": dropped}
    # the benchmark's own call times over the part of the window that
    # has spans, to set beside the span latency
    first_us = min(s.start_us for s in spans
                   if s.side == "client" and s.start_us >= t0)
    beside = [(r - i) / 1e3 for _s, i, r in run.calls
              if i // 1000 >= first_us]
    if beside:
        summary["call_p50_us_same_part"] = median(beside)
    ok = len(kept) >= MIN_CALLS and \
        dropped <= MAX_DROPPED_SHARE * (len(kept) + dropped)
    summary["reported"] = ok
    print(json.dumps({"info": {"rpc_stages": summary}}), flush=True)
    if ok:
        run._rpc_stage_table = {
            name: [k[i] for k in kept] for i, name in enumerate(STAGES)}
    return run._rpc_stage_table


def stage_median(run, stage: str):
    cols = table(run)
    return median(cols[stage]) if cols else None
