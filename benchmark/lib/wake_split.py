"""The event thread's tick from inside: a frame's wake cut into four
parts, and what the loop's awake time is made of.

**The wake, split.** A wake runs from "the writer's flush returned" to
"the frame was cut", and the accepted stage metrics time it whole: a
call's request wake (b1 -> server ``received_us``) and response wake
(b5 -> client ``first_byte_us``) as ``lib/rpc_spans.py`` takes b1 and
b5, a stream frame's wire (b -> receiver ``received_us``) as
``lib/stream_frames.py`` takes b. While spans record, the span that
holds the cut also holds the event loop's three stamps of the tick that
made it (``brpc_tpu/transport/event_dispatcher.py``: ``wake_sleep_us``
the loop last went to ``select``, ``wake_tick_us`` it woke with
something to fire, ``wake_callback_us`` this socket's callback began).
With m0 the wake's start, m4 the cut and the three clamped into
[m0, m4] and made monotone, four parts sum EXACTLY to the wake:

    loop_busy  m0 -> sleep     written while the loop was still at work
                               in an earlier tick; 0 where it slept
    select     -> tick         the kernel's wake, the wait for the
                               interpreter, the batch's resolve
    queue      -> callback     other sockets' callbacks ahead in the tick
    read       -> m4           this callback up to the cut: recvs, the
                               lane's pump, frames cut ahead of this one

A frame cut off the loop (a plucking joiner's own reply, a fiber's
pass) has no tick: its stamps are 0, it is counted and left out. The
pairs come from the two accepted joins, asked about one candidate pair
at a time so that the spans stay known; nothing here joins by itself.
A part's metric is its MEAN over the window's wakes (the four means add
up to the mean wake; a part that is 0 in most wakes has a median of 0,
and the result line takes no zero); medians go to the info line.

**The loop's awake time.** Five sums the loop keeps while spans record
(``syscall_stats.snapshot()``): ``dispatcher_loop_us`` asleep + awake,
``dispatcher_awake_us``, and of awake ``dispatcher_read_us``,
``dispatcher_cut_us``, ``dispatcher_process_us``. ``share()`` is one
over another, in %, of the window's deltas.

A program without the stamps or sums (an older commit) or a run without
a profile gives nothing, and the readers leave their metrics out.
"""

from __future__ import annotations

import json

from benchmark.lib import rpc_spans, stream_frames
from benchmark.lib.stats import median

PARTS = ("loop_busy", "select", "queue", "read")
STAMPS = ("wake_sleep_us", "wake_tick_us", "wake_callback_us")
MIN_WAKES = rpc_spans.MIN_CALLS
MAX_DROPPED_SHARE = rpc_spans.MAX_DROPPED_SHARE


def parts_of(m0: int, sleep_us: int, tick_us: int, callback_us: int,
             m4: int) -> tuple:
    """The four parts of one wake, in us, in the order of PARTS."""
    marks = [m0]
    for stamp in (sleep_us, tick_us, callback_us):
        marks.append(min(max(stamp, marks[-1]), m4))
    marks.append(m4)
    parts = tuple(b - a for a, b in zip(marks, marks[1:]))
    assert sum(parts) == m4 - m0 and min(parts) >= 0, (marks, parts)
    return parts


def _one_at_a_time(spans, key_of, join):
    """``(pairs, dropped)``: what ``join`` keeps of ``spans``, asked one
    candidate pair at a time (the spans that share ``key_of``), so that
    each kept stage tuple comes with its spans."""
    buckets: dict = {}
    for s in spans:
        key = key_of(s)
        if key is not None:
            buckets.setdefault(key, []).append(s)
    pairs, dropped = [], 0
    for bucket in buckets.values():
        kept, lost = join(bucket)
        dropped += lost
        if kept:
            pairs.append((kept[0], bucket))
    return pairs, dropped


def _wake(holder, stage_us: int, cut_us: int):
    """``(m0, sleep, tick, callback, m4)`` of the wake that ended in
    ``holder``'s cut, or None under a program without the stamps."""
    stamps = [getattr(holder, k, None) for k in STAMPS]
    if None in stamps:
        return None
    return (cut_us - stage_us, *stamps, cut_us)


def wakes_of_calls(spans, method=None, start_us=None, end_us=None):
    """``(wakes, dropped)``: two wakes a call that
    ``rpc_spans.join_calls`` keeps (the request's from the server span,
    the response's from the client span)."""
    clients = {(s.trace_id, s.span_id) for s in spans if s.side == "client"}

    def key_of(s):
        if s.side == "client":
            # an attempt of a retried call is no call (join_calls' rule)
            return None if (s.trace_id, s.parent_span_id) in clients \
                else (s.trace_id, s.span_id)
        return (s.trace_id, s.parent_span_id) if s.side == "server" else None

    pairs, dropped = _one_at_a_time(
        spans, key_of,
        lambda b: rpc_spans.join_calls(b, method, start_us, end_us))
    wakes = []
    for stages, bucket in pairs:
        client = next(s for s in bucket if s.side == "client")
        server = next(s for s in bucket if s.side == "server")
        wakes.append(_wake(server, stages[1], server.received_us))
        wakes.append(_wake(client, stages[5], client.first_byte_us))
    return wakes, dropped


def wakes_of_frames(spans, start_us=None, end_us=None):
    """``(wakes, dropped)``: one wake (the wire) a hop that
    ``stream_frames.join_frames`` keeps, from the receiving half."""
    def key_of(s):
        return (s.stream_id, s.frame_seq) \
            if getattr(s, "side", "") == "stream" else None

    pairs, dropped = _one_at_a_time(
        spans, key_of,
        lambda b: stream_frames.join_frames(b, start_us, end_us))
    wakes = []
    for stages, bucket in pairs:
        recv = next(s for s in bucket if s.service == "stream-recv")
        wakes.append(_wake(recv, stages[1], recv.received_us))
    return wakes, dropped


def split(wakes):
    """``(columns, off_loop)``: the parts of the wakes cut on the loop
    as ``{part: [us, ...]}``, and the count of those cut off it; None
    where a wake has no stamps at all (an older program)."""
    if None in wakes:
        return None
    cols = {p: [] for p in PARTS}
    off_loop = 0
    for m0, sleep_us, tick_us, callback_us, m4 in wakes:
        if not callback_us:
            off_loop += 1
            continue
        for p, v in zip(PARTS, parts_of(m0, sleep_us, tick_us, callback_us,
                                        m4)):
            cols[p].append(v)
    return cols, off_loop


def table(run):
    """The window's part columns, or None: no spans, no stamps, more
    than MAX_DROPPED_SHARE of the pairs dropped at the joins, or fewer
    than MIN_WAKES cut on the loop. Computed once a run; the summary
    goes to an earlier line of stdout."""
    if hasattr(run, "_wake_split_table"):
        return run._wake_split_table
    run._wake_split_table = None
    spans = rpc_spans.program_spans()
    t0 = run._win_start_ns // 1000
    t1 = t0 + int(run.window_s * 1e6)
    calls, lost_calls = wakes_of_calls(
        spans, run.cell.traffic.get("method"), t0, t1)
    frames, lost_frames = wakes_of_frames(spans, t0, t1)
    wakes, dropped = calls + frames, lost_calls + lost_frames
    found = split(wakes) if wakes else None
    if found is None:
        return None
    cols, off_loop = found
    kept = len(calls) // 2 + len(frames)
    n = len(wakes) - off_loop
    ok = n >= MIN_WAKES and dropped <= MAX_DROPPED_SHARE * (kept + dropped)
    summary = {"wakes": n, "off_loop_share": off_loop / len(wakes),
               "dropped": dropped, "reported": ok}
    if n:
        summary["mean_us"] = {p: sum(c) / n for p, c in cols.items()}
        summary["p50_us"] = {p: median(c) for p, c in cols.items()}
        summary["nonzero_share"] = {
            p: sum(1 for v in c if v) / n for p, c in cols.items()}
        summary["wake_mean_us"] = sum(summary["mean_us"].values())
    print(json.dumps({"info": {"wake_split": summary}}), flush=True)
    if ok:
        run._wake_split_table = cols
    return run._wake_split_table


def part_mean(run, part: str):
    """The part's mean over the window's wakes, or None (also where it
    reads 0: the result line takes no zero)."""
    cols = table(run)
    return (sum(cols[part]) / len(cols[part]) or None) if cols else None


def share(run, part: str, whole: str):
    """100 x one of the loop's sums over another, from the window's
    deltas; None under a program without them or where ``whole`` did not
    move (no span recorded in the window)."""
    s = run.counters["syscalls"]
    if not s.get(whole) or part not in s:
        return None
    return 100.0 * s[part] / s[whole]
