"""A stream frame's hop from inside the program, and the window's
stream counters.

**Spans.** While the profile of a ``--trace 1`` run is on, every data
frame of a stream leaves one rpcz span a side (``brpc_tpu/rpc/span.py``,
``FrameSpan``), joined by the receiving stream's id and ``frame_seq``.
Writer and acceptor of every stream share a process here, so the two
halves lie on one clock and telescope into three stages:

    write_start_us -> b -> received_us -> deliver_start_us

b = min(sender write_done_us, receiver received_us): a boundary that
two threads stamp is taken at the earlier stamp (PR 24's rule), so no
stage is negative and the three sum exactly to the hop as the spans see
it, ``deliver_start_us - write_start_us``. A program without such spans
(an older commit) or a run without a profile gives nothing, and the
readers leave their metrics out.

**Counters.** ``RunData`` carries no delta of a service's own counters,
so the service marks the window itself (its first measured frame, and
``finish()``), and ``window_counters()`` is the difference of the
program's process-wide ``stream_*`` sums between the marks."""

from __future__ import annotations

import json

from benchmark.lib.stats import median

STAGES = ("write", "wire", "deliver")
MAX_DROPPED_SHARE = 0.10
MIN_FRAMES = 20


def stages_of(send, recv) -> tuple:
    """The three stages of one hop, in us, in the order of STAGES."""
    b = min(send.write_done_us, recv.received_us)
    marks = (send.start_us, b, recv.received_us, recv.deliver_start_us)
    stages = tuple(y - x for x, y in zip(marks, marks[1:]))
    assert sum(stages) == recv.deliver_start_us - send.start_us, marks
    return stages


def join_frames(spans, start_us=None, end_us=None):
    """``(kept, dropped)``: the stage tuples of the frames whose sending
    half started inside [start_us, end_us] (all, where a bound is None),
    and the count of such frames dropped for a missing receiving half, a
    missing stamp, an error or a negative stage. A receiving half
    without its sending half (a frame under way when the profile began)
    is no frame here."""
    sends, recvs = [], {}
    for s in spans:
        if getattr(s, "side", "") != "stream":
            continue
        if s.service == "stream-send":
            sends.append(s)
        elif s.service == "stream-recv":
            recvs[(s.stream_id, s.frame_seq)] = s
    kept, dropped = [], 0
    for s in sends:
        if (start_us is not None and s.start_us < start_us) or \
                (end_us is not None and s.start_us > end_us):
            continue
        r = recvs.get((s.stream_id, s.frame_seq))
        if r is None or s.error_code or not s.write_done_us \
                or not (r.received_us and r.deliver_start_us):
            dropped += 1
            continue
        stages = stages_of(s, r)
        if min(stages) < 0:
            dropped += 1
            continue
        kept.append(stages)
    return kept, dropped


def table(run):
    """The window's stage columns ``{stage: [us, ...]}`` over frames and
    hops, or None: no spans, more than MAX_DROPPED_SHARE of the frames
    dropped, or fewer than MIN_FRAMES left. Computed once a run; the
    summary goes to an earlier line of stdout."""
    if hasattr(run, "_stream_stage_table"):
        return run._stream_stage_table
    run._stream_stage_table = None
    from benchmark.lib.rpc_spans import program_spans
    t0 = run._win_start_ns // 1000
    kept, dropped = join_frames(program_spans(), t0,
                                t0 + int(run.window_s * 1e6))
    if not kept and not dropped:
        return None
    ok = len(kept) >= MIN_FRAMES and \
        dropped <= MAX_DROPPED_SHARE * (len(kept) + dropped)
    cols = {name: [k[i] for k in kept] for i, name in enumerate(STAGES)}
    summary = {"frames": len(kept), "dropped": dropped, "reported": ok}
    if kept:
        summary["stages_p50"] = {n: median(c) for n, c in cols.items()}
        summary["hop_p50_us"] = median([sum(k) for k in kept])
    print(json.dumps({"info": {"stream_stages": summary}}), flush=True)
    if ok:
        run._stream_stage_table = cols
    return run._stream_stage_table


def stage_median(run, stage: str):
    """The stage's median, or None (also where it reads 0: the result
    line takes no zero)."""
    cols = table(run)
    return (median(cols[stage]) or None) if cols else None


# ------------------------------------------------------------ counters
_marks: dict = {}


def _snapshot():
    try:
        from brpc_tpu.rpc.stream import counters_snapshot
    except ImportError:         # an older program: no metric
        return None
    return counters_snapshot()


def mark_window_start() -> None:
    _marks["start"] = _snapshot()
    _marks.pop("end", None)


def mark_window_end() -> None:
    _marks["end"] = _snapshot()


def window_counters():
    """The program's stream counts between the two marks, or None."""
    start, end = _marks.get("start"), _marks.get("end")
    if not start or not end:
        return None
    return {k: end[k] - start[k] for k in end}
