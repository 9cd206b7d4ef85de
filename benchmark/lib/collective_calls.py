"""A lowered call from inside the program, and the window's lowering
counters.

**Spans.** While the profile of a ``--trace 1`` run is on, every
``ParallelChannel`` call that is lowered to one collective leaves ONE
client span (``brpc_tpu/rpc/combo_channels.py``; no server span, no sub
call), annotated "collective lowered", with four stamps on one clock:

    start_us -> write_done_us -> dispatch_us -> first_byte_us

entry to the lowering, the scatter handed off, the program dispatched
(``jit`` returned to the caller) and the result ready on the reply
device (stamped by the program's one waiter thread). ``start_us ->
dispatch_us`` is the host's whole cost of a lowered call. A program
without such spans (an older commit) or a run without a profile gives
nothing, and the readers leave their metrics out.

**Counters.** ``RunData`` carries no delta of the program's own
counters, so the service marks the window itself (its first measured
call, and ``finish()``), and ``window_counters()`` is the difference of
the program's process-wide ``parallel_collective_*`` sums between the
marks."""

from __future__ import annotations

import json

from benchmark.lib.stats import median

STAGES = ("scatter", "dispatch", "ready")
ANNOTATION = "collective lowered"
MAX_DROPPED_SHARE = 0.10
MIN_CALLS = 20


def lowered_calls(spans, start_us=None, end_us=None):
    """``(kept, dropped)``: the stage tuples (in the order of STAGES) of
    the lowered calls whose span started inside [start_us, end_us] (all,
    where a bound is None), and the count of such calls dropped for an
    error, a missing stamp or stamps out of order."""
    kept, dropped = [], 0
    for s in spans:
        if getattr(s, "side", "") != "client" or not any(
                text.startswith(ANNOTATION) for _us, text in s.annotations):
            continue
        if (start_us is not None and s.start_us < start_us) or \
                (end_us is not None and s.start_us > end_us):
            continue
        marks = (s.start_us, s.write_done_us, s.dispatch_us,
                 s.first_byte_us)
        stages = tuple(b - a for a, b in zip(marks, marks[1:]))
        if s.error_code or not all(marks) or min(stages) < 0:
            dropped += 1
            continue
        kept.append(stages)
    return kept, dropped


def table(run):
    """The window's stage columns ``{stage: [us, ...]}`` and ``issue``
    (scatter + dispatch), or None: no spans, more than MAX_DROPPED_SHARE
    of the calls dropped, or fewer than MIN_CALLS left. Computed once a
    run; the summary goes to an earlier line of stdout."""
    if hasattr(run, "_collective_stage_table"):
        return run._collective_stage_table
    run._collective_stage_table = None
    from benchmark.lib.rpc_spans import program_spans
    t0 = run._win_start_ns // 1000
    kept, dropped = lowered_calls(program_spans(), t0,
                                  t0 + int(run.window_s * 1e6))
    if not kept and not dropped:
        return None
    ok = len(kept) >= MIN_CALLS and \
        dropped <= MAX_DROPPED_SHARE * (len(kept) + dropped)
    cols = {name: [k[i] for k in kept] for i, name in enumerate(STAGES)}
    cols["issue"] = [k[0] + k[1] for k in kept]
    summary = {"calls": len(kept), "dropped": dropped, "reported": ok}
    if kept:
        summary["stages_p50"] = {n: median(c) for n, c in cols.items()}
        summary["span_p50_us"] = median([sum(k) for k in kept])
    print(json.dumps({"info": {"collective_stages": summary}}), flush=True)
    if ok:
        run._collective_stage_table = cols
    return run._collective_stage_table


def stage_median(run, stage: str):
    """The stage's median, or None (also where it reads 0: the result
    line takes no zero)."""
    cols = table(run)
    return (median(cols[stage]) or None) if cols else None


# ------------------------------------------------------------ counters
_marks: dict = {}


def _snapshot():
    try:
        from brpc_tpu.rpc.combo_channels import collective_counters
    except ImportError:         # an older program: no metric
        return None
    return collective_counters()


def mark_window_start() -> None:
    _marks["start"] = _snapshot()
    _marks.pop("end", None)


def mark_window_end() -> None:
    _marks["end"] = _snapshot()


def window_counters():
    """The program's lowering counts between the two marks, or None."""
    start, end = _marks.get("start"), _marks.get("end")
    if not start or not end:
        return None
    return {k: end[k] - start[k] for k in end}
