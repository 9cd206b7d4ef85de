"""``benchmark/lib/wake_split.py`` and its eight readers (ISSUE 35) on
hand-made spans and counters: the four parts telescope exactly to the
wake the accepted stage times whole, stamps are clamped, a frame cut off
the loop is left out, and nothing is reported under 20 wakes, with too
many pairs dropped at the joins, or under a program without the stamps
or sums. The eight entries are held by NAME."""

import json
from types import SimpleNamespace

import pytest

import bench_testlib

from benchmark.layer_metrics import (dispatcher_awake_share,
                                     dispatcher_cut_share,
                                     dispatcher_process_share,
                                     dispatcher_read_share,
                                     wake_loop_busy_us, wake_queue_us,
                                     wake_read_us, wake_select_us)
from benchmark.lib import rpc_spans, stream_frames, wake_split

T0 = 1_000_000          # the window's start, us
WAKE_CELLS = ["tpu_performance.echo_small_d1",
              "tpu_performance.echo_small_d50",
              "parallel_allreduce.fanout_4mb_d1",
              "streaming_echo.ring_2mb_w8",
              "longtail_echo.poisson_1pct_8conn"]
LOOP_CELLS = ["tpu_performance.step_2mb_d8",
              "parallel_allreduce.fanout_4mb_d1",
              "streaming_echo.ring_2mb_w8"]
WAKE = [("wake_loop_busy_us", wake_loop_busy_us, "loop_busy"),
        ("wake_select_us", wake_select_us, "select"),
        ("wake_queue_us", wake_queue_us, "queue"),
        ("wake_read_us", wake_read_us, "read")]
# a part is listed where it is not 0 by the cell's build: one sync
# caller writes while the loop sleeps (no busy part to speak of), and a
# tick with one callback has no queue (the callback begins with the
# tick): the result line takes no zero, the info line has them all
CELLS_OF = {"wake_loop_busy_us": WAKE_CELLS[1:],
            "wake_queue_us": WAKE_CELLS[2:]}
LOOP = [("dispatcher_awake_share", dispatcher_awake_share),
        ("dispatcher_read_share", dispatcher_read_share),
        ("dispatcher_cut_share", dispatcher_cut_share),
        ("dispatcher_process_share", dispatcher_process_share)]
THE_EIGHT = [
    {"name": name, "unit": "us", "better": "lower",
     "source": "program_span", "layer": "socket and framing",
     "moves": "call_p50_us", "workloads": CELLS_OF.get(name, WAKE_CELLS)}
    for name, _, _ in WAKE] + [
    {"name": name, "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "socket and framing",
     "moves": "call_p99_us", "workloads": LOOP_CELLS}
    for name, _ in LOOP]


# ---------------------------------------------------------- one wake
@pytest.mark.parametrize("stamps, want", [
    # the loop slept before the bytes were written: no busy part
    ((50, 130, 140), (0, 30, 10, 60)),
    # written while the loop was at work in an earlier tick
    ((120, 130, 140), (20, 10, 10, 60)),
    # written inside the tick that read it, other callbacks ahead
    ((50, 90, 150), (0, 0, 50, 50)),
    # arrived during its own socket's callback: all of it is the read
    ((50, 60, 70), (0, 0, 0, 100)),
    # a stamp past the cut is clamped to it (two threads, one clock)
    ((120, 130, 260), (20, 10, 70, 0)),
    ((300, 310, 320), (100, 0, 0, 0)),
    # stamps out of order are made monotone, never negative
    ((150, 120, 180), (50, 0, 30, 20)),
], ids=["asleep", "busy", "same_tick", "own_callback", "past_the_cut",
        "all_past", "disordered"])
def test_four_parts_sum_exactly_to_the_wake(stamps, want):
    got = wake_split.parts_of(100, *stamps, 200)
    assert got == want
    assert sum(got) == 100 and min(got) >= 0


# ------------------------------------------------------------- calls
def _call(i, wake=True, on_loop=(True, True), **over):
    """Client and server span of call ``i`` as ``rpc_spans`` wants them,
    10 us a stage except the two wakes: 100 us each, the loop asleep 20
    us before the bytes were written, its tick 30 us after, the callback
    10 us later (parts 0, 30, 10, 60)."""
    t = T0 + 1000 * i
    st = dict(start_us=t, write_done_us=t + 10, received_us=t + 110,
              handler_start_us=t + 120, handler_end_us=t + 130,
              flushed_us=t + 140, first_byte_us=t + 240, end_us=t + 250)
    st.update(over)

    def stamps(m0, loop):
        if not wake:
            return {}
        if not loop:
            return dict.fromkeys(wake_split.STAMPS, 0)
        return dict(zip(wake_split.STAMPS, (m0 - 20, m0 + 30, m0 + 40)))
    c = SimpleNamespace(side="client", method="Echo", trace_id=100 + i,
                        span_id=2 * i + 1, parent_span_id=0, error_code=0,
                        **{k: st[k] for k in rpc_spans.CLIENT_STAMPS},
                        **stamps(t + 140, on_loop[1]))
    s = SimpleNamespace(side="server", method="Echo", trace_id=100 + i,
                        span_id=2 * i + 2, parent_span_id=c.span_id,
                        error_code=0,
                        **{k: st[k] for k in rpc_spans.SERVER_STAMPS},
                        **stamps(t + 10, on_loop[0]))
    return [c, s]


def _frame(i, wake=True):
    """A hop's two halves as ``stream_frames`` wants them: wire 200 us,
    written 50 us before the loop slept (parts 50, 80, 30, 40)."""
    t = T0 + 1000 * i
    stamps = dict(zip(wake_split.STAMPS, (t + 100, t + 180, t + 210))) \
        if wake else {}
    return [
        SimpleNamespace(side="stream", service="stream-send", stream_id=7,
                        method="frame", frame_seq=i, start_us=t,
                        write_done_us=t + 50, error_code=0),
        SimpleNamespace(side="stream", service="stream-recv", stream_id=7,
                        method="frame", frame_seq=i, received_us=t + 250,
                        deliver_start_us=t + 300, **stamps)]


def _run(syscalls=None, method="Echo"):
    return SimpleNamespace(
        _win_start_ns=T0 * 1000, window_s=1.0, calls=[],
        counters={"syscalls": syscalls or {}},
        cell=SimpleNamespace(traffic={"method": method} if method else {}))


def _table(monkeypatch, spans, **kw):
    monkeypatch.setattr(rpc_spans, "program_spans", lambda: spans)
    return wake_split.table(_run(**kw))


def test_a_call_gives_two_wakes_that_telescope_to_its_stages():
    spans = _call(0) + _call(1, write_done_us=T0 + 1000 + 150)
    wakes, dropped = wake_split.wakes_of_calls(spans, "Echo", T0, T0 + 10**6)
    assert dropped == 0 and len(wakes) == 4
    kept, _ = rpc_spans.join_calls(spans, "Echo", T0, T0 + 10**6)
    stages = [k[i] for k in kept for i in (1, 5)]
    assert stages == [100, 100, 0, 100]     # the accepted stages, whole
    # each wake runs from its stage's start to its cut
    assert [w[4] - w[0] for w in wakes] == stages
    cols, off_loop = wake_split.split(wakes)
    assert off_loop == 0
    per_wake = list(zip(*(cols[p] for p in wake_split.PARTS)))
    assert per_wake[0] == per_wake[1] == per_wake[3] == (0, 30, 10, 60)
    assert per_wake[2] == (0, 0, 0, 0)      # b1 was the cut itself
    assert [sum(p) for p in per_wake] == stages


def test_a_hop_gives_one_wake_that_telescopes_to_its_wire():
    spans = _frame(0) + _frame(1)
    wakes, dropped = wake_split.wakes_of_frames(spans, T0, T0 + 10**6)
    kept, _ = stream_frames.join_frames(spans, T0, T0 + 10**6)
    assert dropped == 0 and [w[4] - w[0] for w in wakes] == \
        [k[1] for k in kept] == [200, 200]
    cols, off_loop = wake_split.split(wakes)
    assert off_loop == 0 and {p: c[0] for p, c in cols.items()} == {
        "loop_busy": 50, "select": 80, "queue": 30, "read": 40}


def test_the_joins_rules_are_the_accepted_ones():
    """What ``join_calls`` and ``join_frames`` keep and drop of a mixed
    lot is what reaches the split: nothing here joins by itself."""
    failed = _call(21)
    failed[0].error_code = 1008
    attempt = SimpleNamespace(**vars(_call(0)[0]))
    attempt.span_id, attempt.parent_span_id = 999, 1    # child of call 0
    other = _call(22)
    for s in other:
        s.method = "Hold"
    spans = (_call(0) + _call(1) + _call(20)[:1]        # no server half
             + _call(23)[1:]                            # began untraced
             + failed + [attempt] + other
             + _call(24, handler_start_us=0)            # a stamp missing
             + _frame(0) + _frame(3)[:1]                # no receiving half
             + _frame(4)[1:])
    kept, dropped = rpc_spans.join_calls(spans, "Echo", T0, T0 + 10**6)
    wakes, lost = wake_split.wakes_of_calls(spans, "Echo", T0, T0 + 10**6)
    assert (len(wakes), lost) == (2 * len(kept), dropped) == (4, 3)
    kept, dropped = stream_frames.join_frames(spans, T0, T0 + 10**6)
    wakes, lost = wake_split.wakes_of_frames(spans, T0, T0 + 10**6)
    assert (len(wakes), lost) == (len(kept), dropped) == (1, 1)
    # outside the window: no call, and none dropped
    assert wake_split.wakes_of_calls(spans, "Echo", T0 + 10**7, None) \
        == ([], 0)


def test_a_frame_cut_off_the_loop_is_counted_and_left_out(monkeypatch,
                                                          capsys):
    """echo_small_d1's shape: every reply plucked by its joiner."""
    spans = [s for i in range(25) for s in _call(i, on_loop=(True, False))]
    cols = _table(monkeypatch, spans)
    assert {p: len(c) for p, c in cols.items()} == dict.fromkeys(
        wake_split.PARTS, 25)
    info = json.loads(capsys.readouterr().out)["info"]["wake_split"]
    assert info["wakes"] == 25 and info["off_loop_share"] == 0.5
    assert info["reported"] and info["dropped"] == 0
    assert info["mean_us"] == {"loop_busy": 0, "select": 30, "queue": 10,
                               "read": 60}
    assert info["wake_mean_us"] == 100
    assert info["nonzero_share"]["loop_busy"] == 0


@pytest.mark.parametrize("spans, why", [
    ([], "no span at all"),
    ([s for i in range(9) for s in _call(i)], "18 wakes"),
    ([s for i in range(30) for s in _call(i, on_loop=(False, False))],
     "all cut off the loop"),
    ([s for i in range(30) for s in _call(i, wake=False)],
     "an older program: spans without the stamps"),
    ([s for i in range(30) for s in _frame(i, wake=False)],
     "an older program's frames"),
    ([s for i in range(30) for s in _call(i)]
     + [_call(i)[0] for i in range(40, 45)], "5 of 35 calls dropped"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else "")
def test_nothing_is_reported(monkeypatch, spans, why):
    assert _table(monkeypatch, spans) is None, why
    run = _run()
    for _, reader, _ in WAKE:
        assert reader.read(run) is None


@pytest.mark.parametrize("name, reader, part", WAKE,
                         ids=[w[0] for w in WAKE])
def test_wake_reader_is_the_parts_mean(monkeypatch, name, reader, part):
    spans = [s for i in range(15) for s in _call(i)] \
        + [s for i in range(20, 30) for s in _frame(i)]
    monkeypatch.setattr(rpc_spans, "program_spans", lambda: spans)
    run = _run()
    calls, frames = (0, 30, 10, 60), (50, 80, 30, 40)
    i = wake_split.PARTS.index(part)
    assert reader.read(run) == pytest.approx(
        (30 * calls[i] + 10 * frames[i]) / 40)
    # computed once a run
    monkeypatch.setattr(rpc_spans, "program_spans", lambda: 1 / 0)
    assert reader.read(run) is not None


def test_a_part_that_reads_zero_is_left_out(monkeypatch):
    spans = [s for i in range(15) for s in _call(i)]
    monkeypatch.setattr(rpc_spans, "program_spans", lambda: spans)
    run = _run()
    assert wake_loop_busy_us.read(run) is None      # the loop slept
    assert wake_select_us.read(run) == 30


# -------------------------------------------------------------- the loop
SUMS = {"dispatcher_loop_us": 2_000_000, "dispatcher_awake_us": 1_200_000,
        "dispatcher_read_us": 300_000, "dispatcher_cut_us": 60_000,
        "dispatcher_process_us": 600_000}


@pytest.mark.parametrize("name, reader, want", [
    (n, r, w) for (n, r), w in zip(LOOP, (60.0, 25.0, 5.0, 50.0))],
    ids=[n for n, _ in LOOP])
def test_share_reader(name, reader, want):
    assert reader.read(_run(dict(SUMS, recv=9))) == pytest.approx(want)
    # the parent: no such counter; an untraced window: no sum moved
    assert reader.read(_run({"recv": 9, "dispatcher_ticks": 3})) is None
    assert reader.read(_run(dict.fromkeys(SUMS, 0))) is None


def test_the_three_shares_stay_inside_awake():
    run = _run(SUMS)
    assert sum(r.read(run) for _, r in LOOP[1:]) <= 100


def test_the_program_carries_the_stamps_and_the_sums():
    from brpc_tpu.rpc.span import FrameSpan, Span
    from brpc_tpu.transport import syscall_stats
    assert set(SUMS) <= set(syscall_stats.snapshot())
    for span in (Span(trace_id=1, span_id=2),
                 FrameSpan(trace_id=1, span_id=2)):
        assert set(wake_split.STAMPS) <= set(span.to_dict())


# ----------------------------------------------------------- the entries
@pytest.mark.parametrize("entry", THE_EIGHT,
                         ids=[e["name"] for e in THE_EIGHT])
def test_entry_is_in_the_benchmark_by_name(entry):
    """Found by name, never by position (PERF.md section 7 (h)): a later
    PR appends after them and edits no test."""
    bench = bench_testlib.bench()
    found = [m for m in bench["per_layer"] if m["name"] == entry["name"]]
    assert found == [entry]
    e2e = {e["name"]: e for e in bench["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= set(e2e["workloads"])
    assert bench_testlib.read_bytes(
        f"{bench_testlib.ROOT}/benchmark/layer_metrics/{entry['name']}.py")


def test_the_eight_stand_together_after_what_was_there():
    per_layer = bench_testlib.bench()["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index(THE_EIGHT[0]["name"])
    assert per_layer[first:first + 8] == THE_EIGHT
    assert names[first - 1] == "worker_held_share"      # the parent's last
    layers = {m["layer"] for m in per_layer[:first]}
    assert THE_EIGHT[0]["layer"] in layers              # a layer that was
