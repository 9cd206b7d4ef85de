"""``benchmark/lib/rpc_spans.py`` on hand-made spans: the join, the two
boundaries that two threads stamp, and the rule for reporting nothing."""

import json
from types import SimpleNamespace

import pytest

import bench_testlib  # noqa: F401 - puts the checkout on sys.path

from benchmark.lib import rpc_spans

T0 = 1_000_000          # the window's start, us


def _call(i, method="Echo", start=None, **over):
    """Client and server span of call ``i``: 10 us a stage, by default
    each boundary's own-thread stamp first (write_done before received,
    flushed before first_byte)."""
    t = T0 + 1000 * i if start is None else start
    stamps = dict(start_us=t, write_done_us=t + 10, received_us=t + 20,
                  handler_start_us=t + 30, handler_end_us=t + 40,
                  flushed_us=t + 50, first_byte_us=t + 60, end_us=t + 70)
    stamps.update(over)
    c = SimpleNamespace(side="client", method=method, trace_id=100 + i,
                        span_id=2 * i + 1, parent_span_id=0, error_code=0,
                        **{k: stamps[k] for k in rpc_spans.CLIENT_STAMPS})
    s = SimpleNamespace(side="server", method=method, trace_id=100 + i,
                        span_id=2 * i + 2, parent_span_id=c.span_id,
                        error_code=0,
                        **{k: stamps[k] for k in rpc_spans.SERVER_STAMPS})
    return c, s


def _run(window_s=1.0, method="Echo"):
    return SimpleNamespace(
        _win_start_ns=T0 * 1000, window_s=window_s, calls=[],
        cell=SimpleNamespace(traffic={"method": method} if method else {}))


@pytest.mark.parametrize("over, want", [
    ({}, (10, 10, 10, 10, 10, 10, 10)),
    # the write's completion stamped after the server had the frame:
    # b1 is the server's stamp, the wake is 0, nothing is negative
    ({"write_done_us": T0 + 25}, (20, 0, 10, 10, 10, 10, 10)),
    # the client saw the response before the server stamped its flush
    ({"flushed_us": T0 + 65}, (10, 10, 10, 10, 20, 0, 10)),
    ({"write_done_us": T0 + 69, "flushed_us": T0 + 999},
     (20, 0, 10, 10, 20, 0, 10)),
])
def test_boundaries_take_the_earlier_stamp(over, want):
    c, s = _call(0, **over)
    got = rpc_spans.stages_of(c, s)
    assert got == want
    assert sum(got) == c.end_us - c.start_us == 70


def test_join_drops_what_lacks_a_half_or_a_stamp():
    spans = []
    for i in range(6):
        spans += _call(i)
    lone_client, _ = _call(6)
    _, lone_server = _call(7)                   # its call began untraced
    no_stamp = _call(8, handler_start_us=0)
    failed = _call(9)
    failed[0].error_code = 1008
    disordered = _call(12, handler_start_us=T0 + 12000 + 45)
    other = _call(10, method="Hold")
    before = _call(11, start=T0 - 500)
    attempt = SimpleNamespace(**vars(_call(0)[0]))
    attempt.span_id, attempt.parent_span_id = 999, 1    # child of call 0
    spans += [lone_client, lone_server, *no_stamp, *failed, *disordered,
              *other, *before, attempt]
    kept, dropped = rpc_spans.join_calls(spans, "Echo", T0, T0 + 10 ** 6)
    assert len(kept) == 6 and dropped == 4
    assert all(k == (10,) * 7 for k in kept)
    # no method named (the fan-out's traffic): every method counts
    kept, dropped = rpc_spans.join_calls(spans, None, T0, T0 + 10 ** 6)
    assert len(kept) == 7 and dropped == 4
    # no window: the call before it counts too
    assert len(rpc_spans.join_calls(spans, "Echo")[0]) == 7
    summary = rpc_spans.summarize(kept, dropped)
    assert summary["span_latency_us"]["mean"] == pytest.approx(
        sum(v["mean"] for v in summary["stages"].values())) == 70


@pytest.mark.parametrize("good, bad, reported", [
    (20, 2, True),          # 2 of 22 dropped: 9.1%
    (20, 3, False),         # 3 of 23: 13%
    (19, 0, False),         # fewer than 20 left
    (0, 0, False),          # a program or a run without spans
])
def test_nothing_is_reported_past_the_limits(good, bad, reported,
                                             monkeypatch, capsys):
    spans = []
    for i in range(good):
        spans += _call(i, handler_end_us=T0 + 1000 * i + 40 + i % 10)
    for i in range(good, good + bad):
        spans.append(_call(i)[0])
    monkeypatch.setattr(rpc_spans, "program_spans", lambda: spans)
    run = _run()
    run.calls = [(i, (T0 + 1000 * i) * 1000, (T0 + 1000 * i + 90) * 1000)
                 for i in range(good)]
    value = rpc_spans.stage_median(run, "handler")
    again = rpc_spans.stage_median(run, "issue")        # no second pass
    lines = [json.loads(ln)["info"]["rpc_stages"]
             for ln in capsys.readouterr().out.splitlines()]
    if reported:
        assert value == 14.5 and again == 10
    else:
        assert value is None and again is None
    if good or bad:
        assert len(lines) == 1
        assert lines[0]["reported"] is reported
        assert lines[0]["dropped"] == bad and lines[0]["calls"] == good
        if good:
            assert lines[0]["call_p50_us_same_part"] == 90
    else:
        assert lines == []


def test_an_older_program_gives_nothing(monkeypatch):
    """The parent commit's collector is there and empty in a traced run;
    a program without one must not raise either."""
    import sys
    monkeypatch.setitem(sys.modules, "brpc_tpu.rpc.span", None)
    assert rpc_spans.program_spans() == []
    assert rpc_spans.stage_median(_run(), "issue") is None
