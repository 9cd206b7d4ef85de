"""The trace reduction on a synthetic trace small enough to check by
hand (the recorded chip slice has its own file)."""

import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.lib.trace_reduce import UNATTRIBUTED, Trace, union


def _trace():
    ops = [("fusion", 100.0, 100.0), ("fusion", 150.0, 100.0),
           ("copy", 400.0, 50.0)]
    mods = [("jit_perf_step(123)", 100.0, 150.0),
            ("jit_count_bad(9)", 400.0, 50.0)]
    spans = [("bench.issue", 0.0, 100.0), ("bench.wait_ready", 240.0, 200.0),
             ("bench.verify", 300.0, 50.0)]
    return Trace({0: {"XLA Ops": ops, "XLA Modules": mods}}, spans)


def test_busy_window_and_programs():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    t = _trace()
    assert t.window() == (0.0, 450.0)
    assert t.busy_intervals(0) == [(100.0, 250.0), (400.0, 450.0)]
    assert t.busy_s([0]) == pytest.approx(200e-9)
    assert t.program_durations_us("perf_step", [0]) == [0.15]
    assert t.program_durations_us("perf", [0]) == []
    assert t.top_ops([0])[0] == ["fusion", pytest.approx(200e-9)]


def test_idle_gaps_go_to_the_innermost_host_span():
    # idle: 0-100 (issue), 250-400: wait_ready 250-300 and 350-400,
    # verify (nested, started later) 300-350
    gaps = dict(_trace().idle_gaps([0]))
    assert gaps == {"bench.issue": pytest.approx(100e-9),
                    "bench.wait_ready": pytest.approx(100e-9),
                    "bench.verify": pytest.approx(50e-9)}


def test_idle_outside_any_span_is_named_so():
    t = Trace({0: {"XLA Ops": [("op", 0.0, 10.0), ("op", 90.0, 10.0)]}}, [])
    assert t.idle_gaps([0]) == [[UNATTRIBUTED, pytest.approx(80e-9)]]
    assert Trace({}, []).window() is None and Trace({}, []).idle_gaps([0]) == []
