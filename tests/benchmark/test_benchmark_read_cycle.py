"""The two counters of the read cycle (ISSUE 27) as per-layer metrics:
``dispatcher_ticks_per_call`` and ``pluck_join_share`` read
``syscall_stats.snapshot()`` through the window's delta, report nothing
under a program that lacks the counters (the parent), and come out of
the real command in rehearsal in the cells that list them, and in no
other. Rehearsal numbers are no measurements."""

import types

import pytest

from bench_testlib import bench, last_line, run_cell

from benchmark.layer_metrics import (dispatcher_ticks_per_call,
                                     pluck_join_share)

NAMES = ("dispatcher_ticks_per_call", "pluck_join_share")
CELLS = {
    "dispatcher_ticks_per_call": ["tpu_performance.echo_small_d50",
                                  "tpu_performance.step_2mb_d8"],
    "pluck_join_share": ["tpu_performance.echo_small_d1",
                         "tpu_performance.echo_small_d50"],
}


def _entry(name):
    return {m["name"]: m for m in bench()["per_layer"]}[name]


@pytest.mark.parametrize("name, unit, better, moves", [
    ("dispatcher_ticks_per_call", "ticks/call", "lower", "calls_per_s"),
    ("pluck_join_share", "%", "higher", "call_p50_us"),
])
def test_entries(name, unit, better, moves):
    m = _entry(name)
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": "program_counter", "layer": "socket and framing",
                 "moves": moves, "workloads": CELLS[name]}
    # every listed cell reports the end-to-end metric it moves
    e2e = {e["name"]: e for e in bench()["end_to_end"]}[moves]
    assert set(m["workloads"]) <= set(e2e["workloads"])


def _run(syscalls, calls=4):
    return types.SimpleNamespace(counters={"syscalls": syscalls},
                                 verified_calls=calls)


@pytest.mark.parametrize("syscalls, calls, want", [
    ({"recv": 9}, 10, None),                     # the parent: no counter
    ({"dispatcher_ticks": 25}, 0, None),         # no verified call
    ({"dispatcher_ticks": 25}, 10, 2.5),
    ({"dispatcher_ticks": 0}, 10, 0.0),
])
def test_dispatcher_ticks_per_call_reader(syscalls, calls, want):
    assert dispatcher_ticks_per_call.read(_run(syscalls, calls)) == want


@pytest.mark.parametrize("syscalls, want", [
    ({"recv": 9}, None),                         # the parent: no counter
    ({"join_plucked": 0, "join_waited": 0}, None),   # no join waited
    ({"join_plucked": 19, "join_waited": 1}, 95.0),
    ({"join_plucked": 0, "join_waited": 7}, 0.0),
])
def test_pluck_join_share_reader(syscalls, want):
    assert pluck_join_share.read(_run(syscalls)) == want


def test_the_program_carries_the_counters():
    from brpc_tpu.transport import syscall_stats
    assert {"dispatcher_ticks", "join_plucked", "join_waited"} <= \
        set(syscall_stats.snapshot())


@pytest.mark.parametrize("cell", sorted({c for v in CELLS.values()
                                         for c in v}))
def test_traced_rehearsal_reports_them_where_listed(cell):
    proc = run_cell(cell, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    assert res["correct"] is True and res["failed"] == 0
    metrics = res["metrics"]
    for name in NAMES:
        assert (name in metrics) == (cell in CELLS[name]), (name, cell)
    if "dispatcher_ticks_per_call" in metrics:
        m = metrics["dispatcher_ticks_per_call"]
        # a busy period pauses read interest: a few ticks a call where
        # the level trigger spun tens of times
        assert m["unit"] == "ticks/call" and 0 < m["value"] < 4
    if "pluck_join_share" in metrics:
        m = metrics["pluck_join_share"]
        assert m["unit"] == "%" and 0 < m["value"] <= 100
        if cell.endswith("echo_small_d1"):
            assert m["value"] > 90      # one caller: it reads its own reply
    # untraced, no per-layer metric is printed at all
    assert not set(NAMES) & set(last_line(run_cell(cell))["metrics"])
