"""``longtail_echo``'s deployment in this process (CPU, rehearsal sizes):
a hold that is too short, an arrival dropped and an arrival doubled each
leave failures behind, and the schedule as issued leaves none."""

import threading
import types

import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.drivers import open_loop
from benchmark.lib.loader import Cell, load_module
from benchmark.lib.stamps import Stamps

CELL = "longtail_echo.poisson_1pct_8conn"
WINDOW_S = 0.6


@pytest.fixture(scope="module")
def dep():
    import jax

    cell = Cell(CELL, rehearse=True)
    # enough long calls in a short window for the checks below
    cell.traffic = dict(cell.traffic, long_share=0.1)
    ctx = types.SimpleNamespace(cell=cell, seed=11, inject="",
                                devices=jax.devices()[:1],
                                stamps=Stamps(trace=False))
    dep = load_module("services", cell.config["service"]).build(ctx)
    dep.prepare()
    dep.start()
    try:
        dep.warm()
        yield dep
    finally:
        dep.close()


def _window(dep, issue=None):
    """One window through the driver (or through ``issue(plan)``);
    returns the failures it left."""
    stamps = dep.stamps
    before = len(stamps.failures)
    if issue is None:
        win = open_loop.run(dep, dep.traffic, WINDOW_S, stamps)
        assert win.attempted == len(dep._plan) > 30
    else:
        plan = dep.plan(dep.traffic["rehearse_rate_calls_per_s"], WINDOW_S)
        seqs = issue([dep.first_seq + i for i in range(len(plan))])
        done = threading.Semaphore(0)
        for seq in seqs:
            dep.call(seq, lambda _cntl: done.release())
        for _ in seqs:
            assert done.acquire(timeout=30)
    assert dep.finish() == 0
    dep.first_seq += len(dep._plan)
    return stamps.failures[before:]


def test_the_schedule_as_issued_leaves_no_failure(dep):
    assert _window(dep) == []
    assert any(a.long for a in dep._plan)


def test_a_hold_of_1_ms_is_not_the_workload(dep):
    hold = dep.hold_s
    dep.hold_s = 0.001
    try:
        failures = _window(dep)
    finally:
        dep.hold_s = hold
    longs = [dep._plan_base + i for i, a in enumerate(dep._plan) if a.long]
    # a loaded host may stretch a handler past 5 ms by itself: every
    # failure is a long call's short hold, and most long calls fail
    assert failures and {seq for seq, _why in failures} <= set(longs)
    assert len(failures) > len(longs) // 2
    assert all("under long_hold_ms" in why for _seq, why in failures)


@pytest.mark.parametrize("issue, want", [
    (lambda seqs: seqs[:5] + seqs[6:], "handled 0 times"),
    (lambda seqs: seqs[:5] + [seqs[5]] + seqs[5:], "handled 2 times"),
], ids=["dropped", "doubled"])
def test_an_arrival_dropped_or_doubled_is_not_correct(dep, issue, want):
    failures = _window(dep, issue)
    assert [seq for seq, _why in failures] == [dep._plan_base + 5]
    assert want in failures[0][1]
