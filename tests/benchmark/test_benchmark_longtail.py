"""The ``longtail_echo`` deployment: its schedule, and what must make a
run ``correct`` or not. Rehearsal numbers are no measurements."""

import json

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from bench_testlib import last_line, read_json, ROOT, run_cell
from benchmark.reference import longtail as reference

CELL = "longtail_echo.poisson_1pct_8conn"
TRAFFIC = read_json(ROOT, "benchmark", "traffic", "poisson_1pct_8conn.json")


def _schedule(seed, rate, seconds):
    return reference.schedule(seed, rate, seconds, TRAFFIC["connections"],
                              TRAFFIC["long_share"],
                              len(TRAFFIC["payload_bytes"]))


def test_the_schedule_is_a_pure_function_of_the_seed():
    big = 3200000123            # the driver's seeds pass 2**31
    a, b = _schedule(big, 10000.0, 10.0), _schedule(big, 10000.0, 10.0)
    assert a == b and a != _schedule(big + 1, 10000.0, 10.0)
    n = len(a)
    assert abs(n - 100000) < 5 * 100000 ** 0.5          # Poisson count
    assert all(x.at_s < y.at_s for x, y in zip(a, a[1:]))
    assert 0.0 < a[0].at_s and a[-1].at_s < 10.0
    longs = sum(1 for x in a if x.long)
    assert abs(longs - 0.01 * n) < 5 * (n * 0.01 * 0.99) ** 0.5
    for field, k in (("conn", 8), ("size", 3)):
        counts = [sum(1 for x in a if getattr(x, field) == v)
                  for v in range(k)]
        assert sum(counts) == n
        assert all(abs(c - n / k) < 5 * (n / k) ** 0.5 for c in counts)
    # a shorter window's arrivals are the longer one's first
    assert _schedule(big, 10000.0, 1.0) == [x for x in a if x.at_s < 1.0]


def test_a_rehearsed_run_issues_exactly_the_schedule_and_is_correct():
    proc = run_cell(CELL, seconds=2.0, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    plan = _schedule(7, TRAFFIC["rehearse_rate_calls_per_s"], 2.0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == len(plan)
    loops = [json.loads(ln)["info"]["open_loop"]
             for ln in proc.stdout.splitlines() if '"open_loop"' in ln]
    assert loops[-1]["scheduled"] == len(plan)
    assert loops[-1]["scheduled_long"] == sum(1 for a in plan if a.long)
    assert loops[-1]["long_call_us"]["n"] == loops[-1]["scheduled_long"]
    # the program's two sources are there to read
    assert {"worker_wait_p95_us", "worker_held_share"} <= set(res["metrics"])
    # the long calls are in no sample: the samples are the short calls
    samples = [json.loads(ln)["info"]["samples"]
               for ln in proc.stdout.splitlines() if '"samples"' in ln]
    assert samples == [len(plan) - loops[-1]["scheduled_long"]]


def test_a_corrupted_response_is_not_correct():
    proc = run_cell(CELL, "--inject", "corrupt_response")
    res = last_line(proc)
    assert res["correct"] is False and res["failed"] > 0
    assert proc.returncode != 0
    assert "differ from the reference" in proc.stdout
