"""CPU a call by thread role and the probe of the wait for the
interpreter (ISSUE 29) as per-layer metrics: six readers of
``syscall_stats.snapshot()`` through the window's delta. Each reports
nothing under a program that lacks its counters (the parent) and a
number with them; the real command prints all six in rehearsal in the
three cells that report ``calls_per_s``, traced, and none untraced.
Rehearsal numbers are no measurements."""

import types

import pytest

from bench_testlib import bench, last_line, run_cell

from benchmark.layer_metrics import (cpu_us_per_call_callers,
                                     cpu_us_per_call_dispatcher,
                                     cpu_us_per_call_native,
                                     cpu_us_per_call_workers,
                                     interp_wait_over_4ms_share,
                                     interp_wait_us)

CELLS = ["tpu_performance.echo_small_d50", "tpu_performance.step_2mb_d8",
         "streaming_echo.ring_2mb_w8"]
ENTRIES = [
    ("cpu_us_per_call_dispatcher", "us/call", "socket and framing"),
    ("cpu_us_per_call_workers", "us/call", "entry and dispatch"),
    ("cpu_us_per_call_callers", "us/call", "entry and dispatch"),
    ("cpu_us_per_call_native", "us/call", "device lane"),
    ("interp_wait_us", "us", "entry and dispatch"),
    ("interp_wait_over_4ms_share", "%", "entry and dispatch"),
]
NAMES = [e[0] for e in ENTRIES]
CPU_NAMES = NAMES[:4]

# a window's delta as the program with the counters gives it
ROLES = {"cpu_us_dispatcher": 300, "cpu_us_worker": 1000, "cpu_us_timer": 40,
         "cpu_us_device_wait": 160, "cpu_us_probe": 10,
         "cpu_us_caller": 2500, "cpu_us_python": 4010}
PROBE = {"interp_probe_n": 400, "interp_probe_wait_us": 600_000,
         "interp_probe_over_1ms": 200, "interp_probe_over_4ms": 50}
PARENT = {"recv": 9, "dispatcher_ticks": 3}


def test_the_six_entries_end_the_list_in_order():
    """Found by name, in order and together; what follows them is a
    later cell's."""
    per_layer = bench()["per_layer"]
    first = [m["name"] for m in per_layer].index(NAMES[0])
    assert per_layer[first:first + len(ENTRIES)] == [
        {"name": name, "unit": unit, "better": "lower",
         "source": "program_counter", "layer": layer,
         "moves": "calls_per_s", "workloads": CELLS}
        for name, unit, layer in ENTRIES]
    e2e = {e["name"]: e for e in bench()["end_to_end"]}["calls_per_s"]
    assert set(CELLS) <= set(e2e["workloads"])


def _run(syscalls, calls=10, cpu_s=0.005):
    return types.SimpleNamespace(
        counters={"syscalls": syscalls, "cpu_s": cpu_s},
        verified_calls=calls)


@pytest.mark.parametrize("reader, want", [
    (cpu_us_per_call_dispatcher, 30.0),
    (cpu_us_per_call_workers, 120.0),       # worker + timer + device_wait
    (cpu_us_per_call_callers, 250.0),
    (cpu_us_per_call_native, 99.0),         # 5,000 us of process - 4,010
    (interp_wait_us, 1500.0),
    (interp_wait_over_4ms_share, 12.5),
], ids=NAMES)
def test_reader(reader, want):
    assert reader.read(_run(PARENT)) is None        # the parent: no counter
    full = dict(PARENT, **ROLES, **PROBE)
    assert reader.read(_run(full)) == pytest.approx(want)
    if reader in (interp_wait_us, interp_wait_over_4ms_share):
        # the probe never ran (an untraced window), or measured no sleep
        idle = dict(full, **dict.fromkeys(PROBE, 0))
        assert reader.read(_run(idle)) is None
    else:
        assert reader.read(_run(full, calls=0)) is None


def test_a_share_of_zero_is_reported_as_zero():
    s = dict(PROBE, interp_probe_over_4ms=0)
    assert interp_wait_over_4ms_share.read(_run(s)) == 0.0


def test_the_four_roles_sum_to_the_process_less_the_probe():
    run = _run(dict(ROLES, **PROBE), cpu_s=0.005)
    total = sum(m.read(run) for m in (
        cpu_us_per_call_dispatcher, cpu_us_per_call_workers,
        cpu_us_per_call_callers, cpu_us_per_call_native))
    assert total == pytest.approx((5000 - ROLES["cpu_us_probe"]) / 10)


def test_the_program_carries_the_counters():
    from brpc_tpu.transport import syscall_stats
    assert set(ROLES) | set(PROBE) <= set(syscall_stats.snapshot())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_all_six(cell):
    proc = run_cell(cell, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    assert res["correct"] is True and res["failed"] == 0
    metrics = res["metrics"]
    assert set(NAMES) <= set(metrics)
    units = {name: unit for name, unit, _ in ENTRIES}
    for name in NAMES:
        assert metrics[name]["unit"] == units[name]
        assert metrics[name]["value"] >= 0
    assert metrics["interp_wait_over_4ms_share"]["value"] <= 100
    # the split is of the CPU the accepted metric reads (the probe's own
    # thread aside): the four say where host_cpu_us_per_call went
    if "host_cpu_us_per_call" in metrics:
        total = sum(metrics[n]["value"] for n in CPU_NAMES)
        whole = metrics["host_cpu_us_per_call"]["value"]
        assert 0.9 * whole <= total <= 1.0001 * whole
    # untraced, no per-layer metric is printed at all
    assert not set(NAMES) & set(last_line(run_cell(cell))["metrics"])
