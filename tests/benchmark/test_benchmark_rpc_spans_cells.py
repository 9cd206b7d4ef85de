"""The six ``rpc_*_us`` metrics through the real command in rehearsal:
in every cell that lists them with ``--trace 1``, in none without a
profile. Rehearsal numbers are no measurements."""

import json

import pytest

from bench_testlib import bench, cell_names, last_line, run_cell

RPC = sorted(m["name"] for m in bench()["per_layer"]
             if m["name"].startswith("rpc_"))

SIX = ("rpc_issue_us", "rpc_request_wake_us", "rpc_server_queue_us",
       "rpc_response_write_us", "rpc_response_wake_us", "rpc_complete_us")
THREE = ("tpu_performance.echo_small_d1", "tpu_performance.echo_small_d50",
         "parallel_allreduce.fanout_4mb_d1")


def _cells_of(name):
    return {m["name"]: m for m in bench()["per_layer"]}[name]["workloads"]


def test_six_entries_of_three_cells():
    """Found by name and by membership: a later cell may add an ``rpc_``
    reader or list its cell under these."""
    assert set(SIX) <= set(RPC)
    for name in SIX:
        assert set(THREE) <= set(_cells_of(name))
        assert "tpu_performance.step_2mb_d8" not in _cells_of(name)


@pytest.mark.parametrize("cell", cell_names())
def test_traced_rehearsal_prints_the_stages(cell):
    proc = run_cell(cell, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = last_line(proc)["metrics"]
    listed = [n for n in RPC if cell in _cells_of(n)]
    assert sorted(n for n in metrics if n.startswith("rpc_")) == listed
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()[:-1]]
    stages = [i["rpc_stages"] for i in infos if "rpc_stages" in i]
    if not listed:
        assert stages == []
        return
    assert len(stages) == 1 and stages[0]["reported"]
    s = stages[0]
    assert s["calls"] >= 20 and s["dropped"] <= 0.1 * s["calls"]
    # the stages' means add up to the mean span latency, and the spans
    # cover the call the benchmark timed from outside
    assert sum(v["mean"] for v in s["stages"].values()) == \
        pytest.approx(s["span_latency_us"]["mean"])
    for name in listed:
        assert metrics[name]["unit"] == "us"
        assert metrics[name]["value"] == \
            s["stages"][name[len("rpc_"):-len("_us")]]["p50"]


def test_untraced_run_has_no_span():
    proc = run_cell("tpu_performance.echo_small_d1", trace=0)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert not any(n.startswith("rpc_") for n in last_line(proc)["metrics"])
    assert "rpc_stages" not in proc.stdout
