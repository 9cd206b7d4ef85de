"""The eight metrics of ISSUE 35 through the real command in rehearsal:
the wake's four parts and the loop's four shares in every cell that
lists them with ``--trace 1``, none without a profile. Rehearsal numbers
are no measurements."""

import json
import os

import pytest

from bench_testlib import ROOT, bench, last_line, run_cell

from test_benchmark_wake_split import (CELLS_OF, LOOP, LOOP_CELLS, WAKE,
                                       WAKE_CELLS)

WAKE_NAMES = [w[0] for w in WAKE]
LOOP_NAMES = [n for n, _ in LOOP]
CELLS = sorted(set(WAKE_CELLS) | set(LOOP_CELLS))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The checkout through links, in a directory of its own: a traced
    run empties ``<root>/benchmark_out/trace/<cell>`` before it starts,
    and the other files' traced rehearsals of the same cells may run
    beside these in another worker."""
    root = tmp_path_factory.mktemp("wake_split_root")
    for name in ("benchmark", "brpc_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    return str(root)


def _infos(proc, key):
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()[:-1]
             if ln.startswith('{"info"')]
    return [i[key] for i in infos if key in i]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_prints_the_split(cell, root):
    proc = run_cell(cell, root=root, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    assert res["correct"] is True and res["failed"] == 0
    metrics = res["metrics"]
    units = {m["name"]: m["unit"] for m in bench()["per_layer"]}
    if cell in WAKE_CELLS:
        split = _infos(proc, "wake_split")
        assert len(split) == 1 and split[0]["reported"], split
        s = split[0]
        assert s["wakes"] >= 20 and 0 <= s["off_loop_share"] < 1
        # the means add up to the mean wake; a part is printed where it
        # is not 0, and every wake has a read and a wake of the loop
        assert sum(s["mean_us"].values()) == pytest.approx(s["wake_mean_us"])
        for name, _, part in WAKE:
            if s["mean_us"][part] and cell in CELLS_OF.get(name, WAKE_CELLS):
                assert metrics[name] == {"value": s["mean_us"][part],
                                         "unit": units[name]}
            else:
                assert name not in metrics
        assert {"wake_select_us", "wake_read_us"} <= set(metrics)
    else:
        assert not set(WAKE_NAMES) & set(metrics)
    if cell in LOOP_CELLS:
        assert set(LOOP_NAMES) <= set(metrics)
        shares = {n: metrics[n]["value"] for n in LOOP_NAMES}
        assert all(metrics[n]["unit"] == "%" for n in LOOP_NAMES)
        assert 0 < shares["dispatcher_awake_share"] <= 100
        assert sum(shares[n] for n in LOOP_NAMES[1:]) <= 100
        syscalls = _infos(proc, "syscalls")[0]
        # whole us each, so a window's difference is off by under one
        assert syscalls["dispatcher_read_us"] + syscalls["dispatcher_cut_us"] \
            + syscalls["dispatcher_process_us"] \
            <= syscalls["dispatcher_awake_us"] + 4
        assert syscalls["dispatcher_awake_us"] \
            <= syscalls["dispatcher_loop_us"] + 2
    else:
        assert not set(LOOP_NAMES) & set(metrics)


def test_untraced_run_has_no_split_and_moves_no_sum(root):
    proc = run_cell("parallel_allreduce.fanout_4mb_d1", root=root, trace=0)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert not set(WAKE_NAMES + LOOP_NAMES) & set(last_line(proc)["metrics"])
    assert _infos(proc, "wake_split") == []
    syscalls = _infos(proc, "syscalls")[0]
    assert [syscalls[k] for k in syscalls
            if k.startswith("dispatcher_") and k.endswith("_us")] == [0] * 5
