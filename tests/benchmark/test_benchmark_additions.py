"""The harness takes additions as data: a throw-away traffic mix, a
per-layer reader and a cell are ADDED to a temporary copy of the
benchmark (no existing file is edited, BENCHMARK.json only gains
entries) and the new cell runs in rehearsal."""

import json
import os
import shutil

from bench_testlib import (ROOT, last_line, read_bytes, read_json,
                           run_cell)


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("brpc_tpu",):       # the program itself, untouched
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    before = {}
    for dirpath, _d, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = read_bytes(p)

    # 1) a traffic mix: parameters only
    with open(os.path.join(root, "benchmark", "traffic",
                           "echo_64b_d4.json"), "w") as f:
        json.dump({"driver": "closed_loop", "style": "callback",
                   "method": "Echo", "depth": 4, "payload_bytes": [64],
                   "pool": 2}, f)
    # 2) a per-layer metric: a reader of its own
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "lane_bytes_per_call.py"), "w") as f:
        f.write("def read(run):\n"
                "    return run.counters['lane']['bytes_out'] / "
                "max(1, run.verified_calls)\n")
    # 3) entries in BENCHMARK.json
    bench = read_json(ROOT, "BENCHMARK.json")
    cell = "tpu_performance.echo_64b_d4"
    bench["workloads"].append({
        "name": cell, "config": "tpu_performance", "traffic": "echo_64b_d4",
        "chips": 1, "why": "throw-away cell of the additions test"})
    for m in bench["end_to_end"]:
        if m["name"] == "calls_per_s":
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "lane_bytes_per_call", "unit": "B/call", "better": "lower",
        "source": "program_counter", "layer": "device lane",
        "moves": "calls_per_s", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    proc = run_cell(cell, root=root, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    assert res["correct"] is True
    # a request and a response of 64 bytes each
    assert res["metrics"]["lane_bytes_per_call"]["value"] > 100
    proc = run_cell(cell, root=root, trace=0)
    assert set(last_line(proc)["metrics"]) >= {"calls_per_s", "setup_s"}
    # no file that was there has changed
    for p, content in before.items():
        assert read_bytes(p) == content, p
