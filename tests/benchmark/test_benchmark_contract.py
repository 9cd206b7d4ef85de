"""BENCHMARK.json against the contract's limits that can be checked
without a run: keys, names, units, lengths, files.

The files of this directory hold at most four tests each, on purpose:
tier-1 runs under xdist ``--dist loadfile``, which hands out files
largest first, and the suite has tests that depend on which files share
a worker (tests/test_http.py leaves ``rpcz_enabled`` on, which fails
tests/test_device_stats.py after it). Small files are handed out last
and leave the rest of the schedule as it was."""

import os
import re

from bench_testlib import ROOT, bench, read_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
B = bench()
METRICS = B["end_to_end"] + B["per_layer"]


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(B["paths"]) <= 16 and all(PATH.match(p)
                                              for p in B["paths"])
    assert len(B["command"]) <= 32 and all(_line(w) for w in B["command"])
    assert any(B["command"][1].startswith(p + "/") for p in B["paths"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    # a full check with all 24 cells must fit the driver's limit
    runs = 2 + 14 * 24
    assert (runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


def test_config_and_workload_entries():
    for conf in B["configs"]:
        _check_config(conf)
    for cell in B["workloads"]:
        _check_workload(cell)


def _check_config(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"])
    assert _line(conf["source"]) and _line(conf["why"])
    assert any(conf["file"].startswith(p + "/") for p in B["paths"])
    assert os.path.isfile(os.path.join(ROOT, conf["file"]))
    assert len(conf["reduced"]) <= 16
    assert all(NAME.match(k) for k in conf["reduced"])
    assert any(w["config"] == conf["name"] for w in B["workloads"])
    body = read_json(ROOT, conf["file"])
    for key in ("source", "assumed", "reduced", "guarantees", "layout",
                "service"):
        assert key in body, f"{conf['file']} lacks {key!r}"
    assert body["reduced"] == conf["reduced"]
    assert body["layout"]["channel_options"]["max_retry"] == 0
    assert body["layout"]["channel_options"]["backup_request_ms"] is None


def _check_workload(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in B["configs"]}
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    reports = [m["name"] for m in B["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in reports and len(reports) >= 2
    layer = [m for m in B["per_layer"]
             if cell["name"] in m.get("workloads", [cell["name"]])]
    assert layer
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in reports for m in layer)


def test_metric_entries():
    for metric in METRICS:
        _check_metric(metric)


def _check_metric(metric):
    end_to_end = metric in B["end_to_end"]
    keys = ({"name", "unit", "better", "bound", "source"} if end_to_end
            else {"name", "unit", "better", "source", "layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in B["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    kind = "end_to_end" if end_to_end else "layer_metrics"
    assert os.path.isfile(os.path.join(ROOT, "benchmark", kind,
                                       metric["name"] + ".py"))
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert _line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in B["end_to_end"]}
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def _names_are_unique_and_cells_within_limits():
    for group in (B["configs"], B["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs)) and 2 <= len(pairs) <= 24
    four = sum(1 for w in B["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(pairs) // 2)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_names_cells_and_file_names_within_limits():
    _names_are_unique_and_cells_within_limits()
    for top in B["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel
