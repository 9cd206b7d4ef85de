"""Room for a later configuration: what a new model's cell adds (a
configuration, its traffic, a one-chip cell listed last under
``call_p50_us`` and ``calls_per_s``, and two per-layer readers with
their entries after everything) is ADDED to a copy of the benchmark, and
every accepted test that holds BENCHMARK.json's entries without starting
a run passes there. No file that was there changes.

The tests it runs are found from the suite, never listed by hand: each
test of a file that reads BENCHMARK.json and starts no run of the
benchmark (it names no ``run_cell``, ``check_cell_rehearses`` or
``subprocess``, itself or through a function or fixture of its module).
A later entries test is covered the day it lands."""

import ast
import json
import os
import shutil
import subprocess
import sys
import time
import xml.etree.ElementTree as ET
from types import SimpleNamespace

import pytest

from bench_testlib import ROOT, read_bytes, read_json

HERE = os.path.dirname(os.path.abspath(__file__))
READS = {"bench", "cell_names", "metric_names", "BENCHMARK.json"}
RUNS = {"run_cell", "check_cell_rehearses", "subprocess"}
CONF = "room_moe"
TRAFFIC = "chat_short"
CELL = f"{CONF}.{TRAFFIC}"
READERS = [
    {"name": "room_token_gap_us", "unit": "us", "better": "lower",
     "source": "program_span", "layer": "model", "moves": "call_p50_us",
     "workloads": [CELL]},
    {"name": "room_experts_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "calls_per_s",
     "workloads": [CELL]},
]
LIMIT_S = 30.0


def _names(node):
    """Every name a node uses: names, attributes, arguments (fixtures)
    and the file name BENCHMARK.json in a string."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            yield n.id
        elif isinstance(n, ast.Attribute):
            yield n.attr
        elif isinstance(n, ast.arg):
            yield n.arg
        elif isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and "BENCHMARK.json" in n.value:
            yield "BENCHMARK.json"


def entries_tests(directory: str) -> list:
    """``file::test`` of every test that reads the entries and starts no
    run, from the files of ``directory`` (this file aside)."""
    found = []
    for f in sorted(os.listdir(directory)):
        if not (f.startswith("test_") and f.endswith(".py")) \
                or f == os.path.basename(__file__):
            continue
        tree = ast.parse(read_bytes(os.path.join(directory, f)))
        if not READS & set(_names(tree)):
            continue
        uses = {n.name: set(_names(n)) for n in tree.body
                if isinstance(n, ast.FunctionDef)}
        runs = set(RUNS)
        while True:
            more = {name for name, used in uses.items()
                    if used & runs} - runs
            if not more:
                break
            runs |= more
        found += [f"{f}::{name}" for name in uses
                  if name.startswith("test_") and name not in runs]
    return found


def _write_json(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=2)


def _add_a_configuration(root: str) -> None:
    """Files and appended entries only, as a new model's cell brings."""
    bench = read_json(root, "BENCHMARK.json")
    body = read_json(root, "benchmark", "configs", "tpu_performance.json")
    body.update(name=CONF, reduced=["num_hidden_layers"],
                model={"hidden_size": 2048, "num_hidden_layers": 2,
                       "n_routed_experts": 64, "num_experts_per_tok": 6})
    _write_json(body, root, "benchmark", "configs", f"{CONF}.json")
    bench["configs"].append({
        "name": CONF, "source": "a throw-away configuration of the room test",
        "file": f"benchmark/configs/{CONF}.json",
        "reduced": ["num_hidden_layers"],
        "why": "sparse experts behind the service, as a new model brings them"})
    _write_json({"driver": "closed_loop", "style": "sync", "callers": 1,
                 "method": "Echo", "payload_bytes": [64], "pool": 2},
                root, "benchmark", "traffic", f"{TRAFFIC}.json")
    bench["workloads"].append({
        "name": CELL, "config": CONF, "traffic": TRAFFIC, "chips": 1,
        "why": "short chat turns at depth 1: a throw-away cell"})
    for m in bench["end_to_end"]:
        if m["name"] in ("call_p50_us", "calls_per_s"):
            m["workloads"].append(CELL)
    for entry in READERS:
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               entry["name"] + ".py"), "w") as f:
            f.write("def read(run):\n    return None\n")
        bench["per_layer"].append(entry)
    _write_json(bench, root, "BENCHMARK.json")


def _files(root: str) -> dict:
    out = {}
    for top in ("benchmark", os.path.join("tests", "benchmark")):
        for dirpath, dirnames, files in os.walk(os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                out[os.path.join(dirpath, f)] = read_bytes(
                    os.path.join(dirpath, f))
    return out


@pytest.fixture(scope="module")
def room(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("room"))
    for top in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(ROOT, top), os.path.join(root, top),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "brpc_tpu"), os.path.join(root, "brpc_tpu"))
    before, old = _files(root), read_json(root, "BENCHMARK.json")
    _add_a_configuration(root)
    tests = entries_tests(os.path.join(root, "tests", "benchmark"))
    xml = os.path.join(root, "room.xml")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "--rootdir", root, f"--junitxml={xml}"]
        + [os.path.join("tests", "benchmark", t) for t in tests],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    took = time.monotonic() - t0
    suite = ET.parse(xml).getroot()
    suite = suite if suite.tag == "testsuite" else suite.find("testsuite")
    counts = {k: int(suite.get(k)) for k in
              ("tests", "failures", "errors", "skipped")}
    return SimpleNamespace(root=root, before=before, old=old, tests=tests,
                           proc=proc, took=took, counts=counts)


def test_the_entries_tests_are_found_from_the_suite():
    tests = entries_tests(HERE)
    assert {
        "test_benchmark_contract.py::test_config_and_workload_entries",
        "test_benchmark_remote_entries.py::"
        "test_the_cell_reports_the_median_and_the_rate_cells_are_pr29s",
        "test_benchmark_remote_entries.py::"
        "test_the_six_new_entries_stand_together_after_what_was_there",
        "test_benchmark_remote_entries.py::"
        "test_the_cell_and_its_configuration_as_the_issue_names_them",
        "test_benchmark_thread_roles.py::"
        "test_the_six_entries_end_the_list_in_order",
        "test_benchmark_collective.py::"
        "test_pr29s_six_entries_stand_unchanged_and_together",
        "test_benchmark_wake_split.py::"
        "test_the_eight_stand_together_after_what_was_there",
        "test_benchmark_rpc_spans_cells.py::test_six_entries_of_three_cells",
    } <= set(tests)
    # a test that starts a run of the benchmark is left to the suite
    assert not {"test_benchmark_cells.py::test_cell_rehearses",
                "test_benchmark_thread_roles.py::"
                "test_traced_rehearsal_reports_all_six",
                "test_benchmark_additions.py::"
                "test_a_new_cell_is_files_and_entries_only"} & set(tests)


def test_every_entries_test_passes_beside_a_new_configuration(room):
    out = room.proc.stdout[-4000:] + room.proc.stderr[-2000:]
    rc = room.proc.returncode
    assert rc == 0, out
    assert room.counts["tests"] >= len(room.tests), out
    assert room.counts["failures"] == room.counts["errors"] == 0, out
    assert room.counts["skipped"] == 0, out      # no xfail, no skip
    added = read_json(room.root, "BENCHMARK.json")["workloads"]
    assert CELL in {w["name"] for w in added}


def test_the_run_takes_under_30_seconds(room):
    assert room.took < LIMIT_S, room.took


def test_what_was_there_is_unchanged(room):
    """New files and appended entries only."""
    for path, content in room.before.items():
        assert read_bytes(path) == content, path
    new = read_json(room.root, "BENCHMARK.json")
    for group in ("configs", "workloads", "per_layer"):
        assert new[group][:len(room.old[group])] == room.old[group]
    for was, now in zip(room.old["end_to_end"], new["end_to_end"],
                        strict=True):
        listed = was.get("workloads", [])
        assert now.get("workloads", [])[:len(listed)] == listed
        assert dict(now, workloads=listed) == dict(was, workloads=listed)
