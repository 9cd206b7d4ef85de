"""Shared by the benchmark's tests: where things are and how the real
command is run in rehearsal (a child process pinned to the CPU)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def read_bytes(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def bench(root: str = ROOT) -> dict:
    return read_json(root, "BENCHMARK.json")


def cell_names() -> list:
    return [w["name"] for w in bench()["workloads"]]


def run_cell(workload: str, *extra: str, root: str = ROOT,
             seconds: float = 1.0, trace: int = 0, rehearse: bool = True):
    """The benchmark's own command line, as the driver gives it."""
    cmd = [sys.executable] + bench(root)["command"][1:] + [
        "--workload", workload, "--seed", "7", "--seconds", str(seconds),
        "--trace", str(trace)]
    if rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd + list(extra), cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def last_line(proc) -> dict:
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, f"no stdout; stderr ends: {proc.stderr[-2000:]}"
    return json.loads(lines[-1])


def metric_names(kind: str, cell: str) -> set:
    return {m["name"] for m in bench()[kind]
            if cell in m.get("workloads", [cell])}


def check_cell_rehearses(cell: str, trace: int) -> None:
    """One cell through the real command in rehearsal: exit 0, the last
    line has exactly the contract's keys, ``correct`` is true."""
    proc = run_cell(cell, trace=trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    want = RESULT_KEYS | ({"breakdown"} if trace else set())
    assert set(res) == want
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    dev_keys = DEVICE_KEYS | ({"busy_s", "window_s"} if trace else set())
    assert set(res["device"]) == dev_keys
    assert res["device"]["platform"] == "cpu"      # marked: not a chip
    kind = "per_layer" if trace else "end_to_end"
    assert set(res["metrics"]) <= metric_names(kind, cell)
    assert res["metrics"], "no metric was reported"
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    if trace:
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in res["breakdown"].values())
    else:
        assert "setup_s" in res["metrics"]
    # the rehearsal says so on an earlier line
    assert '"rehearsal": true' in proc.stdout.splitlines()[0]
