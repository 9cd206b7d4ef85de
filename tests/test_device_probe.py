"""tools/device_probe.py — the dedicated device-lane probe.

A backend that never comes up must leave evidence (python stacks,
per-thread kernel wchan, timeline), not an error string. These tests
exercise the forensic path with a self-test hang — no jax in the child
before the hang point — and the /proc readers against our own live
process.
"""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import device_probe  # noqa: E402


def test_task_wchans_reads_own_threads():
    evt = threading.Event()
    th = threading.Thread(target=evt.wait, daemon=True)
    th.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:   # wait until the thread parks
            tasks = device_probe._task_wchans(os.getpid())
            if any("futex" in t["wchan"] for t in tasks):
                break
            time.sleep(0.05)
        assert len(tasks) >= 2          # main + waiter at least
        assert all({"tid", "comm", "state", "wchan"} <= set(t) for t in tasks)
        # the waiter thread is parked in futex — its wchan must say so
        wchans = " ".join(t["wchan"] for t in tasks)
        assert "futex" in wchans
    finally:
        evt.set()
        th.join(5)


def test_snapshot_shape():
    snap = device_probe._snapshot(os.getpid(), time.monotonic())
    assert "tasks" in snap and "vm_rss" in snap
    assert snap["elapsed_s"] <= 0.5


def test_hang_produces_forensic_report(tmp_path, monkeypatch):
    """The flagship path: a child that wedges in a C call (sleep) must
    yield a report naming the python frame and the kernel syscall."""
    monkeypatch.setenv("BRPC_TPU_PROBE_SELFTEST_HANG", "1")
    out = str(tmp_path / "probe.json")
    t0 = time.monotonic()
    lane = device_probe.run_probe(budget_s=6.0, out_path=out)
    assert time.monotonic() - t0 < 30.0   # hang bounded by budget + dump
    assert "hung" in lane["error"]
    hang = lane["hang"]
    # the exact blocking python frame is named
    assert "_child_main" in hang["python_stacks"]
    # the kernel-side syscall is named per thread
    tasks = hang["final_snapshot"]["tasks"]
    assert tasks and any("nanosleep" in t["wchan"] or t["wchan"] != "0"
                         for t in tasks)
    assert hang["last_phase"].get("phase") == "selftest_hang"
    # the incremental artifact landed on disk and parses
    with open(out) as f:
        doc = json.load(f)
    assert "error" in doc and "hang" in doc


def test_attribution_names_external_client_hang():
    """Blocked in PJRT client creation with no repo frame on the stack
    must be attributed EXTERNAL with the syscalls named."""
    hang = {
        "python_stacks": 'File ".../jaxlib/xla_client.py", line 161 '
                         "in make_c_api_client",
        "final_snapshot": {
            "tasks": [{"wchan": "hrtimer_nanosleep"},
                      {"wchan": "ep_poll"}],
        },
    }
    a = device_probe._attribute_hang(hang)
    assert a.startswith("EXTERNAL") and "hrtimer_nanosleep" in a
    # without the client frame, a repo frame is attributed to the repo
    hang["python_stacks"] = 'File ".../brpc_tpu/transport/ici.py", ' \
                            "line 1 in pull"
    assert device_probe._attribute_hang(hang).startswith("REPO")


def test_lane_failure_keeps_bringup_evidence(tmp_path, monkeypatch):
    """A sweep failure after a healthy bring-up must report partial
    results (bringup + lane_error), not discard the evidence."""
    monkeypatch.setenv("BRPC_TPU_PROBE_SELFTEST_LANE_FAIL", "1")
    lane = device_probe.run_probe(budget_s=60.0,
                                  out_path=str(tmp_path / "p.json"))
    assert lane.get("bringup", {}).get("platform") == "cpu", lane
    assert "selftest lane failure" in lane.get("lane_error", ""), lane
    assert "_child_lane" in lane.get("lane_error_traceback", ""), \
        "traceback must localize the lane failure"
    assert "error" not in lane    # bring-up itself succeeded


def test_lane_failure_fails_the_tool(tmp_path):
    """A device-lane error is an error: the CLI exits non-zero (it used
    to os._exit(0) whatever the lane did)."""
    import subprocess
    env = dict(os.environ, BRPC_TPU_PROBE_SELFTEST_LANE_FAIL="1")
    p = subprocess.run(
        [sys.executable, device_probe.__file__, "--budget", "60",
         "--out", str(tmp_path / "p.json")],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    assert "selftest lane failure" in json.loads(
        p.stdout.strip().splitlines()[-1])["lane_error"]


def test_probe_child_dead_is_reported(monkeypatch):
    """A child that dies before producing a result must be reported
    with rc + stderr tail, not hang the parent."""
    real_popen = device_probe.subprocess.Popen

    def bad_popen(argv, **kw):
        return real_popen([sys.executable, "-c",
                           "import sys; sys.stderr.write('boom'); "
                           "sys.exit(3)"], **kw)

    monkeypatch.setattr(device_probe.subprocess, "Popen", bad_popen)
    lane = device_probe.run_probe(budget_s=5.0, out_path=None)
    assert "rc=3" in lane["error"] and "boom" in lane["error"]
