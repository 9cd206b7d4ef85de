"""The client process of ``remote_caller``: every wait on it is bounded
and no run leaves it behind. A child killed inside the window ends the
run within seconds; after a run, and after a parent that was killed,
no child is alive; a child whose pipe closes exits by itself."""

import os
import signal
import subprocess
import sys
import time

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from bench_testlib import ROOT, bench

CELL = "remote_caller.step_2mb_d8_tpud"
CHILD = os.path.join(ROOT, "benchmark", "drivers", "remote_child.py")


def _start(seconds: float):
    cmd = [sys.executable] + bench()["command"][1:] + [
        "--workload", CELL, "--seed", "7", "--seconds", str(seconds),
        "--trace", "0", "--rehearse"]
    return subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _children_of(pid: int) -> list:
    out = []
    for p in os.listdir("/proc"):
        if not p.isdigit():
            continue
        try:
            with open(f"/proc/{p}/stat") as f:
                rest = f.read().rsplit(")", 1)[1].split()
            with open(f"/proc/{p}/cmdline") as f:
                cmdline = f.read()
        except OSError:
            continue
        if int(rest[1]) == pid and rest[0] != "Z" \
                and "remote_child.py" in cmdline:
            out.append(int(p))
    return out


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _wait_for_child(proc, timeout_s: float = 120.0) -> int:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        kids = _children_of(proc.pid)
        if kids:
            return kids[0]
        assert proc.poll() is None, proc.stderr.read()[-3000:]
        time.sleep(0.05)
    raise AssertionError("the run started no client process")


def _gone(pid: int, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while _alive(pid):
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.05)
    return True


def test_a_child_killed_inside_the_window_ends_the_run_within_seconds():
    proc = _start(60.0)
    try:
        child = _wait_for_child(proc)
        time.sleep(4.0)         # set-up is over or nearly: either way
        os.kill(child, signal.SIGKILL)
        t0 = time.monotonic()
        out, err = proc.communicate(timeout=30)
        assert time.monotonic() - t0 < 20
    finally:
        proc.kill()
    assert proc.returncode != 0
    assert "client process" in err
    lines = [ln for ln in out.splitlines() if ln.startswith('{"correct"')]
    assert not lines or '"correct": false' in lines[-1]


def test_no_child_is_alive_after_a_run():
    proc = _start(1.0)
    try:
        child = _wait_for_child(proc)
        out, err = proc.communicate(timeout=240)
    finally:
        proc.kill()
    assert proc.returncode == 0, err[-3000:]
    assert '"correct": true' in out.splitlines()[-1]
    assert _gone(child, 5.0)
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark_out", "remote_caller", f"data.{proc.pid}.bin"))


def test_no_child_is_alive_after_a_parent_that_was_killed():
    proc = _start(60.0)
    try:
        child = _wait_for_child(proc)
        time.sleep(2.0)
        proc.kill()             # SIGKILL: no finally runs, no close()
        proc.communicate(timeout=30)
        assert _gone(child, 10.0), "the client outlived its parent"
    finally:
        proc.kill()
        if _alive(child):
            os.kill(child, signal.SIGKILL)
    leftover = os.path.join(ROOT, "benchmark_out", "remote_caller",
                            f"data.{proc.pid}.bin")
    if os.path.exists(leftover):
        os.remove(leftover)


def test_a_child_whose_pipe_closes_exits_and_a_bad_command_answers():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.Popen([sys.executable, CHILD], stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.DEVNULL, env=env, text=True)
    try:
        child.stdin.write('{"cmd": "clock"}\n{"cmd": "warm"}\n')
        child.stdin.flush()
        import json
        clock = json.loads(child.stdout.readline())
        assert clock["ok"] and abs(clock["monotonic_ns"]
                                   - time.monotonic_ns()) < 60e9
        # nothing loaded: the command fails, the child says so and lives
        failed = json.loads(child.stdout.readline())
        assert failed["ok"] is False and failed["error"]
        assert child.poll() is None
        child.stdin.close()
        assert child.wait(timeout=10) == 0
    finally:
        child.kill()
