"""Test env: force JAX onto a virtual 8-device CPU platform BEFORE any jax
import, so sharding/collective tests run without TPU hardware (the same
trick the reference uses by testing everything over 127.0.0.1 loopback,
SURVEY.md §4)."""

import os
import sys

# force, not setdefault: tier-1 is a CPU suite wherever it runs, and on a
# TPU host an inherited platform would hand the chip to the first test
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---------------------------------------------------------- orphan guard
# A test that leaks a child process (an example server, a smoke
# subprocess) leaves a port, a pidfile and possibly a device holder
# behind for whoever runs next on this machine — a chip has room for
# one process. Fail the SUITE if it exits with live children it did
# not start with.
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: tier-2 tests excluded from the tier-1 gate "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "sanitize: rebuilds the native lane under "
        "ASan/UBSan and re-runs the differential fuzzers against it")


def _live_children():
    """(pid, cmdline) of our direct live children, zombies excluded
    (a reaped-later zombie is not a leak)."""
    me = os.getpid()
    out = []
    try:
        pids = [int(p) for p in os.listdir("/proc") if p.isdigit()]
    except OSError:
        return out
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
            rest = stat.rsplit(")", 1)[1].split()
            state, ppid = rest[0], int(rest[1])
            if ppid != me or state == "Z":
                continue
            from brpc_tpu.butil.pidfile import cmdline as _cmdline
            out.append((pid, _cmdline(pid)[:160]))
        except (OSError, ValueError, IndexError):
            continue
    return out


@pytest.fixture(scope="session", autouse=True)
def _orphan_guard():
    import time as _t
    before = {pid for pid, _ in _live_children()}
    yield
    # children watchdog/terminate themselves asynchronously: grant a
    # short grace before calling anything a leak
    deadline = _t.monotonic() + 5.0
    leaked = []
    while _t.monotonic() < deadline:
        leaked = [c for c in _live_children() if c[0] not in before]
        if not leaked:
            return
        _t.sleep(0.25)
    # kill them so THIS failure doesn't outlive the session, then fail
    # loudly with names
    import signal as _sig
    for pid, _ in leaked:
        try:
            os.kill(pid, _sig.SIGKILL)
        except OSError:
            pass
    pytest.fail(f"test suite leaked child processes: {leaked}",
                pytrace=False)
