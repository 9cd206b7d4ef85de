"""The lane's same-process copy onto the receiving device (PR 36).

``IciConn._take_local`` copies a single-device array through
``pxla.batched_device_put``, which is what ``jax.device_put(x, device)``
comes down to, once ``ici._d2d_put`` has proved on the copy's own two
devices that the private call gives what the public one gives; where the
proof fails, for whatever reason, every take makes the public call.
Pinned on raw conn pairs between the eight virtual CPU devices: arrays,
devices and counters, no times.
"""

import socket as pysocket
import time

import numpy as np
import pytest

from brpc_tpu.butil.device_pool import DeviceRecvPool
from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.transport import ici, syscall_stats
from brpc_tpu.transport.tcp import TcpConn

COUNTERS = ("ici_d2d_copies_direct", "ici_d2d_copies_public")


def _moved(before):
    now = syscall_stats.snapshot()
    return tuple(now[k] - before[k] for k in COUNTERS)


def _wait(cond, timeout_s=5.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, f"{what} never held"
        time.sleep(0.002)


def _arr(fill, n=48, device=0):
    import jax
    import jax.numpy as jnp
    return jax.device_put(jnp.full((n,), fill, jnp.float32),
                          jax.devices()[device])


@pytest.fixture
def raw():
    """Two IciConns over one TCP connection, neither under a Socket; b
    receives on device 1 from a pool of its own."""
    lis = pysocket.socket()
    lis.bind(("127.0.0.1", 0))
    lis.listen(1)
    port = lis.getsockname()[1]
    sa = pysocket.create_connection(("127.0.0.1", port))
    sb, _ = lis.accept()
    lis.close()
    ep = str2endpoint(f"tcp://127.0.0.1:{port}")
    a = ici.IciConn(TcpConn(sa, ep, ep), ep, ep)
    b = ici.IciConn(TcpConn(sb, ep, ep), ep, ep, recv_device_ordinal=1,
                    pool=DeviceRecvPool(capacity_bytes=256 << 10))
    _wait(lambda: (a._pump(), b._pump(), a.peer_info and b.peer_info)[2],
          what="the hellos")
    yield a, b
    a.close()
    b.close()


def _send_and_take(a, b, arrays):
    a.write_device_payload(arrays)
    _wait(lambda: (b._pump(), len(b._lane) >= 1)[1], what="the lane entry")
    return b.take_device_payload()


def _same_as_device_put(got, src):
    import jax
    want = jax.device_put(src, jax.devices()[1])
    assert type(got) is type(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.sharding == want.sharding and got.committed
    assert got.devices() == want.devices() == {jax.devices()[1]}
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert (got + 1).devices() == {jax.devices()[1]}      # usable there


@pytest.fixture
def unresolved(monkeypatch):
    """``_d2d_put`` as a fresh process has it: nothing proved yet."""
    monkeypatch.setattr(ici, "_d2d_put_fn", False)


def test_this_jax_has_the_direct_copy_and_it_is_proved(raw, unresolved):
    """The alarm for a jax that moved the name or changed the call: the
    ring's rate rests on it (PERF.md section 6, PR 36)."""
    from jax._src.interpreters import pxla
    a, b = raw
    before = syscall_stats.snapshot()
    src = _arr(3.5)
    (got,) = _send_and_take(a, b, [src])
    assert ici._d2d_put_fn is pxla.batched_device_put
    assert _moved(before) == (1, 0)
    _same_as_device_put(got, src)


@pytest.mark.parametrize("direct", [True, False],
                         ids=["pxla", "jax.device_put"])
def test_the_copy_is_jax_device_puts(raw, direct, monkeypatch):
    """The same array either way (type, value, dtype, device, committed
    sharding), counted under the call that made it."""
    a, b = raw
    fast = ici._proved_d2d_put(*__import__("jax").devices()[:2])
    assert fast is not None
    used = []
    monkeypatch.setattr(ici, "_d2d_put_fn", (
        lambda *args: used.append(1) or fast(*args)) if direct else None)
    before = syscall_stats.snapshot()
    src = _arr(3.5)
    (got,) = _send_and_take(a, b, [src])
    assert bool(used) == direct
    assert _moved(before) == ((1, 0) if direct else (0, 1))
    _same_as_device_put(got, src)


def _raises(*args, **kwargs):
    raise TypeError("batched_device_put() takes 3 positional arguments")


def _lands_elsewhere(aval, sharding, xs, devices):
    import jax
    return jax.device_put(xs[0], jax.devices()[2])


def _uncommitted(aval, sharding, xs, devices):
    import jax.numpy as jnp
    return jnp.asarray(np.asarray(xs[0]))


def _other_numbers(aval, sharding, xs, devices):
    import jax
    return jax.device_put(xs[0] + 1, devices[0])


def _not_an_array(aval, sharding, xs, devices):
    return [np.asarray(xs[0])]


@pytest.mark.parametrize("private", [
    None, _raises, _lands_elsewhere, _uncommitted, _other_numbers,
    _not_an_array],
    ids=["name-gone", "signature-changed", "wrong-device", "uncommitted",
         "wrong-values", "another-type"])
def test_a_private_call_that_fails_its_proof_is_never_used(
        raw, unresolved, monkeypatch, private, caplog):
    """Whatever a later jax makes of ``pxla.batched_device_put``, the
    first copy finds out on a few numbers, says so once, and every take
    goes through ``jax.device_put``: no take raises, no connection
    fails, and the ``syscalls`` line shows which call copied."""
    def load():
        if private is None:
            raise ImportError("cannot import name 'batched_device_put'")
        return private
    monkeypatch.setattr(ici, "_private_batched_put", load)
    a, b = raw
    before = syscall_stats.snapshot()
    with caplog.at_level("WARNING", logger="brpc_tpu.transport"):
        for fill in (1.0, 2.0):
            src = _arr(fill)
            (got,) = _send_and_take(a, b, [src])
            _same_as_device_put(got, src)
    assert ici._d2d_put_fn is None
    assert _moved(before) == (0, 2)
    assert sum("batched_device_put" in r.getMessage()
               for r in caplog.records) == 1


def test_only_what_is_elsewhere_is_copied(raw):
    """An array already on the receiving device comes back as it is and
    counts as no copy; one spread over two devices is none of the
    direct call's and goes through the public one."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    a, b = raw
    here = _arr(1.0, device=1)
    there = _arr(2.0, device=0)
    spread = jax.device_put(
        np.arange(8, dtype=np.float32),
        NamedSharding(Mesh(np.array(jax.devices()[2:4]), ("x",)),
                      PartitionSpec("x")))
    before = syscall_stats.snapshot()
    got = _send_and_take(a, b, [here, there, spread])
    assert got[0] is here
    assert _moved(before) == (1, 1)
    for g, src in zip(got[1:], (there, spread)):
        assert g.devices() == {jax.devices()[1]}
        assert np.array_equal(np.asarray(g), np.asarray(src))


# ------------------------------------------------- the benchmark's cells
@pytest.mark.parametrize("cell, copies", [
    ("streaming_echo.ring_2mb_w8", True),
    ("parallel_allreduce.fanout_4mb_d1", True),
    ("tpu_performance.step_2mb_d8", False)])
def test_a_runs_syscalls_line_says_which_call_copied(cell, copies):
    """The real command in rehearsal (a child pinned to the CPU): the
    cells whose batches cross devices copy every one of them through the
    proved direct call and none through the public one, the one-chip
    cell copies nothing, and the run stays ``correct`` (frames once and
    in order, every ``/device`` cell balanced)."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3600000777", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    (sys_line,) = [ln["info"]["syscalls"] for ln in lines[:-1]
                   if "syscalls" in ln.get("info", {})]
    assert sys_line["ici_d2d_copies_public"] == 0
    assert (sys_line["ici_d2d_copies_direct"] > 0) == copies
