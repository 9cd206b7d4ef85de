"""CPU time by thread role (``butil/thread_cpu.py``) and the probe of
the wait for the interpreter (``butil/interp_probe.py``), ISSUE 29.

CPU only: counts, orders and existence, never a time. A role is said
where the fabric starts the thread; a thread that says nothing is the
application's (``caller``); a thread that ended keeps its CPU in its
role's total; the probe thread lives only while ``span.recording()``
says yes.
"""

import resource
import threading
import time

import pytest

from brpc_tpu.butil import interp_probe, thread_cpu
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.bvar.variable import dump_exposed
from brpc_tpu.rpc import span as span_mod
from brpc_tpu.transport import syscall_stats

ROLE_KEYS = {f"cpu_us_{r}" for r in thread_cpu.ROLES}
PROBE_KEYS = {"interp_probe_n", "interp_probe_wait_us",
              "interp_probe_over_1ms", "interp_probe_over_4ms"}


def _burn(ms=20):
    end = time.thread_time() + ms / 1e3
    x = 0
    while time.thread_time() < end:
        x += 1
    return x


def _role_of(thread):
    return thread_cpu._roles.get(thread.ident, "caller")


def _thread_named(prefix):
    return [t for t in threading.enumerate() if t.name.startswith(prefix)]


def _wait_for(cond, deadline_s=5.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


# ------------------------------------------------------------- roles

def _start_dispatcher():
    from brpc_tpu.transport.event_dispatcher import EventDispatcher
    d = EventDispatcher(name="role_test_dispatcher")
    d._ensure_thread()
    return d._thread, d.stop


def _start_worker():
    from brpc_tpu.fiber.scheduler import TaskControl
    tc = TaskControl(concurrency=1, name="role_test_tc")
    tc.start()
    return tc._threads[0], tc.stop_and_join


def _start_timer():
    from brpc_tpu.fiber.timer import TimerThread
    tt = TimerThread(name="role_test_timer")
    fired = threading.Event()
    tt.schedule_after(0.0, fired.set)
    assert fired.wait(5)
    return tt._thread, tt.stop


class _Slow:
    """Something with ``block_until_ready`` that is not ready yet: the
    poller parks a waiter thread in it, as it does for a jax.Array."""

    def __init__(self):
        self.go = threading.Event()

    def is_ready(self):
        return False

    def block_until_ready(self):
        self.go.wait(10)
        _burn(20)


def _start_waiter():
    from brpc_tpu.fiber.device_poller import DeviceEventPoller
    obj = _Slow()
    DeviceEventPoller(name="role_test_poller").watch(obj, lambda: None)
    assert _wait_for(lambda: _thread_named("role_test_poller_wait"))
    return _thread_named("role_test_poller_wait")[0], obj.go.set


@pytest.mark.parametrize("start, role", [
    (_start_dispatcher, "dispatcher"),
    (_start_worker, "worker"),
    (_start_timer, "timer"),
    (_start_waiter, "device_wait"),
])
def test_a_fabric_thread_says_its_role_where_it_starts(start, role):
    thread, stop = start()
    try:
        assert _wait_for(lambda: _role_of(thread) == role), _role_of(thread)
    finally:
        stop()
    thread.join(5)
    assert not thread.is_alive()
    # an ended thread leaves no entry behind for a reused ident
    assert thread.ident not in thread_cpu._roles


def test_a_bare_thread_is_the_applications():
    seen = {}
    go, done = threading.Event(), threading.Event()

    def app():
        _burn(30)
        done.set()
        go.wait(10)
    t = threading.Thread(target=app, name="role_test_app")
    before = thread_cpu.by_role()
    t.start()
    try:
        assert done.wait(10)
        assert _role_of(t) == "caller"
        seen = thread_cpu.by_role()
    finally:
        go.set()
        t.join(5)
    assert seen["caller"] - before["caller"] >= 25_000
    for role in set(thread_cpu.ROLES) - {"caller"}:
        # nothing of it went anywhere else (other roles may have run
        # meanwhile, but not 25 ms of them in a quiet test process)
        assert seen[role] - before[role] < 25_000, role


def test_the_host_lets_a_thread_read_anothers_clock():
    assert thread_cpu.source() in ("cpuclock", "proc")


# ---------------------------------------------------------- snapshot

def test_snapshot_keys_sum_and_bound():
    _burn(10)
    snap = syscall_stats.snapshot()
    ru = resource.getrusage(resource.RUSAGE_SELF)
    assert ROLE_KEYS | {"cpu_us_python"} | PROBE_KEYS <= set(snap)
    assert snap["cpu_us_python"] == sum(snap[k] for k in ROLE_KEYS)
    # Python's threads are some of the process's: never more CPU than it
    # (read after them, so it can only have grown; one tick of slack for
    # a host that accounts the two clocks apart)
    assert snap["cpu_us_python"] <= (ru.ru_utime + ru.ru_stime) * 1e6 + 20_000
    assert all(snap[k] >= 0 for k in ROLE_KEYS)
    later = syscall_stats.snapshot()
    assert all(later[k] >= snap[k] for k in ROLE_KEYS | {"cpu_us_python"})


def test_a_waiter_threads_cpu_outlives_it():
    before = thread_cpu.by_role()["device_wait"]
    thread, release = _start_waiter()
    release()
    thread.join(10)
    assert not thread.is_alive()
    assert thread not in threading.enumerate()
    # its 20 ms of work inside the wait, read after the thread is gone
    assert thread_cpu.by_role()["device_wait"] - before >= 15_000


def test_an_application_thread_that_used_the_fabric_and_ended_is_counted():
    """The benchmark's callers end before the window's second reading:
    a thread's first bvar write leaves the watch that keeps its CPU."""
    counter = Adder()
    before = thread_cpu.by_role()["caller"]

    def app():
        counter.add(1)
        _burn(30)
    t = threading.Thread(target=app, name="role_test_gone")
    t.start()
    t.join(10)
    assert thread_cpu.by_role()["caller"] - before >= 25_000


def test_vars_carry_the_roles_and_the_probe():
    syscall_stats.expose_syscall_vars()
    names = {name for name, _ in dump_exposed()}
    assert {f"thread_cpu_us_{r}" for r in thread_cpu.ROLES} <= names
    assert PROBE_KEYS <= names


# -------------------------------------------------------------- probe

def _probe_threads():
    return _thread_named("interp_probe")


def _probe_gone():
    return _wait_for(lambda: not _probe_threads()
                     and not interp_probe.running())


@pytest.fixture
def quiet():
    saved = flag("rpcz_enabled")
    set_flag("rpcz_enabled", False)
    assert not span_mod.recording()
    assert _probe_gone()
    yield
    set_flag("rpcz_enabled", saved)
    if not saved:
        assert _probe_gone()


def _fake_profile(monkeypatch):
    """A jax profiler whose session this test sets and clears."""
    import sys
    import types
    state = types.SimpleNamespace(profile_session=None)

    class Annotation:
        def __init__(self, *a, **kw):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False
    fake = types.SimpleNamespace(_profile_state=state,
                                 TraceAnnotation=Annotation)
    monkeypatch.setitem(sys.modules, span_mod._PROFILER_MODULE, fake)
    monkeypatch.setattr(span_mod, "_profile_state", None)
    monkeypatch.setattr(span_mod, "_clock_session", None)
    return state


def _turn_on_flag(monkeypatch):
    set_flag("rpcz_enabled", True)
    return lambda: set_flag("rpcz_enabled", False)


def _turn_on_profile(monkeypatch):
    state = _fake_profile(monkeypatch)
    state.profile_session = object()
    return lambda: setattr(state, "profile_session", None)


@pytest.mark.parametrize("turn_on", [_turn_on_flag, _turn_on_profile],
                         ids=["flag", "profile"])
def test_the_probe_lives_only_while_spans_record(turn_on, quiet,
                                                 monkeypatch):
    still = interp_probe.snapshot()
    time.sleep(0.05)
    assert not _probe_threads()
    assert interp_probe.snapshot() == still     # adders stand still
    turn_off = turn_on(monkeypatch)
    try:
        assert not _probe_threads()     # nobody has asked yet
        assert span_mod.recording()     # the first yes starts it
        assert _wait_for(lambda: len(_probe_threads()) == 1)
        probe = _probe_threads()[0]
        assert probe.daemon
        assert _wait_for(lambda: _role_of(probe) == "probe")
        assert _wait_for(lambda: interp_probe.snapshot()["interp_probe_n"]
                         >= still["interp_probe_n"] + 5)
        span_mod.recording()
        assert len(_probe_threads()) == 1       # one, however often asked
        snap = interp_probe.snapshot()
        assert snap["interp_probe_over_4ms"] <= snap["interp_probe_over_1ms"] \
            <= snap["interp_probe_n"]
        assert snap["interp_probe_wait_us"] >= 0
    finally:
        turn_off()
    # nobody asks recording() now: the probe's own question ends it
    assert _probe_gone()
    after = interp_probe.snapshot()
    time.sleep(0.05)
    assert interp_probe.snapshot() == after
    assert not span_mod.recording() and not _probe_threads()
    # and its CPU stays under its role
    assert thread_cpu.by_role()["probe"] > 0
