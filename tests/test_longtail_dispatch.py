"""A handler that holds its worker (ISSUE 32): short calls must not wait
for it, the server span says when a request got its worker, and the
always-on counters say how long handlers hold workers. Through
``Server`` and ``Channel`` on tcp://, eight connections."""

import threading
import time

import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.fiber.scheduler import TaskControl
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions, Service
from brpc_tpu.rpc.span import global_collector
from brpc_tpu.transport import syscall_stats

CONNECTIONS = 8
WAIT_S = 30.0


class Fabric:
    def __init__(self):
        # more workers than connections: eight may be held at once
        self.control = TaskControl(concurrency=CONNECTIONS + 4,
                                   name="longtail")
        self.release = threading.Event()
        self.started = threading.Semaphore(0)
        self.slow: list = []        # (start_ns, end_ns) a SlowStep
        self.echo: list = []        # start_ns an Echo
        svc = Service("LongTail")
        svc.register_method("SlowStep", self._slow_step)
        svc.register_method("Echo", self._echo)
        svc.register_method("Sleep5", self._sleep5)
        self.server = Server(ServerOptions(enable_builtin_services=False),
                             control=self.control)
        self.server.add_service(svc)
        ep = self.server.start("tcp://127.0.0.1:0")
        opts = ChannelOptions(timeout_ms=WAIT_S * 1000, max_retry=0,
                              share_connections=False)
        self.channels = [Channel(f"tcp://{ep.host}:{ep.port}", opts)
                         for _ in range(CONNECTIONS)]

    def _slow_step(self, cntl, request):
        t0 = time.monotonic_ns()
        self.started.release()
        self.release.wait(WAIT_S)
        self.slow.append((t0, time.monotonic_ns()))
        return bytes(request)

    def _echo(self, cntl, request):
        self.echo.append(time.monotonic_ns())
        return bytes(request)

    def _sleep5(self, cntl, request):
        time.sleep(0.005)
        return bytes(request)

    def close(self):
        self.release.set()
        for ch in self.channels:
            ch.close()
        self.server.stop()
        self.server.join(5)
        self.control.stop_and_join()


@pytest.fixture()
def fabric():
    keep = flag("rpcz_enabled")
    f = Fabric()
    yield f
    f.close()
    set_flag("rpcz_enabled", str(keep))


def test_a_short_call_starts_while_every_connection_holds_a_worker(fabric):
    done = threading.Semaphore(0)
    held = []
    for ch in fabric.channels:
        held.append(ch.call("LongTail", "SlowStep", b"hold",
                            done=lambda _c: done.release()))
    for _ in range(CONNECTIONS):
        assert fabric.started.acquire(timeout=WAIT_S), \
            "a SlowStep never reached its handler"
    # every connection's last request now sits in a held handler; the
    # short call goes in behind one of them and must come back while
    # they are all still held (no time limit but the call's own)
    cntl = fabric.channels[3].call_sync("LongTail", "Echo", b"short")
    assert not cntl.failed(), cntl.error_text
    assert not fabric.slow, "a hold ended before the short call returned"
    fabric.release.set()
    for _ in range(CONNECTIONS):
        assert done.acquire(timeout=WAIT_S)
    assert not any(c.failed() for c in held)
    assert len(fabric.slow) == CONNECTIONS
    # order of stamps: the short handler STARTED before the first hold ended
    assert fabric.echo[0] < min(end for _s, end in fabric.slow)


def test_worker_us_lies_between_received_and_handler_start(fabric):
    set_flag("rpcz_enabled", "true")
    global_collector.clear()
    for i in range(6):
        cntl = fabric.channels[i].call_sync("LongTail", "Echo", b"x")
        assert not cntl.failed(), cntl.error_text
    deadline = time.monotonic() + 10.0
    while True:     # a server span is submitted when its write completes
        spans = [s for s in global_collector.recent(100)
                 if s.side == "server" and s.method == "Echo"]
        if len(spans) == 6 or time.monotonic() >= deadline:
            break
        time.sleep(0.02)
    assert len(spans) == 6
    for s in spans:
        # a sync handler never runs on the event thread: it got a worker
        assert s.worker_us, "a sync handler's span has no worker_us"
        assert s.received_us <= s.worker_us <= s.handler_start_us
        assert s.to_dict()["worker_us"] == s.worker_us


def test_the_counters_move_where_the_handler_returns(fabric):
    before = syscall_stats.snapshot()
    for i in range(4):
        assert not fabric.channels[i].call_sync(
            "LongTail", "Echo", b"x").failed()
    assert not fabric.channels[0].call_sync(
        "LongTail", "Sleep5", b"x").failed()
    after = syscall_stats.snapshot()
    assert after["usercode_runs"] - before["usercode_runs"] == 5
    assert after["usercode_over_1ms"] - before["usercode_over_1ms"] == 1
    assert after["usercode_held_us"] - before["usercode_held_us"] >= 5000
    assert after["fiber_workers"] >= 8      # the process-wide pool
    assert after["fiber_steals"] >= before["fiber_steals"]
    assert after["dispatcher_stalls"] >= before["dispatcher_stalls"]
    from brpc_tpu.bvar.variable import describe_exposed
    for name in ("usercode_held_us", "usercode_runs", "usercode_over_1ms",
                 "dispatcher_stalls"):
        assert describe_exposed(name) is not None, name
