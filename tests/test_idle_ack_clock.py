"""The lane's idle ACK on the event thread's clock (ISSUE 31).

An ``IciConn`` that consumed a batch owes its idle ACK to the
``EventDispatcher`` as a quiet duty: no entry on the timer thread, no
wake when the arm comes from the event thread, one wake at most when it
comes from another thread, and the same ACK, grant and bound as before.
Pinned here on raw ``IciConn`` pairs, an ``EventDispatcher`` of the
test's own and in-process ``ici://`` servers: counts, not times (the two
bounds the issue states are held with 50 ms of room).
"""

import contextlib
import os
import socket as pysocket
import sys
import threading
import time

import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.fiber.timer import global_timer
from brpc_tpu.transport import ici, syscall_stats
from brpc_tpu.transport.event_dispatcher import (EventDispatcher,
                                                 global_dispatcher)

from test_ici_read_cycle import _Pair, _raw_ici_pair
from test_ici_write_path import _payload, _record_tcp_writes, limit_10s
from test_postfork import _run_in_fork

ROOM_S = 0.05            # what the issue allows beyond ici_idle_ack_ms


@contextlib.contextmanager
def idle_ack_ms(value):
    old = flag("ici_idle_ack_ms")
    assert set_flag("ici_idle_ack_ms", value)
    try:
        yield value / 1000.0
    finally:
        set_flag("ici_idle_ack_ms", old)


def _wait(cond, timeout_s=3.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, f"{what} never held"
        time.sleep(0.001)


@pytest.fixture
def raw():
    """Two IciConns over one TCP connection, hellos exchanged, neither
    under a Socket: nobody reads either fd but the test."""
    a, b = _raw_ici_pair()
    _wait(lambda: (a._pump(), b._pump(), a.peer_info and b.peer_info)[2],
          what="the hellos")
    yield a, b
    a.close()
    b.close()


def _send(conn, n=1):
    for i in range(n):
        conn.write_device_payload([_payload(fill=i)])


def _take(conn, n=1):
    got = 0
    deadline = time.monotonic() + 3
    while got < n:
        conn._pump()
        if conn.take_device_payload() is not None:
            got += 1
        assert time.monotonic() < deadline, "no lane batch arrived"


def _counts():
    s = syscall_stats.snapshot()
    return {k: s[k] for k in ("ici_idle_ack_armed", "ici_idle_ack_carried",
                              "ici_idle_ack_sent", "dispatcher_quiet_wakes",
                              "dispatcher_ticks")}


def _moved(before):
    after = _counts()
    return {k: after[k] - before[k] for k in after}


@contextlib.contextmanager
def arm_log(d):
    """Every arm of ``d`` while the block runs: (the arming thread's
    ident, whether the arm wrote the wake pipe)."""
    log = []
    arming = threading.local()
    arm, wakeup = d.arm_quiet_duty, d._wakeup

    def logged_arm(key, deadline, callback):
        arming.kicked = False
        arm(key, deadline, callback)
        log.append((threading.get_ident(), arming.kicked))

    def logged_wakeup():
        arming.kicked = True
        wakeup()

    d.arm_quiet_duty, d._wakeup = logged_arm, logged_wakeup
    try:
        yield log
    finally:
        del d.arm_quiet_duty, d._wakeup


# ------------------------------------------------ arming costs no wake
@limit_10s
def test_takes_on_the_event_thread_arm_without_a_wake_or_a_timer(raw):
    """100 takes inside an fd callback: every one owes an ACK, none
    writes the wake pipe, and the timer thread's heap never hears."""
    a, b = raw
    d = global_dispatcher()
    taken = []

    def on_readable():                 # on the event thread
        b._pump()
        while b.take_device_payload() is not None:
            taken.append(threading.get_ident())

    timers = global_timer().pending()
    before = _counts()
    with idle_ack_ms(1.0), arm_log(d) as log:
        b.start_events(on_readable, lambda: None)
        for n in range(1, 101):
            _send(a)
            _wait(lambda: len(taken) == n, what="the take")
            # the ACK unarms the conn: every take arms anew
            _wait(lambda: (a._pump(), a.outstanding_batches)[1] == 0,
                  what="the idle ACK")
    assert set(taken) == {d._loop_ident}
    assert len(log) == 100
    assert [kicked for _tid, kicked in log] == [False] * 100
    assert {tid for tid, _ in log} == {d._loop_ident}
    assert global_timer().pending() == timers
    moved = _moved(before)
    assert moved["ici_idle_ack_armed"] == 100
    assert moved["ici_idle_ack_sent"] == 100 and \
        moved["ici_idle_ack_carried"] == 0


def test_the_lane_schedules_nothing_on_the_timer_for_the_ack(monkeypatch):
    """The whole life of an ici:// call, sync and done=: whatever the
    timer thread is asked to run, it is never the lane's ACK."""
    from brpc_tpu.fiber.timer import TimerThread

    asked = []
    schedule_at = TimerThread.schedule_at

    def logged(self, deadline, fn):
        asked.append(getattr(fn, "__qualname__", repr(fn)))
        return schedule_at(self, deadline, fn)

    monkeypatch.setattr(TimerThread, "schedule_at", logged)
    p = _Pair()
    try:
        before = _counts()
        for i in range(20):
            p.call(b"s%d" % i)
        calls = [p.call_async(b"a%d" % i, done=lambda c: None)
                 for i in range(20)]
        for c in calls:
            assert c.join(5) and not c.failed(), c.error_text
        assert _moved(before)["ici_idle_ack_armed"] >= 20
    finally:
        p.close()
    assert asked                                  # RPC deadlines still do
    assert not [name for name in asked if "idle_ack" in name
                or "IciConn" in name]


# ---------------------------------------------------- one duty a conn
@limit_10s
def test_concurrent_takes_arm_one_duty_and_send_one_ack(raw):
    a, b = raw
    d = global_dispatcher()
    n = 8                              # under half the window: no
    _send(a, n)                        # half-window ACK cuts in
    _wait(lambda: (b._pump(), len(b._lane))[1] == n, what="8 descriptors")
    before = _counts()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with idle_ack_ms(150.0):
            start = threading.Barrier(n)

            def take():
                start.wait(5)
                assert b.take_device_payload() is not None

            threads = [threading.Thread(target=take) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(5)
                assert not t.is_alive()
            assert _moved(before)["ici_idle_ack_armed"] == 1
            assert [k for k in d._duties if k is b] == [b]
            _wait(lambda: (a._pump(), a.outstanding_batches)[1] == 0,
                  what="the one ACK for all eight")
    finally:
        sys.setswitchinterval(old)
    moved = _moved(before)
    assert moved["ici_idle_ack_sent"] == 1 and \
        moved["ici_idle_ack_carried"] == 0
    assert b.lane_introspection()["idle_acks"] == 1
    assert b not in d._duties


# ------------------------------------------------ the bound, no close
@limit_10s
def test_a_quiescent_conn_acks_within_the_flag_and_50_ms(raw):
    a, b = raw
    ran = []
    due = b._idle_ack_due
    b._idle_ack_due = lambda: (ran.append(time.monotonic()), due())
    with idle_ack_ms(20.0) as delay:
        _send(a)
        _take(b)
        armed = time.monotonic()
        time.sleep(delay / 2)
        a._pump()
        assert a.outstanding_batches == 1 and not ran      # not early
        _wait(lambda: (a._pump(), a.outstanding_batches)[1] == 0,
              what="the idle ACK")
        seen = time.monotonic()
    assert len(ran) == 1
    assert delay * 0.9 <= ran[0] - armed <= delay + ROOM_S
    assert seen - armed <= delay + ROOM_S + 0.01
    assert not b._closed and not a._closed


@limit_10s
def test_done_calls_balance_every_device_cell_without_a_close():
    """done= calls: both sides take on the event thread. The last
    reply's batch is ACKed by the client's duty alone; nothing closes."""
    from benchmark.lib import counters

    p = _Pair()
    try:
        before = counters.snapshot()
        calls = [p.call_async(b"d%d" % i, done=lambda c: None)
                 for i in range(40)]
        for c in calls:
            assert c.join(5) and not c.failed(), c.error_text
        assert counters.settle_and_check(before, timeout_s=5) == []
        lane = counters.delta(before, counters.snapshot())["lane"]
        assert lane["transfers"] == 80
        for sock in (p.client_socket, p.server_socket):
            assert sock.conn.lane_introspection()["outstanding_batches"] == 0
            assert not sock.conn._closed
    finally:
        p.close()


# ----------------------------------------------------------- carried
@limit_10s
def test_a_reverse_frame_before_the_deadline_carries_the_ack(raw):
    a, b = raw
    with idle_ack_ms(60.0) as delay:
        _send(a)
        _take(b)
        before = _counts()
        writes = []
        _record_tcp_writes(b, writes)
        b.write(memoryview(b"reply bytes"))
        _wait(lambda: (a._pump(), a.outstanding_batches)[1] == 0,
              what="the piggybacked ACK")
        _wait(lambda: _moved(before)["ici_idle_ack_carried"] == 1,
              timeout_s=delay + 1, what="the duty")
    assert _moved(before)["ici_idle_ack_sent"] == 0
    assert b.lane_introspection()["idle_acks"] == 0
    assert [types for types, _at in writes] == [[ici.F_BYTES]]


# ------------------------------------- an arm from another thread
@limit_10s
def test_an_arm_from_another_thread_wakes_a_loop_asleep_for_half_a_second(
        raw):
    a, b = raw
    d = global_dispatcher()
    # the loop in its 0.5 s select with no deadline in it
    _wait(lambda: not d._duties and d._sleep_until == float("inf"),
          what="an idle dispatcher")
    _send(a)
    with idle_ack_ms(2.0) as delay, arm_log(d) as log:
        _take(b)                       # this thread is not the loop's
        armed = time.monotonic()
        _wait(lambda: (a._pump(), a.outstanding_batches)[1] == 0,
              what="the idle ACK")
        assert time.monotonic() - armed <= delay + ROOM_S
    assert log == [(threading.get_ident(), True)]


def test_an_arm_wakes_the_loop_only_past_its_sleep():
    """A loop already due to wake before the deadline is left asleep;
    one that would sleep past it gets one wake-pipe write."""
    d = EventDispatcher("quiet_duty_test")
    ran = []
    try:
        with arm_log(d) as log:
            d.arm_quiet_duty("near", time.monotonic() + 0.15,
                             lambda: ran.append("near"))
            _wait(lambda: 0.0 < d._sleep_until < float("inf"),
                  what="a sleep that ends at the near deadline")
            d.arm_quiet_duty("far", time.monotonic() + 0.3,
                             lambda: ran.append("far"))
            d.arm_quiet_duty("nearer", time.monotonic() + 0.05,
                             lambda: ran.append("nearer"))
            _wait(lambda: len(ran) == 3, what="the three duties")
        assert [kicked for _tid, kicked in log] == [False, False, True]
        assert ran == ["nearer", "near", "far"]
        assert not d._duties and d._duty_next == float("inf")
    finally:
        d.stop()


# ----------------------------------------------------- off, and drops
@limit_10s
def test_a_flag_of_zero_arms_nothing(raw):
    a, b = raw
    d = global_dispatcher()
    before = _counts()
    with idle_ack_ms(0.0):
        _send(a)
        _take(b)
        time.sleep(0.05)
        a._pump()
    assert a.outstanding_batches == 1             # until a frame or close
    assert _moved(before)["ici_idle_ack_armed"] == 0
    assert b not in d._duties and not b._idle_ack_armed


@limit_10s
def test_a_close_drops_the_duty(raw):
    a, b = raw
    d = global_dispatcher()
    before = _counts()
    with idle_ack_ms(100.0) as delay:
        _send(a)
        _take(b)
        assert b in d._duties
        b.close()
        assert b not in d._duties
        time.sleep(delay + 0.05)
    moved = _moved(before)
    assert moved["ici_idle_ack_armed"] == 1
    assert moved["ici_idle_ack_sent"] == 0 and \
        moved["ici_idle_ack_carried"] == 0


def test_a_fork_drops_every_duty_with_the_dispatcher():
    d = global_dispatcher()
    ran = []
    d.arm_quiet_duty("parent's", time.monotonic() + 0.2,
                     lambda: ran.append(os.getpid()))

    def check():
        child = global_dispatcher()
        if child is d:
            return "dispatcher inherited"
        if child._duties:
            return f"duties inherited: {list(child._duties)}"
        time.sleep(0.3)
        return "a parent's duty ran in the child" if ran else "OK"

    assert _run_in_fork(check) == "OK"
    _wait(lambda: ran, what="the duty in the parent")
    assert ran == [os.getpid()]


# ----------------------------------------------- what a tick counts
def test_quiet_wakes_are_not_ticks():
    d = EventDispatcher("quiet_duty_test")
    r, w = pysocket.socketpair()
    ran, read = [], []
    try:
        d.arm_quiet_duty("k", time.monotonic() + 0.02,
                         lambda: ran.append(1))
        _wait(lambda: ran, what="the duty")
        assert d._quiet_wakes == 1 and d._tick_seq == 0
        d.add_consumer(r.fileno(), lambda: read.append(r.recv(16)))
        w.send(b"x")
        _wait(lambda: read, what="the fd callback")
        assert d._tick_seq == 1 and d._quiet_wakes == 1
        # a duty that comes due while an fd callback holds the loop runs
        # at the end of that tick: no select() timeout is taken for it
        def busy():
            read.append(r.recv(16))
            d.arm_quiet_duty("k", time.monotonic() + 0.01,
                             lambda: ran.append(2))
            time.sleep(0.03)

        d.add_consumer(r.fileno(), busy)
        w.send(b"y")
        _wait(lambda: ran == [1, 2], what="the second duty")
        assert d._quiet_wakes == 1 and d._tick_seq == 2
    finally:
        d.remove_consumer(r.fileno())
        d.stop()
        r.close()
        w.close()


def test_a_duty_that_raises_stops_nothing():
    d = EventDispatcher("quiet_duty_test")
    ran = []
    try:
        d.arm_quiet_duty("bad", time.monotonic() + 0.01, lambda: 1 / 0)
        d.arm_quiet_duty("good", time.monotonic() + 0.02,
                         lambda: ran.append(1))
        _wait(lambda: ran, what="the duty after the one that raised")
    finally:
        d.stop()


def test_no_duty_is_lost_or_run_twice_under_many_arming_threads():
    """32 threads arm 100 keys each against a 10 us switch interval:
    every duty runs once, on the loop's thread, not before its
    deadline."""
    d = EventDispatcher("quiet_duty_test")
    ran = {}
    early = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def arm_many(t):
        for i in range(100):
            key = (t, i)
            deadline = time.monotonic() + 0.001 * (i % 5)

            def duty(key=key, deadline=deadline):
                if time.monotonic() < deadline:
                    early.append(key)
                ran[key] = ran.get(key, 0) + 1      # loop thread alone

            d.arm_quiet_duty(key, deadline, duty)

    try:
        threads = [threading.Thread(target=arm_many, args=(t,))
                   for t in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
            assert not t.is_alive()
        _wait(lambda: len(ran) == 3200, timeout_s=10,
              what="all 3,200 duties")
    finally:
        sys.setswitchinterval(old)
        d.stop()
    assert set(ran.values()) == {1} and not early
    assert not d._duties


# --------------------------------------------------------- the grant
@limit_10s
def test_a_slow_reply_still_brings_the_window_grant():
    """The benchmark's ``Hold``: a handler slower than the idle-ACK
    interval. The server's duty comes due while the worker sleeps, and
    its bare ACK is the only frame that carries the grant."""
    p = _Pair()
    try:
        conn = p.client_socket.conn
        window = conn.lane_introspection()["window"]
        assert flag("ici_adaptive_window")
        before = _counts()
        cntl = p.channel.call_sync("R", "Slow", b"hold",
                                   request_device_arrays=[_payload()])
        assert not cntl.failed(), cntl.error_text
        assert conn.lane_introspection()["peer_grant"] == 2 * window
        assert p.server_socket.conn.lane_introspection()["idle_acks"] >= 1
        assert _moved(before)["ici_idle_ack_sent"] >= 1
    finally:
        p.close()
