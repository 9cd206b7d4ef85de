"""The open-loop driver against a fake deployment: who issues, what a
call's time is, and that a late generator drops nothing."""

import threading
import time
import types

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.drivers import open_loop
from benchmark.lib.stamps import Stamps
from benchmark.reference.longtail import schedule


class Payload:
    def is_ready(self):
        return True


class FakeDeployment:
    """Answers every call after ``reply_s`` on a thread of its own; call
    number ``stall_at`` of a window blocks the generator ``stall_s``."""

    def __init__(self, reply_s=0.001, stall_at=None, stall_s=0.0,
                 fail_seq=None):
        self.first_seq = 100
        self.ctx = types.SimpleNamespace(
            cell=types.SimpleNamespace(rehearse=False))
        self.reply_s, self.fail_seq = reply_s, fail_seq
        self.stall_at, self.stall_s = stall_at, stall_s
        self.issued, self.issuers, self.verified = [], set(), []

    def plan(self, rate, seconds):
        self.arrivals = schedule(5, rate, seconds, 8, 0.05, 3)
        return self.arrivals

    def call(self, seq, done):
        self.issued.append(seq)
        self.issuers.add(threading.current_thread().name)
        if seq - self.first_seq == self.stall_at:
            time.sleep(self.stall_s)    # the generator's thread stalls
        threading.Timer(self.reply_s, lambda: done(Payload())).start()

    def ready_now(self, cntl):
        return cntl.is_ready()

    def response_arrays(self, seq, cntl):
        if seq == self.fail_seq:
            raise RuntimeError("call failed: refused")
        return [cntl]

    def verify(self, seq, cntl, arrs):
        self.verified.append(seq)


def test_the_driver_issues_the_plan_and_times_from_the_scheduled_arrival():
    dep, stamps = FakeDeployment(), Stamps(trace=False)
    win = open_loop.run(dep, {"rate_calls_per_s": 400}, 0.5, stamps)
    plan = dep.arrivals
    assert win.attempted == len(plan) > 100
    assert dep.issued == [100 + i for i in range(len(plan))]
    assert dep.issuers == {"bench-generator"}
    assert sorted(dep.verified) == dep.issued and not stamps.failures
    # the samples are the short calls alone, from the scheduled arrival
    shorts = {100 + i: a for i, a in enumerate(plan) if not a.long}
    assert sorted(s for s, _i, _r in stamps.calls) == sorted(shorts)
    for seq, sched, ready in stamps.calls:
        assert sched == win.start_ns + int(shorts[seq].at_s * 1e9)
        assert ready - sched >= dep.reply_s * 1e9


def test_a_generator_made_late_drops_no_arrival():
    # the generator stalls 150 ms at its 20th call: the arrivals of that
    # time are issued at once afterwards, each exactly once, and their
    # time still runs from when they were scheduled
    dep = FakeDeployment(stall_at=20, stall_s=0.15)
    stamps = Stamps(trace=False)
    win = open_loop.run(dep, {"rate_calls_per_s": 400}, 0.5, stamps)
    plan = dep.arrivals
    assert dep.issued == [100 + i for i in range(len(plan))]
    assert win.attempted == len(plan) and not stamps.failures
    t_stall = plan[20].at_s
    late = [(s, i, r) for s, i, r in stamps.calls
            if t_stall + 0.01 < plan[s - 100].at_s < t_stall + 0.10]
    assert len(late) > 10
    for seq, sched, ready in late:
        assert ready - sched >= (t_stall + 0.15
                                 - plan[seq - 100].at_s) * 1e9


def test_a_failed_call_counts_and_has_no_latency():
    dep, stamps = FakeDeployment(fail_seq=103), Stamps(trace=False)
    win = open_loop.run(dep, {"rate_calls_per_s": 400}, 0.2, stamps)
    assert [s for s, _why in stamps.failures] == [103]
    assert 103 not in [s for s, _i, _r in stamps.calls]
    assert win.attempted == len(dep.arrivals)
