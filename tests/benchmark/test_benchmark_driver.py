"""The closed-loop driver against a fake deployment: who issues, what a
call's time is, and what is stamped as the generator's own."""

import threading
import time

import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.drivers import closed_loop
from benchmark.lib.stamps import Stamps


class Payload:
    """Stands for a device array that becomes ready ``after_s`` later."""

    def __init__(self, after_s: float = 0.0):
        self.at = time.monotonic() + after_s

    def is_ready(self) -> bool:
        return time.monotonic() >= self.at

    def block_until_ready(self) -> None:
        time.sleep(max(0.0, self.at - time.monotonic()))


class FakeDeployment:
    """Answers every call after ``reply_s`` on a thread of its own with
    a payload that is ready ``payload_s`` after that."""

    def __init__(self, reply_s=0.002, payload_s=0.0, fail_seq=None):
        self.first_seq = 0
        self.reply_s, self.payload_s = reply_s, payload_s
        self.fail_seq = fail_seq
        self.issuers, self.verified = set(), []

    def call(self, seq, done):
        self.issuers.add(threading.current_thread().name)
        threading.Timer(self.reply_s, lambda: done(
            Payload(self.payload_s))).start()

    def call_sync(self, seq):
        self.issuers.add(threading.current_thread().name)
        time.sleep(self.reply_s)
        return Payload(self.payload_s)

    def ready_now(self, cntl):
        return cntl.is_ready()

    def response_arrays(self, seq, cntl):
        if seq == self.fail_seq:
            raise RuntimeError("call failed: refused")
        return [cntl]

    def verify(self, seq, cntl, arrs):
        self.verified.append(seq)


def test_sync_callers_are_threads_of_their_own():
    dep, stamps = FakeDeployment(), Stamps(trace=False)
    win = closed_loop.run(dep, {"style": "sync", "callers": 5}, 0.2, stamps)
    assert len(dep.issuers) == 5 and len(win.thread_cpu_s) == 5
    assert win.attempted == len(stamps.calls) == len(dep.verified) > 5
    assert not stamps.handovers and not stamps.failures
    assert all(r - i >= 2e6 for _s, i, r in stamps.calls)


def test_callback_call_ends_at_done_when_the_payload_is_ready():
    dep, stamps = FakeDeployment(reply_s=0.005), Stamps(trace=False)
    slow = dep.verify
    dep.verify = lambda *a: (time.sleep(0.004), slow(*a))  # a busy generator
    closed_loop.run(dep, {"style": "callback", "depth": 4}, 0.3, stamps)
    done = {s: (d, p, ready) for s, d, p, ready in stamps.handovers}
    assert len(done) == len(stamps.calls) > 8
    assert all(ready for _d, _p, ready in done.values())
    # the call's end is the callback's stamp, not the pick-up after it
    assert all(r == done[s][0] for s, _i, r in stamps.calls)
    waits = sorted(p - d for d, p, _r in done.values())
    assert waits[len(waits) // 2] > 2e6     # and the wait is stamped apart


def test_callback_call_waits_for_a_payload_that_is_not_ready():
    dep = FakeDeployment(reply_s=0.002, payload_s=0.01)
    stamps = Stamps(trace=False)
    closed_loop.run(dep, {"style": "callback", "depth": 2}, 0.2, stamps)
    assert stamps.calls and not any(r for *_x, r in stamps.handovers)
    assert all(r - i >= 12e6 for _s, i, r in stamps.calls)


@pytest.mark.parametrize("traffic", [{"style": "sync", "callers": 2},
                                     {"style": "callback", "depth": 2}])
def test_a_failed_call_counts_and_has_no_latency(traffic):
    dep, stamps = FakeDeployment(fail_seq=3), Stamps(trace=False)
    win = closed_loop.run(dep, traffic, 0.1, stamps)
    assert [s for s, _why in stamps.failures] == [3]
    assert 3 not in [s for s, _i, _r in stamps.calls]
    assert win.attempted == len(stamps.calls) + 1
