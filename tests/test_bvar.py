import threading

import pytest

from brpc_tpu.bvar import (
    Adder, IntRecorder, LatencyRecorder, Maxer, Miner, PassiveStatus,
    Percentile, PerSecond, Sampler, Status, Window,
    dump_exposed, dump_prometheus, unexpose_all,
)
from brpc_tpu.bvar.variable import dump_exposed_variables
# these expose at import, some only at their first use deep in a test:
# loaded here, the fixture's snapshot below holds their variables
import brpc_tpu.rpc.usercode  # noqa: F401
import brpc_tpu.transport.event_dispatcher  # noqa: F401


@pytest.fixture(autouse=True)
def clean_registry():
    # what the process had exposed before (at import, by an earlier
    # Server) comes back afterwards: later files of one xdist worker
    # assert on it (tests/test_longtail_dispatch.py)
    before = dump_exposed_variables()
    unexpose_all()
    yield
    unexpose_all()
    for name, var in before:
        var.expose(name)


class TestReducers:
    def test_adder_single_thread(self):
        a = Adder()
        a.add(5)
        a << 3
        assert a.get_value() == 8

    def test_adder_multi_thread(self):
        a = Adder()

        def worker():
            for _ in range(1000):
                a.add(1)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert a.get_value() == 8000

    def test_adder_keeps_dead_thread_counts(self):
        a = Adder()
        t = threading.Thread(target=lambda: a.add(42))
        t.start()
        t.join()
        import gc
        gc.collect()
        assert a.get_value() == 42

    def test_maxer_miner(self):
        m, n = Maxer(), Miner()
        for v in [3, 9, 1]:
            m.update(v)
            n.update(v)
        assert m.get_value() == 9
        assert n.get_value() == 1
        assert Maxer().get_value() is None

    def test_int_recorder(self):
        r = IntRecorder()
        r.record(10)
        r.record(20)
        assert r.average() == 15
        assert r.count == 2

    def test_reset(self):
        a = Adder()
        a.add(7)
        assert a.reset() == 7
        assert a.get_value() == 0

    def test_passive_and_status(self):
        p = PassiveStatus(lambda: 123)
        assert p.get_value() == 123
        s = Status("idle")
        s.set_value("busy")
        assert s.get_value() == "busy"


class TestPercentile:
    def test_percentiles(self):
        p = Percentile()
        for i in range(1, 101):
            p.add(i)
        assert 45 <= p.get_percentile(0.5) <= 55
        assert p.get_percentile(0.99) >= 95

    def test_multi_thread_merge(self):
        p = Percentile()

        def worker(base):
            for i in range(100):
                p.add(base + i)

        ts = [threading.Thread(target=worker, args=(k * 100,)) for k in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert len(p.merged_samples()) == 400


class TestWindow:
    def test_window_delta(self):
        sampler = Sampler()
        a = Adder()
        w = Window(a, window_size=10, sampler=sampler)
        a.add(100)
        sampler.take_sample(now=0.0)
        a.add(50)
        sampler.take_sample(now=1.0)
        assert w.get_value() == 50

    def test_per_second(self):
        sampler = Sampler()
        a = Adder()
        qps = PerSecond(a, window_size=10, sampler=sampler)
        sampler.take_sample(now=0.0)
        a.add(500)
        sampler.take_sample(now=2.0)
        assert qps.get_value() == pytest.approx(250.0)

    def test_window_over_maxer_uses_in_window_max(self):
        sampler = Sampler()
        m = Maxer()
        w = Window(m, window_size=2, sampler=sampler)
        m.update(100)           # before the window
        sampler.take_sample(now=0.0)
        m.update(50)
        sampler.take_sample(now=1.0)
        m.update(30)
        sampler.take_sample(now=2.0)
        # last 2 ticks saw maxima 50 and 30 → window max is 50, not 0
        assert w.get_value() == 50

    def test_adder_reset_is_exact_and_get_value_clears(self):
        a = Adder()
        a.add(7)
        assert a.reset() == 7
        assert a.get_value() == 0
        a.add(3)
        assert a.get_value() == 3
        assert a.reset() == 3

    def test_window_slides(self):
        sampler = Sampler()
        a = Adder()
        w = Window(a, window_size=2, sampler=sampler)
        for t in range(5):
            a.add(10)
            sampler.take_sample(now=float(t))
        # only last 2 seconds counted
        assert w.get_value() == 20


class TestLatencyRecorder:
    def test_composite(self):
        sampler = Sampler()
        lr = LatencyRecorder(sampler=sampler)
        for v in [100, 200, 300]:
            lr.record(v)
        assert lr.latency() == 200
        assert lr.max_latency() == 300
        assert lr.count() == 3
        assert lr.latency_percentile(0.99) >= 200


class TestRegistryAndDump:
    def test_expose_dump(self):
        a = Adder()
        a.add(3)
        a.expose("test_counter")
        assert ("test_counter", 3) in dump_exposed()

    def test_expose_replaces(self):
        a, b = Adder(), Adder()
        a.expose("dup")
        b.expose("dup")
        b.add(9)
        assert dump_exposed() == [("dup", 9)]
        assert a.name is None

    def test_prometheus_dump(self):
        a = Adder()
        a.add(5)
        a.expose("rpc server-count")
        sampler = Sampler()
        lr = LatencyRecorder(sampler=sampler)
        lr.record(10)
        lr.expose("echo_latency")
        text = dump_prometheus()
        assert "rpc_server_count 5" in text
        assert "echo_latency_count 1" in text
        assert "echo_latency_latency_avg_us 10" in text
