"""Flight recorder (ISSUE 6): continuous fiber-aware profiling, the
per-connection resource census, the event-loop stall watchdog, and the
non-blocking on-demand /hotspots — driven through a real tcp:// server
with a raw HTTP client (the operator's view)."""

import json
import os
import socket as pysocket
import threading
import time

import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions, Service


def http_get(ep, path):
    s = pysocket.create_connection((ep.host, ep.port), timeout=10)
    s.sendall(f"GET {path} HTTP/1.1\r\nHost: x\r\n"
              f"Content-Length: 0\r\n\r\n".encode())
    data = b""
    s.settimeout(10)
    while b"\r\n\r\n" not in data:
        chunk = s.recv(65536)
        if not chunk:
            break
        data += chunk
    head, _, rest = data.partition(b"\r\n\r\n")
    status = int(head.split(b" ")[1])
    clen = 0
    for h in head.split(b"\r\n")[1:]:
        if h.lower().startswith(b"content-length"):
            clen = int(h.split(b":")[1])
    while len(rest) < clen:
        chunk = s.recv(65536)
        if not chunk:
            break
        rest += chunk
    s.close()
    return status, rest


@pytest.fixture()
def flags_guard():
    # the flags are defined at flight_recorder/contention import time
    import brpc_tpu.builtin.flight_recorder  # noqa: F401
    import brpc_tpu.fiber.contention  # noqa: F401
    keep = {n: flag(n) for n in
            ("continuous_profiler_hz", "continuous_profiler_window_s",
             "continuous_profiler_windows", "dispatcher_stall_ms",
             "census_idle_s", "rpcz_enabled",
             "contention_samples_per_second")}
    yield
    for n, v in keep.items():
        set_flag(n, str(v))


@pytest.fixture()
def server(flags_guard):
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("Bench")

    @svc.method()
    def PyEcho(cntl, request):
        return bytes(request)

    @svc.method()
    async def InlineHold(cntl, request):
        # DELIBERATELY bad user code: an async handler that blocks
        # synchronously, so with inline processing it monopolizes the
        # event thread, which is what the watchdog must catch. Held
        # until the watchdog has counted a stall beyond the count the
        # request names (on a loaded host its thread may wait long for
        # its turn, and only a LIVE tick can be flagged: waiting after
        # the call is too late) and a little beyond, for the annotation
        # that follows the count; at most the request's seconds
        from brpc_tpu.transport.event_dispatcher import nstalls
        limit_s, before = bytes(request).split()
        t_end = time.monotonic() + float(limit_s)
        while time.monotonic() < t_end and \
                nstalls.get_value() <= int(before):
            time.sleep(0.01)
        time.sleep(0.3)
        return b"done"

    server.add_service(svc)
    ep = server.start("tcp://127.0.0.1:0")
    yield server, ep
    server.stop()
    server.join(2)


class TestContinuousProfiler:
    def test_capture_and_attribution(self, server):
        from brpc_tpu.builtin.flight_recorder import global_recorder
        srv, ep = server
        rec = global_recorder()
        assert rec.running()      # Server.start brought it up
        rec.clear()
        set_flag("continuous_profiler_hz", "100")
        ch = Channel(f"tcp://{ep.host}:{ep.port}",
                     ChannelOptions(timeout_ms=5000))
        t_end = time.monotonic() + 1.5
        n = 0
        while time.monotonic() < t_end:
            c = ch.call_sync("Bench", "PyEcho", b"x" * 64)
            assert not c.failed(), c.error_text
            n += 1
        ch.close()
        m = rec.merged()
        assert m["nsamples"] > 20
        assert m["nbusy"] > 0
        # the serving work must attribute to the method (classic path:
        # serving-controller fiber-local; turbo path: fiber name;
        # transport legs: the conn's last_method hint)
        assert any(k == "rpc:Bench.PyEcho" for k in m["labels"]), \
            dict(m["labels"])

    def test_http_continuous_page_and_formats(self, server):
        srv, ep = server
        st, body = http_get(ep, "/hotspots?mode=continuous")
        assert st == 200
        assert b"continuous profile" in body
        assert b"dispatcher_stall_ms_max_10s" in body
        st, body = http_get(ep, "/hotspots?mode=continuous&format=json")
        assert st == 200
        prof = json.loads(body)
        assert {"nsamples", "nbusy", "labels", "folded"} <= set(prof)
        st, body = http_get(ep, "/hotspots?mode=continuous&format=svg")
        assert st == 200
        assert body.startswith(b"<svg")

    def test_window_roll_and_diff(self, server):
        from brpc_tpu.builtin.flight_recorder import global_recorder
        srv, ep = server
        rec = global_recorder()
        rec.clear()
        set_flag("continuous_profiler_hz", "200")
        set_flag("continuous_profiler_window_s", "1")
        try:
            # burn CPU so windows hold busy samples while they roll
            stop = [False]

            def spin():
                while not stop[0]:
                    sum(i * i for i in range(500))

            t = threading.Thread(target=spin, daemon=True)
            t.start()
            # window_diff needs two COMPLETED windows (the in-progress
            # one is excluded); windows() = completed + current
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and len(rec.windows()) < 3:
                time.sleep(0.1)
            stop[0] = True
            t.join(2)
            assert len(rec.windows()) >= 3
            d = rec.window_diff()
            assert d["ok"], d
            st, body = http_get(ep, "/hotspots?mode=continuous&diff=1")
            assert st == 200
            assert b"window diff" in body
        finally:
            set_flag("continuous_profiler_window_s", "10")

    def test_merge_dump_states(self):
        from brpc_tpu.builtin.flight_recorder import merge_dump_states
        a = {"nsamples": 100, "nbusy": 40, "windows": 3, "span_s": 30.0,
             "stall_ms_max_10s": 5.0,
             "folded": {"rpc:S.M;f1;f2": 30, "thread:x;f3": 10},
             "labels": {"rpc:S.M": 30, "thread:x": 10}}
        b = {"nsamples": 50, "nbusy": 20, "windows": 2, "span_s": 20.0,
             "stall_ms_max_10s": 9.0,
             "folded": {"rpc:S.M;f1;f2": 15, "rpc:S.N;f4": 5},
             "labels": {"rpc:S.M": 15, "rpc:S.N": 5}}
        m = merge_dump_states([a, b])
        assert m["nsamples"] == 150 and m["nbusy"] == 60
        assert m["folded"]["rpc:S.M;f1;f2"] == 45      # counters SUM
        assert m["stall_ms_max_10s"] == 9.0            # maxima MAX
        assert m["labels"]["rpc:S.M"] == 45
        assert m["shards_reporting"] == 2

    def test_aggregator_merged_hotspots(self, tmp_path):
        from brpc_tpu.rpc.shard_group import ShardAggregator
        for i, n in enumerate((7, 11)):
            (tmp_path / f"shard-{i}.json").write_text(json.dumps({
                "shard": i, "pid": 1000 + i, "seq": 1, "time": 0,
                "vars": {}, "status": {}, "latency_samples": {},
                "hotspots": {"nsamples": n, "nbusy": n, "windows": 1,
                             "span_s": 10.0, "stall_ms_max_10s": float(i),
                             "folded": {"rpc:B.E;f": n},
                             "labels": {"rpc:B.E": n}}}))
        agg = ShardAggregator(str(tmp_path), 2)
        m = agg.merged_hotspots()
        assert m["nsamples"] == 18
        assert m["folded"]["rpc:B.E;f"] == 18
        assert m["stall_ms_max_10s"] == 1.0

    def test_aggregator_merged_census(self, tmp_path):
        from brpc_tpu.rpc.shard_group import ShardAggregator
        for i, (b, c) in enumerate(((100, 3), (50, 2))):
            (tmp_path / f"shard-{i}.json").write_text(json.dumps({
                "shard": i, "pid": 2000 + i, "seq": 1, "time": 0,
                "vars": {}, "status": {}, "latency_samples": {},
                "census": {
                    "subsystems": {
                        "sockets": {"bytes": b, "count": c,
                                    "server_bytes": b, "server_count": c},
                        "fds": {"count": 10 + i}},
                    "total_bytes": b,
                    "connections": {"count": c, "resident_bytes": b,
                                    "idle": 0}}}))
        agg = ShardAggregator(str(tmp_path), 2)
        m = agg.merged_census()
        assert m["shards_reporting"] == 2
        assert m["total_bytes"] == 150
        assert m["subsystems"]["sockets"]["bytes"] == 150
        assert m["subsystems"]["sockets"]["count"] == 5
        assert m["subsystems"]["fds"]["count"] == 21
        assert m["connections"]["count"] == 5


class TestOnDemandHotspots:
    def test_profile_runs_on_sampler_thread_and_503_when_busy(self, server):
        srv, ep = server
        results = {}

        def get(key, path):
            results[key] = http_get(ep, path)

        t1 = threading.Thread(
            target=get, args=("a", "/hotspots?seconds=1.2"))
        t1.start()
        time.sleep(0.45)   # job admitted (parked loop wakes <=0.25s)
        st2, body2 = http_get(ep, "/hotspots?seconds=1.2")
        t1.join(10)
        st1, body1 = results["a"]
        assert st1 == 200
        # the concurrent profile is REFUSED, not queued, not a 500
        assert st2 == 503, (st2, body2)
        assert b"already running" in body2

    def test_worker_not_blocked_during_profile(self, server):
        srv, ep = server
        done = threading.Event()
        results = {}

        def profile():
            results["p"] = http_get(ep, "/hotspots?seconds=1.5")
            done.set()

        t = threading.Thread(target=profile)
        t.start()
        time.sleep(0.4)
        # the handler fiber is PARKED on the sampler's completion —
        # RPCs keep flowing while the profile runs
        ch = Channel(f"tcp://{ep.host}:{ep.port}",
                     ChannelOptions(timeout_ms=3000))
        t0 = time.monotonic()
        c = ch.call_sync("Bench", "PyEcho", b"during-profile")
        dt = time.monotonic() - t0
        ch.close()
        assert not c.failed(), c.error_text
        assert dt < 1.0, f"RPC stalled {dt}s behind the profile window"
        assert done.wait(10)
        assert results["p"][0] == 200


class TestStallWatchdog:
    def test_inline_handler_stall_flagged_and_annotated(self, server):
        from brpc_tpu.rpc.span import global_collector
        srv, ep = server
        set_flag("rpcz_enabled", "true")
        set_flag("dispatcher_stall_ms", "40")
        set_flag("continuous_profiler_hz", "100")
        try:
            from brpc_tpu.transport.event_dispatcher import (
                nstalls, stall_ms_max_10s)
            before = nstalls.get_value()
            ch = Channel(f"tcp://{ep.host}:{ep.port}",
                         ChannelOptions(timeout_ms=30000))
            c = ch.call_sync("Bench", "InlineHold", b"20 %d" % before)
            ch.close()
            assert not c.failed(), c.error_text
            assert nstalls.get_value() > before
            assert stall_ms_max_10s() >= 40.0
            # the server's span is submitted when its response write
            # completes, which may be after the client has its reply
            deadline = time.monotonic() + 10.0
            while True:
                spans = [s for s in global_collector.recent(50)
                         if s.method == "InlineHold" and s.side == "server"]
                if spans or time.monotonic() >= deadline:
                    break
                time.sleep(0.05)
            assert spans, "InlineHold span missing from rpcz"
            notes = [t for s in spans for _, t in s.annotations]
            assert any("dispatcher_stall" in t for t in notes), notes
        finally:
            set_flag("rpcz_enabled", "false")


class TestCensus:
    def test_census_page_and_connection_rows(self, server):
        srv, ep = server
        # a conn with queued-parse state: keep one extra idle conn open
        idle = pysocket.create_connection((ep.host, ep.port), timeout=5)
        try:
            time.sleep(0.1)
            st, body = http_get(ep, "/census")
            assert st == 200
            census = json.loads(body)
            assert "sockets" in census["subsystems"]
            assert "iobuf_pool" in census["subsystems"]
            assert "fds" in census["subsystems"]
            assert census["subsystems"]["fds"]["count"] > 0
            assert "total_bytes" in census
            assert census["connections"]["count"] >= 1
            st, body = http_get(ep, "/connections")
            assert st == 200
            rows = json.loads(body)["connections"]
            assert rows
            for r in rows:
                assert {"resident_bytes", "last_active_s",
                        "idle_class"} <= set(r)
                assert r["idle_class"] in ("idle", "active")
        finally:
            idle.close()

    def test_census_totals_equal_connection_rows(self, server):
        srv, ep = server
        idle = [pysocket.create_connection((ep.host, ep.port), timeout=5)
                for _ in range(5)]
        try:
            time.sleep(0.2)
            ok = False
            for _ in range(4):
                _, cbody = http_get(ep, "/census")
                _, nbody = http_get(ep, "/connections")
                sub = json.loads(cbody)["subsystems"]["sockets"]
                rows = json.loads(nbody)["connections"]
                # server-scoped totals == this server's rows (the
                # process-wide bytes/count additionally cover client
                # channel sockets, which /connections never lists)
                if sub["server_bytes"] == sum(r["resident_bytes"]
                                              for r in rows) \
                        and sub["server_count"] == len(rows):
                    ok = True
                    break
                time.sleep(0.2)
            assert ok, (sub, len(rows))
        finally:
            for s in idle:
                s.close()

    def test_idle_classification_and_bvars(self, server):
        from brpc_tpu.transport.socket import (conn_resident_bytes_avg,
                                               idle_conn_count)
        srv, ep = server
        set_flag("census_idle_s", "0.3")
        idle = pysocket.create_connection((ep.host, ep.port), timeout=5)
        try:
            time.sleep(0.6)
            assert idle_conn_count() >= 1
            assert conn_resident_bytes_avg() >= 0.0
            st, body = http_get(ep, "/connections")
            rows = json.loads(body)["connections"]
            assert any(r["idle_class"] == "idle" for r in rows), rows
        finally:
            idle.close()

    def test_registry_snapshot_quarantines_failing_provider(self):
        from brpc_tpu.butil import resource_census as rc
        rc.register("_test_boom", lambda: 1 / 0)
        try:
            snap = rc.snapshot()
            assert "error" in snap["_test_boom"]
            assert "iobuf_pool" in snap     # the rest still rendered
        finally:
            with rc._lock:
                rc._providers[:] = [(n, f) for n, f in rc._providers
                                    if n != "_test_boom"]

    def test_total_bytes_rolls_up_byte_keys(self):
        from brpc_tpu.butil.resource_census import total_bytes
        c = {"a": {"bytes": 10, "count": 1},
             "b": {"buf_bytes": 5, "other": 99},
             "c": {"error": "x"}}
        assert total_bytes(c) == 15


class TestContentionProfiler:
    def test_contended_fiber_mutex_shows_hot_site(self, server):
        from brpc_tpu import fiber
        from brpc_tpu.fiber.contention import (contention_report,
                                               global_contention_collector)
        from brpc_tpu.fiber.sync import FiberMutex
        srv, ep = server
        global_contention_collector.drain()     # isolate this test
        m = FiberMutex()

        async def holder():
            await m.lock()
            await fiber.sleep(0.12)
            m.unlock()

        async def contender():
            await m.lock()          # <- the hot acquisition site
            m.unlock()

        h = fiber.spawn(holder)
        time.sleep(0.03)            # holder owns the mutex first
        cs = [fiber.spawn(contender) for _ in range(4)]
        h.join(5)
        for c in cs:
            c.join(5)
        rows = contention_report()
        assert rows, "no contention samples recorded"
        # the caller frame is contender's lock() await site
        assert any("contender" in site for site, _, _ in rows), rows
        # ... end to end on the builtin page
        st, body = http_get(ep, "/contentions")
        assert st == 200
        assert b"contender" in body

    def test_sampling_budget_respected(self, flags_guard):
        from brpc_tpu.fiber.contention import (global_contention_collector,
                                               record_contention)
        set_flag("contention_samples_per_second", "3")
        global_contention_collector.drain()
        sampled0 = global_contention_collector.nsampled.get_value()

        class _M:
            pass

        for _ in range(100):
            record_contention(_M(), 5.0)
        admitted = global_contention_collector.nsampled.get_value() \
            - sampled0
        # one second's budget (3) + at most one window rollover (3)
        assert admitted <= 6, admitted


class TestPostfork:
    def test_forked_child_restarts_sampler_and_resets_state(self, server):
        from brpc_tpu.builtin.flight_recorder import global_recorder
        from test_postfork import _run_in_fork
        srv, ep = server
        rec = global_recorder()
        assert rec.running()
        rec.merged()      # parent has a live recorder with state

        def check():
            from brpc_tpu.builtin import flight_recorder as fr
            from brpc_tpu.fiber.contention import \
                global_contention_collector
            child_rec = fr.global_recorder()
            if child_rec is rec:
                return "EXC:recorder not dropped by postfork reset"
            if child_rec.running():
                return "EXC:child sampler running before ensure_running"
            if child_rec.merged()["nsamples"] != 0:
                return "EXC:child inherited parent windows"
            child_rec.ensure_running()
            if not child_rec.running():
                return "EXC:child sampler did not start"
            if global_contention_collector.snapshot():
                return "EXC:contention collector not reset"
            from brpc_tpu.butil.resource_census import snapshot
            if "iobuf_pool" not in snapshot():
                return "EXC:census registry lost providers"
            return "OK"

        assert _run_in_fork(check) == "OK"
        # the parent's recorder is untouched
        assert rec.running()

    def test_recorder_registered_in_postfork_registry(self):
        import brpc_tpu.builtin.flight_recorder  # noqa: F401
        from brpc_tpu.butil import postfork, resource_census  # noqa: F401
        names = postfork.registered_names()
        assert "builtin.flight_recorder" in names
        assert "butil.resource_census" in names
        assert "fiber.contention" in names
