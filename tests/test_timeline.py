"""The telemetry time machine (ISSUE 13): multi-resolution trend rings
over exposed bvars (bvar/series.py), the anomaly watchdog
(bvar/anomaly.py), the /timeline surfaces and the supervisor merge.

Tick discipline: tests drive ``series_sample_tick(wall_t=...)`` by
hand (the window-test pattern) — the bucket stamps are pinned, so the
math assertions are exact, never sleep-shaped."""

import json
import os

import pytest

from brpc_tpu.bvar import (Adder, LatencyRecorder, Maxer, PassiveStatus,
                           unexpose_all)
from brpc_tpu.bvar.variable import dump_exposed_variables
# these expose at import, some only at their first use deep in a test:
# loaded here, the fixture's snapshot below holds their variables
import brpc_tpu.rpc.usercode  # noqa: F401
import brpc_tpu.transport.event_dispatcher  # noqa: F401
from brpc_tpu.bvar.anomaly import AnomalyWatchdog, global_watchdog
from brpc_tpu.bvar.series import (SEC_BUCKETS, SeriesCollector,
                                  global_series, merge_timeline_states,
                                  series_sample_tick, sparkline)


@pytest.fixture(autouse=True)
def _fresh_series(monkeypatch):
    """Every test starts with an empty ring registry and watchdog and
    leaves nothing of its own exposed behind (the unexpose_all
    discipline); what the process had exposed before (at import, by an
    earlier Server) comes back afterwards, because later files of one
    xdist worker assert on it (tests/test_longtail_dispatch.py). The
    GLOBAL sampler thread (alive in a full-suite process from earlier
    server tests) is unhooked from the series engine for the test's
    duration — a real-clock tick landing between a manual wall_t tick
    and its assert would consume deltas and shred exact-sequence
    expectations. Manual series_sample_tick calls are unaffected."""
    from brpc_tpu.bvar import window as _window
    monkeypatch.setattr(_window, "series_sample_tick",
                        lambda *a, **k: None)
    before = dump_exposed_variables()
    unexpose_all()
    global_series().reset()
    global_watchdog().reset()
    yield
    unexpose_all()
    global_series().reset()
    global_watchdog().reset()
    for name, var in before:
        var.expose(name)


def _ticks(n, start=1000):
    for i in range(n):
        series_sample_tick(wall_t=start + i)


class TestKindSemantics:
    def test_adder_delta_buckets(self):
        a = Adder()
        a.expose("tl_adder")
        series_sample_tick(wall_t=100)         # baseline bucket: 0
        a.add(5)
        series_sample_tick(wall_t=101)
        a.add(2)
        a.add(1)
        series_sample_tick(wall_t=102)
        ser = global_series().dump_series(names=["tl_adder"])["tl_adder"]
        assert ser["kind"] == "delta"
        assert ser["sec"] == [[100, 0], [101, 5], [102, 3]]

    def test_gauge_last_and_maxer_max(self):
        vals = [3.0]
        PassiveStatus(lambda: vals[0]).expose("tl_gauge")
        m = Maxer()
        m.update(7)
        m.expose("tl_maxer")
        series_sample_tick(wall_t=100)
        vals[0] = 9.0
        m.update(2)                            # cumulative max stays 7
        series_sample_tick(wall_t=101)
        d = global_series().dump_series()
        assert d["tl_gauge"]["kind"] == "last"
        assert d["tl_gauge"]["sec"] == [[100, 3.0], [101, 9.0]]
        assert d["tl_maxer"]["kind"] == "max"
        assert [v for _, v in d["tl_maxer"]["sec"]] == [7, 7]

    def test_quantile_kind_latency_recorder(self):
        lr = LatencyRecorder()
        lr.expose("tl_lat")
        series_sample_tick(wall_t=100)
        for us in (100, 200, 300, 10_000):
            lr.record(us)
        series_sample_tick(wall_t=101)
        ser = global_series().dump_series()["tl_lat"]
        assert ser["kind"] == "quantile"
        t, b = ser["sec"][-1]
        assert t == 101 and b["count"] == 4
        assert b["max"] == 10_000 and b["p99"] >= 300
        # count deltas partition the recorder's total
        assert sum(x["count"] for _, x in ser["sec"]) == 4

    def test_miner_keeps_minima(self):
        from brpc_tpu.bvar import Miner
        m = Miner()
        m.update(50)
        m.expose("tl_miner")
        series_sample_tick(wall_t=100)
        m.update(3)                            # the floor reading
        series_sample_tick(wall_t=101)
        ser = global_series().dump_series()["tl_miner"]
        assert ser["kind"] == "min"
        assert [v for _, v in ser["sec"]] == [50, 3]

    def test_non_numeric_values_are_skipped(self):
        PassiveStatus(lambda: {"not": "numeric"}).expose("tl_dict")
        PassiveStatus(lambda: "up").expose("tl_str")
        _ticks(2)
        d = global_series().dump_series()
        assert "tl_dict" not in d and "tl_str" not in d


class TestCascade:
    def test_cascade_rollover_math(self):
        a = Adder()
        a.expose("tl_casc")
        m = Maxer()
        m.expose("tl_casc_max")
        for i in range(SEC_BUCKETS + 1):
            a.add(2)                           # 2 per tick
            m.reset()                          # fresh per-tick maxima
            m.update(i)
            series_sample_tick(wall_t=5000 + i)
        d = global_series().dump_series()
        ser = d["tl_casc"]
        # one minute bucket rolled: the sum of its 60 second-deltas.
        # The first tick is the baseline (delta 0), so the minute holds
        # 59 x 2 = 118; the 61st tick stays live in the seconds ring
        # (the seconds deque is a sliding WINDOW — it still shows
        # buckets the minute absorbed; live_sec says how many are new)
        assert len(ser["min"]) == 1
        assert ser["min"][0][1] == 118
        assert ser["live_sec"] == 1
        assert ser["min"][0][1] + sum(
            v for _, v in ser["sec"][-ser["live_sec"]:]) == 120
        # max-kind minute bucket keeps the max of its seconds
        assert d["tl_casc_max"]["min"][0][1] == SEC_BUCKETS - 1

    def test_bucket_vs_counter_exact_under_burst(self):
        import random
        rng = random.Random(13)
        a = Adder()
        a.expose("tl_burst")
        series_sample_tick(wall_t=7000)        # baseline
        total = 0
        for i in range(150):                   # crosses two cascades
            n = rng.randrange(0, 9)
            a.add(n)
            total += n
            series_sample_tick(wall_t=7001 + i)
        ser = global_series().dump_series()["tl_burst"]
        # rolled minutes + the not-yet-cascaded live seconds partition
        # the counter growth EXACTLY (151 pushes = 2 rolled minutes +
        # 31 live seconds)
        live = ser["live_sec"]
        tail = sum(v for _, v in ser["sec"][-live:]) if live else 0
        assert sum(v for _, v in ser["min"]) + tail == total
        assert len(ser["min"]) == 2 and live == 31


class TestLifecycle:
    def test_series_off_produces_nothing(self, monkeypatch):
        monkeypatch.setenv("BRPC_TPU_BVAR_SERIES", "0")
        a = Adder()
        a.expose("tl_off")
        _ticks(3)
        assert global_series().dump_series() == {}
        from brpc_tpu.builtin.services import timeline_page_payload
        payload = timeline_page_payload()
        assert payload["enabled"] is False and payload["series"] == {}

    def test_unexpose_all_and_reexpose_survival(self):
        a = Adder()
        a.add(10)
        a.expose("tl_surv")
        series_sample_tick(wall_t=100)
        a.add(4)
        series_sample_tick(wall_t=101)
        unexpose_all()
        _ticks(2, start=102)                   # frozen, not dropped
        b = Adder()                            # the Server.start shape:
        b.add(500)                             # a NEW object, same name
        b.expose("tl_surv")
        series_sample_tick(wall_t=104)         # re-baseline: no 500-spike
        b.add(3)
        series_sample_tick(wall_t=105)
        ser = global_series().dump_series()["tl_surv"]
        assert ser["sec"] == [[100, 0], [101, 4], [104, 0], [105, 3]]

    def test_postfork_child_fresh_parent_untouched(self):
        a = Adder()
        a.expose("tl_fork")
        series_sample_tick(wall_t=100)
        a.add(6)
        series_sample_tick(wall_t=101)

        r, w = os.pipe()
        pid = os.fork()
        if pid == 0:                           # child
            try:
                fresh = global_series().dump_series()
                a.add(1)
                series_sample_tick(wall_t=102)
                after = global_series().dump_series()
                msg = json.dumps({
                    "fresh_empty": fresh == {},
                    "rebuilt": "tl_fork" in after and
                    after["tl_fork"]["sec"][0][1] == 0})
            except BaseException as e:  # noqa: BLE001
                msg = json.dumps({"exc": f"{type(e).__name__}: {e}"})
            try:
                os.write(w, msg.encode())
            finally:
                os._exit(0)
        os.close(w)
        chunks = []
        while True:
            c = os.read(r, 4096)
            if not c:
                break
            chunks.append(c)
        os.close(r)
        os.waitpid(pid, 0)
        rep = json.loads(b"".join(chunks))
        assert rep == {"fresh_empty": True, "rebuilt": True}, rep
        # parent rings untouched by the child's tick
        ser = global_series().dump_series()["tl_fork"]
        assert ser["sec"] == [[100, 0], [101, 6]]


class TestMerge:
    def _state(self, series):
        return {"enabled": True, "series": series, "incidents": [],
                "watch_keys": []}

    def test_merged_counters_sum_per_bucket(self):
        s0 = {"c": {"kind": "delta", "sec": [[10, 3], [11, 5]],
                    "min": [], "hr": []}}
        s1 = {"c": {"kind": "delta", "sec": [[10, 4], [12, 1]],
                    "min": [], "hr": []}}
        m = merge_timeline_states([(0, self._state(s0)),
                                   (1, self._state(s1))])
        assert m["series"]["c"]["sec"] == [[10, 7], [11, 5], [12, 1]]
        assert m["shards_reporting"] == 2

    def test_merged_p99_is_max_not_average(self):
        # the averaged-p99-would-be-wrong case: one slow shard's spike
        # must survive the merge at full height
        s0 = {"lat": {"kind": "quantile",
                      "sec": [[10, {"count": 90, "p50": 100.0,
                                    "p99": 200.0, "max": 250.0}]],
                      "min": [], "hr": []}}
        s1 = {"lat": {"kind": "quantile",
                      "sec": [[10, {"count": 10, "p50": 4000.0,
                                    "p99": 9000.0, "max": 9500.0}]],
                      "min": [], "hr": []}}
        m = merge_timeline_states([(0, self._state(s0)),
                                   (1, self._state(s1))])
        b = m["series"]["lat"]["sec"][0][1]
        assert b["count"] == 100
        assert b["p99"] == 9000.0              # max of the shards,
        avg = (200.0 * 90 + 9000.0 * 10) / 100  # NOT the count-weighted
        assert b["p99"] != pytest.approx(avg)   # average (~1080)
        assert b["max"] == 9500.0

    def test_merged_gauges_use_var_merge_rules(self):
        # gauges go through shard_group.merge_var_values with the NAME,
        # so merged /vars and merged_timeline agree by construction:
        # limits max, ratios mean, plain gauges sum
        from brpc_tpu.rpc.shard_group import merge_var_values
        for name, vals, want in (
                ("server_concurrency_limit", [128, 64], 128),
                ("iobuf_pool_hit_ratio", [0.9, 0.5], 0.7),
                ("socket_wqueue_bytes", [100, 50], 150)):
            s0 = {name: {"kind": "last", "sec": [[10, vals[0]]],
                         "min": [], "hr": []}}
            s1 = {name: {"kind": "last", "sec": [[10, vals[1]]],
                         "min": [], "hr": []}}
            m = merge_timeline_states([(0, self._state(s0)),
                                       (1, self._state(s1))])
            got = m["series"][name]["sec"][0][1]
            assert got == want, (name, got)
            assert got == merge_var_values(vals, name=name)

    def test_merged_minutes_align_on_the_epoch_grid(self):
        # shards roll minutes at their OWN 60th push: bucket stamps
        # differ by a few seconds across shards and must still SUM
        s0 = {"c": {"kind": "delta", "sec": [],
                    "min": [[117, 40]], "hr": []}}
        s1 = {"c": {"kind": "delta", "sec": [],
                    "min": [[172, 25]], "hr": []}}
        m = merge_timeline_states([(0, self._state(s0)),
                                   (1, self._state(s1))])
        # 117 -> grid 60, 172 -> grid 120: distinct minutes stay
        # distinct; same-grid minutes sum
        assert m["series"]["c"]["min"] == [[60, 40], [120, 25]]
        s1b = {"c": {"kind": "delta", "sec": [],
                     "min": [[119, 25]], "hr": []}}
        m2 = merge_timeline_states([(0, self._state(s0)),
                                    (1, self._state(s1b))])
        assert m2["series"]["c"]["min"] == [[60, 65]]

    def test_merged_incidents_carry_shard_tags(self):
        st = self._state({})
        st["incidents"] = [{"id": 1, "opened_t": 50, "keys": ["x"],
                            "state": "open"}]
        m = merge_timeline_states([(0, self._state({})), (1, st)])
        assert m["incidents"] == [{"id": 1, "opened_t": 50,
                                   "keys": ["x"], "state": "open",
                                   "shard": 1}]


class TestWatchdog:
    def _feed(self, wd, key, values, start=100):
        for i, v in enumerate(values):
            wd.watchdog_pass({key: float(v)}, start + i)

    def test_incident_open_close_determinism(self):
        from brpc_tpu.butil.flags import flag, set_flag
        saved = flag("anomaly_close_ticks")
        set_flag("anomaly_close_ticks", "3")
        try:
            script = [0, 0, 0, 0, 0, 0, 50, 60, 0, 0, 0, 0, 0]
            runs = []
            for _ in range(2):
                wd = AnomalyWatchdog()
                self._feed(wd, "errors_x", script)
                runs.append(wd.incident_snapshot())
            assert runs[0] == runs[1]          # pure function of input
            assert len(runs[0]) == 1
            inc = runs[0][0]
            assert inc["keys"] == ["errors_x"]
            assert inc["state"] == "closed"
            assert inc["opened_t"] == 106      # the 50-spike's tick
            # the 60 rides the freshly-raised baseline (z < z_close):
            # it counts as calm, so 3 calm ticks close at t=109
            assert inc["closed_t"] == 109
            assert inc["peak_value"] == 50.0
        finally:
            set_flag("anomaly_close_ticks", str(saved))

    def test_warmup_suppresses_first_readings(self):
        wd = AnomalyWatchdog()
        # a huge first reading is a baseline, not an anomaly
        self._feed(wd, "errors_y", [10_000, 10_000, 10_000])
        assert wd.incident_snapshot() == []

    def test_coalesces_keys_into_one_incident(self):
        wd = AnomalyWatchdog()
        for i in range(6):
            wd.watchdog_pass({"errors_a": 0.0, "b_shed": 0.0}, 100 + i)
        wd.watchdog_pass({"errors_a": 40.0, "b_shed": 0.0}, 106)
        wd.watchdog_pass({"errors_a": 45.0, "b_shed": 80.0}, 107)
        incs = wd.incident_snapshot()
        assert len(incs) == 1
        assert sorted(incs[0]["keys"]) == ["b_shed", "errors_a"]

    def test_incident_annotates_spans_and_flight_window(self):
        import time as _time

        from brpc_tpu.builtin import flight_recorder as fr
        from brpc_tpu.bvar import anomaly
        from brpc_tpu.butil.flags import flag, set_flag
        from brpc_tpu.rpc import span as sm
        anomaly.bind_watchdog_imports()
        saved = flag("rpcz_enabled")
        set_flag("rpcz_enabled", "true")
        rec = fr.global_recorder()
        rec.clear()
        rec._cur = fr._Window(_time.monotonic())   # live profile window
        try:
            now_us = _time.monotonic_ns() // 1000
            span = sm.Span(trace_id=1, span_id=2, side="server",
                           service="S", method="M",
                           start_us=now_us - 1000, end_us=now_us)
            sm.global_collector.submit(span)
            wd = AnomalyWatchdog()
            self._feed(wd, "errors_z", [0, 0, 0, 0, 0, 0, 99])
            incs = wd.incident_snapshot()
            assert len(incs) == 1 and incs[0]["spans_annotated"] >= 1
            texts = [t for _, t in span.annotations]
            assert any("incident #" in t and "errors_z" in t
                       for t in texts), texts
            labels = rec.merged()["labels"]
            assert any(k.startswith("incident:") and "errors_z" in k
                       for k in labels), dict(labels)
        finally:
            set_flag("rpcz_enabled", str(saved))
            sm.global_collector.clear()
            rec.clear()

    def test_watch_filter_silences_quantile_p99_tracks(self):
        # a pinned anomaly_watch_filter must silence the derived .p99
        # tracks too, or the smokes' exactly-one-incident determinism
        # is a lie; unfiltered, the .p99 track IS watched
        from brpc_tpu.butil.flags import set_flag
        from brpc_tpu.bvar.anomaly import is_watch_key
        assert is_watch_key("some_latency.p99")
        set_flag("anomaly_watch_filter", "errors_only")
        try:
            assert not is_watch_key("some_latency.p99")
            assert is_watch_key("errors_only")
            lr = LatencyRecorder()
            lr.expose("tl_filtered_lat")
            lr.record(100)
            series_sample_tick(wall_t=100)
            assert "tl_filtered_lat.p99" not in \
                global_watchdog().tracked_keys()
        finally:
            set_flag("anomaly_watch_filter", "")
        lr2 = LatencyRecorder()
        lr2.expose("tl_open_lat")
        lr2.record(100)
        series_sample_tick(wall_t=101)
        assert "tl_open_lat.p99" in global_watchdog().tracked_keys()

    def test_rpcz_off_annotates_nothing(self):
        from brpc_tpu.bvar import anomaly
        from brpc_tpu.rpc import span as sm
        anomaly.bind_watchdog_imports()
        sm.global_collector.clear()
        wd = AnomalyWatchdog()
        self._feed(wd, "errors_q", [0, 0, 0, 0, 0, 0, 77])
        incs = wd.incident_snapshot()
        # rpcz off: the collector ring is empty (submit is gated), so
        # the incident records zero annotated spans — and still exists
        assert len(incs) == 1
        assert incs[0]["spans_annotated"] == 0


class TestSurfaces:
    def test_sparkline_bounds(self):
        assert sparkline([]) == ""
        assert sparkline(["x", None]) == ""
        assert sparkline([5]) == "▁"
        assert sparkline([2, 2, 2]) == "▁▁▁"      # constant: floor
        s = sparkline([0, 4, 8])
        assert s[0] == "▁" and s[-1] == "█"
        assert sparkline([-10, 0, 10])[-1] == "█"  # negatives ok
        assert len(sparkline(list(range(100)), width=30)) == 30

    def test_vars_series_param_and_timeline_http(self):
        from tools.spawn_util import http_get_local

        from brpc_tpu.rpc import Server, ServerOptions
        server = Server(ServerOptions(enable_builtin_services=True))
        ep = server.start("tcp://127.0.0.1:0")
        try:
            _ticks(2)
            st, body = http_get_local(ep.port, "/timeline")
            assert st == 200
            page = json.loads(body)
            assert page["enabled"] is True
            assert "server_processed" in page["series"]
            assert set(page) >= {"series", "incidents", "watch_keys",
                                 "resolution"}
            st, body = http_get_local(
                ep.port, "/vars?series=server_processed")
            assert st == 200
            assert json.loads(body)["server_processed"]["kind"] == "delta"
            st, _ = http_get_local(ep.port, "/vars?series=tl_nope")
            assert st == 400
            st, _ = http_get_local(ep.port, "/timeline?name=tl_nope")
            assert st == 400
            # prefix narrows without erroring on absences
            st, body = http_get_local(ep.port, "/timeline?prefix=server_")
            assert st == 200
            assert all(k.startswith("server_")
                       for k in json.loads(body)["series"])
            # the saturation pane links live spikes to their history
            st, body = http_get_local(ep.port, "/status")
            links = json.loads(body).get("saturation_timeline", {})
            assert links.get("deadline_shed", "").startswith(
                "/timeline?name=")
        finally:
            server.stop()
            server.join(2)

    def test_vars_page_carries_inline_sparklines(self):
        from tools.spawn_util import http_get_local

        from brpc_tpu.rpc import Server, ServerOptions
        server = Server(ServerOptions(enable_builtin_services=True))
        ep = server.start("tcp://127.0.0.1:0")
        try:
            _ticks(3)
            st, body = http_get_local(ep.port,
                                      "/vars?prefix=server_processed")
            assert st == 200
            line = body.decode().strip().splitlines()[0]
            assert line.startswith("server_processed : ")
            assert any(ch in line for ch in "▁▂▃▄▅▆▇█"), line
        finally:
            server.stop()
            server.join(2)

    def test_cluster_top_json_timeline_block(self):
        import importlib
        sys_path_tools = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools")
        import sys
        if sys_path_tools not in sys.path:
            sys.path.insert(0, sys_path_tools)
        cluster_top = importlib.import_module("cluster_top")

        from brpc_tpu.rpc import Server, ServerOptions
        server = Server(ServerOptions(enable_builtin_services=True))
        ep = server.start("tcp://127.0.0.1:0")
        try:
            _ticks(3)
            view = cluster_top.scrape([f"127.0.0.1:{ep.port}"])
            node = f"127.0.0.1:{ep.port}"
            assert view["nodes_up"] == 1
            tl = view["timeline"].get(node)
            assert tl is not None and "qps" in tl, view["timeline"]
            assert isinstance(tl["qps"], list) and len(tl["qps"]) >= 2
            # the render path draws the spark columns without raising
            text = cluster_top.render(view)
            assert "qps " in text
        finally:
            server.stop()
            server.join(2)
