"""Device observatory tests (ISSUE 12): stage-resolved device spans,
per-(peer, lane) telemetry cells, the /device page (HTTP + builtin twin
+ supervisor merge), export formats, fork hygiene, and flight-recorder
attribution of device threads.

The measurement contract under test: a device transfer's stage stamps
(stage/wire/ack) must SUM to its latency, cells must balance
(transfers == completed + failed) even under a flap storm, and device
work sampled outside any fiber must attribute to ``device:*`` instead
of a thread-name leaf.
"""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from brpc_tpu.butil.device_pool import DeviceRecvPool
from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions
from brpc_tpu.rpc.service import Service
from brpc_tpu.rpc.span import Span, global_collector
from brpc_tpu.transport import device_stats as ds
from brpc_tpu.transport import ici

_seq = iter(range(100000))


def _make_server(addr: str, builtin: bool = False):
    server = Server(ServerOptions(enable_builtin_services=builtin))
    svc = Service("DevSvc")

    @svc.method()
    def EchoDevice(cntl, request):
        cntl.response_device_arrays = [a
                                       for a in cntl.request_device_arrays]
        return b"dev"

    server.add_service(svc)
    ep = server.start(addr)
    return server, ep


def _device_send_spans(n: int = 400):
    return [s for s in global_collector.recent(n)
            if s.side == "device" and (s.write_done_us
                                       or s.first_byte_us)]


def _device_recv_spans(n: int = 400):
    return [s for s in global_collector.recent(n)
            if s.side == "device" and not (s.write_done_us
                                           or s.first_byte_us)]


@pytest.fixture
def rpcz_on():
    old = flag("rpcz_enabled")
    set_flag("rpcz_enabled", True)
    global_collector.clear()
    yield
    set_flag("rpcz_enabled", old)


# ------------------------------------------------------------ stage spans

class TestStageSpans:
    def test_stage_spans_sum_to_latency_and_inherit_trace(self, rpcz_on):
        import jax.numpy as jnp
        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        ch = Channel(f"ici://127.0.0.1:{ep.port}")
        try:
            arr = jnp.ones((1024,), jnp.float32)
            for _ in range(4):
                cntl = ch.call_sync("DevSvc", "EchoDevice", b"",
                                    request_device_arrays=[arr])
                assert not cntl.failed(), cntl.error_text
            sends = _device_send_spans()
            # request legs ack on the response frame: >= 4 settled
            assert len(sends) >= 4, len(sends)
            parents = {f"{s.span_id:016x}"
                       for s in global_collector.recent(400)
                       if s.side in ("client", "server")}
            for s in sends:
                d = s.to_dict()
                total = d["stage_us"] + d["wire_us"] + d["ack_us"]
                # the stamps ARE the latency decomposition: the three
                # stages must account for >= 90% of the span's wall
                # (rounding costs a few µs)
                assert total >= 0.9 * d["latency_us"], d
                assert d["parent_span_id"] != f"{0:016x}", \
                    "device span lost its owning RPC span"
                assert d["method"] in ("local-d2d", "pjrt-pull",
                                       "staged"), d["method"]
            # at least one device span hangs off a live RPC span in
            # the same collector (trace inheritance end to end)
            assert any(s.to_dict()["parent_span_id"] in parents
                       for s in sends)
            recvs = _device_recv_spans()
            assert recvs, "no device-recv child spans"
            assert recvs[0].service == "device-recv"
            assert all(s.service == "device" for s in sends)
        finally:
            ch.close()
            server.stop()
            server.join(2)

    def test_no_device_spans_when_rpcz_off(self):
        import jax.numpy as jnp
        assert not flag("rpcz_enabled")
        global_collector.clear()
        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        ch = Channel(f"ici://127.0.0.1:{ep.port}")
        try:
            arr = jnp.ones((64,), jnp.float32)
            assert not ch.call_sync("DevSvc", "EchoDevice", b"",
                                    request_device_arrays=[arr]).failed()
            assert global_collector.recent(50) == []
        finally:
            ch.close()
            server.stop()
            server.join(2)


# ------------------------------------------------- conn-level harness

class _Harness:
    """Raw ici transport pair with manual pumping (test_ici idiom)."""

    def __init__(self, window=4, pool=None):
        self.tr = ici.IciTransport(window=window, pool=pool)
        self.server_conn = None
        self._evt = threading.Event()
        self.listener = self.tr.listen(
            str2endpoint("ici://127.0.0.1:0"), self._on_conn)
        self.client = self.tr.connect(
            str2endpoint(f"ici://127.0.0.1:{self.listener.endpoint.port}"))
        assert self._evt.wait(5), "no server conn"
        deadline = time.monotonic() + 5
        while (self.client.peer_info is None
               or self.server_conn.peer_info is None):
            self.pump(self.client)
            self.pump(self.server_conn)
            assert time.monotonic() < deadline
            time.sleep(0.01)

    def _on_conn(self, conn):
        self.server_conn = conn
        self._evt.set()

    @staticmethod
    def pump(conn):
        buf = bytearray(1 << 16)
        try:
            conn.read_into(memoryview(buf))
        except (BlockingIOError, ConnectionError):
            pass

    def close(self):
        self.client.close()
        if self.server_conn is not None:
            self.server_conn.close()
        self.listener.stop()


def _tracker(peer="test-peer", lane="test-lane", nbytes=4096,
             with_span=True):
    parent = Span(trace_id=7, span_id=9) if with_span else None
    return ds.open_transfer(peer, lane, nbytes, parent_span=parent)


class TestTrackerEvents:
    def test_staged_fallback_annotates_span_and_cell(self):
        import jax.numpy as jnp
        h = _Harness()
        try:
            # make the client see a cross-process peer with no pull
            # support: the next lane batch takes the staged fallback
            h.client.peer_info = dict(h.client.peer_info,
                                      proc="elsewhere", can_pull=False)
            t = _tracker(peer=f"sf-{next(_seq)}")
            assert t is not None
            h.client.write_device_payload(
                [jnp.zeros((16,), jnp.float32)], tracker=t)
            assert t.staged
            cell = t.cell.get_value()
            assert cell["staged_fallbacks"] == 1
            assert any("staged_fallback" in txt
                       for _, txt in t.span.annotations)
        finally:
            h.close()

    def test_unsendable_batch_fails_tracker(self):
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=16 << 10)
        h = _Harness(pool=pool)
        try:
            t = _tracker(peer=f"us-{next(_seq)}")
            with pytest.raises(ConnectionError):
                h.client.write_device_payload(
                    [jnp.zeros((16 << 10,), jnp.float32)], tracker=t)
            cell = t.cell.get_value()
            assert cell["failed"] == 1
            assert cell["transfers"] == cell["completed"] + cell["failed"]
        finally:
            h.close()

    def test_leak_reclaim_annotates_and_counts(self):
        """A pull registration un-ACKed at close is a LEAK: the span
        says so, the cell counts the bytes, and the ici counter pair
        (leaked/reclaimed) carries them to /device."""
        import jax.numpy as jnp

        class _StubSrv:
            def await_pull(self, uid, arrays):
                pass

            def address(self):
                return "stub:1"

        saved_get = ici._get_transfer_server
        saved_leak = ici._leaked_pull_bytes[0]
        saved_epochs = dict(ici._leaked_by_epoch)
        leaked_before = ici._leaked_bytes_counter.get_value()
        ici._get_transfer_server = lambda: _StubSrv()
        h = _Harness()
        try:
            h.client.peer_info = dict(h.client.peer_info,
                                      proc=f"ep-{next(_seq)}",
                                      can_pull=True)
            t = _tracker(peer=f"lk-{next(_seq)}")
            h.client.write_device_payload(
                [jnp.zeros((16,), jnp.float32)], tracker=t)
            # never pumped by the peer, never ACKed: close leaks it
            h.client.close()
            cell = t.cell.get_value()
            assert cell["failed"] == 1
            assert cell["leaked_batches"] == 1
            assert cell["leaked_bytes"] > 0
            assert any("leak-reclaim" in txt
                       for _, txt in t.span.annotations)
            assert ici._leaked_bytes_counter.get_value() > leaked_before
            snap = ici.leak_snapshot()
            assert snap["leaked_bytes"] >= cell["leaked_bytes"]
        finally:
            ici._get_transfer_server = saved_get
            ici._leaked_pull_bytes[0] = saved_leak
            ici._leaked_by_epoch.clear()
            ici._leaked_by_epoch.update(saved_epochs)
            h.close()


class TestCellsBalanceUnderFlapStorm:
    def test_flap_storm_cells_balance(self):
        """Connect/transfer/abruptly-close cycles (the flap shape on
        the lane conn): after every conn is closed, each cell must
        balance — transfers == completed + failed, nothing in limbo."""
        import jax.numpy as jnp
        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        arr = jnp.ones((256,), jnp.float32)
        try:
            for cycle in range(6):
                ch = Channel(f"ici://127.0.0.1:{ep.port}",
                             ChannelOptions(timeout_ms=5000,
                                            share_connections=False))
                n = 1 + (cycle % 3)
                for _ in range(n):
                    cntl = ch.call_sync("DevSvc", "EchoDevice", b"",
                                        request_device_arrays=[arr])
                    assert not cntl.failed(), cntl.error_text
                # abrupt close: the response-leg acks for the last call
                # may still be in flight — close settles them
                ch.close()
        finally:
            server.stop()
            server.join(2)
        time.sleep(0.2)
        bad = {}
        for (peer, lane), cell in ds.global_device_stats().rows():
            v = cell.get_value()
            if v["transfers"] != v["completed"] + v["failed"]:
                bad[f"{peer}|{lane}"] = v
        assert not bad, bad


# ------------------------------------------------------------- the page

class TestDevicePage:
    def test_http_and_builtin_twin_agree(self):
        import jax.numpy as jnp
        from spawn_util import http_get_local

        server, ep = _make_server("tcp://127.0.0.1:0", builtin=True)
        dev_server, dev_ep = _make_server("ici://127.0.0.1:0#device=0")
        ch = Channel(f"ici://127.0.0.1:{dev_ep.port}")
        admin_ch = Channel(f"tcp://127.0.0.1:{ep.port}",
                           ChannelOptions(timeout_ms=5000))
        try:
            arr = jnp.ones((512,), jnp.float32)
            for _ in range(3):
                assert not ch.call_sync(
                    "DevSvc", "EchoDevice", b"",
                    request_device_arrays=[arr]).failed()
            status, body = http_get_local(ep.port, "/device")
            assert status == 200
            http_page = json.loads(body)
            cntl = admin_ch.call_sync("builtin", "device", b"")
            assert not cntl.failed(), cntl.error_text
            twin = json.loads(bytes(cntl.response_payload.to_bytes()))
            # the twin views come from ONE builder: cells and leak
            # panes agree (totals may drift by in-flight acks between
            # the two scrapes, the structure must not)
            assert set(http_page.keys()) == set(twin.keys())
            assert http_page["cells"].keys() == twin["cells"].keys()
            assert http_page["enabled"] and twin["enabled"]
            assert http_page["transfer_lane"] == twin["transfer_lane"]
            assert any(c["lane_kind"] == "local-d2d"
                       for c in http_page["conns"])
        finally:
            ch.close()
            admin_ch.close()
            dev_server.stop()
            dev_server.join(2)
            server.stop()
            server.join(2)

    def test_supervisor_merge_math(self):
        """merge_device_payloads: counters SUM, latency samples POOL
        (the averaged-percentile-would-be-wrong case), conns concat,
        lane status reports the worst reading."""
        a = {"enabled": True, "transfer_lane": "up",
             "cells": {"p|l": {"transfers": 4, "completed": 3,
                               "failed": 1, "bytes_out": 400,
                               "stage_us_sum": 40.0, "wire_us_sum": 10.0,
                               "ack_us_sum": 50.0,
                               "max_latency_us": 90.0,
                               "latency_samples": [10.0] * 9}},
             "totals": {"transfers": 4, "failed": 1},
             "conns": [{"remote": "a"}], "leaks": {"leaked_bytes": 5}}
        b = {"enabled": True, "transfer_lane": "down: no server",
             "cells": {"p|l": {"transfers": 2, "completed": 2,
                               "failed": 0, "bytes_out": 100,
                               "stage_us_sum": 10.0, "wire_us_sum": 5.0,
                               "ack_us_sum": 5.0,
                               "max_latency_us": 1000.0,
                               "latency_samples": [1000.0]}},
             "totals": {"transfers": 2, "failed": 0},
             "conns": [{"remote": "b"}], "leaks": {"leaked_bytes": 7}}
        m = ds.merge_device_payloads([a, b])
        cell = m["cells"]["p|l"]
        assert cell["transfers"] == 6 and cell["completed"] == 5
        assert cell["bytes_out"] == 500
        assert cell["max_latency_us"] == 1000.0
        # pooled p50 over [10.0 x9, 1000.0] is 10.0 — an average of
        # per-shard percentiles would report ~505
        assert cell["latency_p50_us"] == 10.0
        assert m["totals"]["transfers"] == 6
        assert len(m["conns"]) == 2
        assert m["transfer_lane"].startswith("down")
        assert m["leaks"]["leaked_bytes"] == 12
        assert m["shards_reporting"] == 2
        # a host-only shard's "not loaded" must not mask a sibling's
        # healthy pull lane (only a real "down:" outranks "up")
        c = {"enabled": True, "transfer_lane": "not loaded",
             "cells": {}, "totals": {}, "conns": []}
        d = {"enabled": True, "transfer_lane": "up",
             "cells": {}, "totals": {}, "conns": []}
        assert ds.merge_device_payloads([c, d])["transfer_lane"] == "up"

    def test_shard_aggregator_merged_device(self, tmp_path):
        from brpc_tpu.rpc.shard_group import ShardAggregator
        for i in range(2):
            doc = {"shard": i, "pid": 1000 + i, "seq": 1,
                   "vars": {}, "status": {},
                   "device": {"enabled": True, "transfer_lane": "up",
                              "cells": {"p|l": {"transfers": 1 + i,
                                                "completed": 1 + i,
                                                "failed": 0,
                                                "latency_samples": []}},
                              "totals": {"transfers": 1 + i},
                              "conns": []}}
            (tmp_path / f"shard-{i}.json").write_text(json.dumps(doc))
        agg = ShardAggregator(str(tmp_path), 2)
        m = agg.merged_device()
        assert m["shards_reporting"] == 2
        assert m["cells"]["p|l"]["transfers"] == 3
        assert m["totals"]["transfers"] == 3

    def test_probe_pane_reads_artifact(self, tmp_path):
        probe = {"headline_GBps": 1.5, "lane_kind": "local-d2d",
                 "stage_breakdown": {"4096": {"stage_us": 1.0}},
                 "sweep": {"ignored": 1}}
        path = tmp_path / "DEVICE_PROBE.json"
        path.write_text(json.dumps(probe))
        old = flag("device_probe_path")
        set_flag("device_probe_path", str(path))
        try:
            page = ds.device_page_payload()
            assert page["probe"]["headline_GBps"] == 1.5
            assert "stage_breakdown" in page["probe"]
            assert "sweep" not in page["probe"]   # bounded pane
            assert "age_s" in page["probe"]
        finally:
            set_flag("device_probe_path", old)


class TestExportFormats:
    def test_prometheus_labels_and_json_safe_vars(self):
        peer = f"prom-{next(_seq)}"
        ds.global_device_stats().device_cell(peer, "test-lane")\
            .note_open(64)
        ds.expose_device_vars()
        from brpc_tpu.bvar.prometheus import dump_prometheus
        lines = [ln for ln in dump_prometheus().splitlines()
                 if ln.startswith("device_stats")
                 and f'peer="{peer}"' in ln]
        assert any("device_stats_transfers{" in ln for ln in lines)
        assert any('lane="test-lane"' in ln for ln in lines)
        from brpc_tpu.bvar.variable import dump_exposed
        dumped = json.dumps(dict(dump_exposed("device_stats")),
                            default=str)
        assert peer in dumped

    def test_ici_vars_survive_unexpose_all(self):
        """The PR 2 unexpose_all survival rule, applied to the ici
        counters: a Server.start after a fixture's unexpose_all must
        re-expose ici_* (a restart used to silently drop them)."""
        from brpc_tpu.bvar.variable import dump_exposed, unexpose_all
        ici._unpulled_registrations.add(0)     # materialize the bvar
        ici._publish_lane_status()
        unexpose_all()
        assert dict(dump_exposed("ici_")) == {}
        server, _ = _make_server("tcp://127.0.0.1:0", builtin=True)
        try:
            names = dict(dump_exposed("ici_"))
            assert "ici_unpulled_registrations" in names
            assert "ici_transfer_lane" in names
            assert dict(dump_exposed("device_stats"))
        finally:
            server.stop()
            server.join(2)


class TestPostfork:
    def test_registered_and_child_starts_fresh(self):
        from brpc_tpu.butil import postfork
        assert "transport.device_stats" in postfork.registered_names()
        reg = ds.global_device_stats()
        reg.device_cell("fork-peer", "fork-lane").note_open(1)
        ds.stamp_device_thread("device:forktest", tid=424242)
        parent_cells = reg._dim.count_stats()
        assert parent_cells >= 1

        r, w = os.pipe()
        pid = os.fork()
        if pid == 0:
            try:
                child = ds.global_device_stats()
                ok = (child is not reg
                      and child._dim.count_stats() == 0
                      and ds.device_thread_label(424242) is None)
                msg = "OK" if ok else \
                    f"stale: {child._dim.count_stats()} cells"
            except BaseException as e:  # noqa: BLE001 - report only
                msg = f"EXC:{type(e).__name__}:{e}"
            try:
                os.write(w, msg.encode()[:4096])
            finally:
                os._exit(0)
        os.close(w)
        chunks = []
        while True:
            b = os.read(r, 4096)
            if not b:
                break
            chunks.append(b)
        os.close(r)
        os.waitpid(pid, 0)
        ds.unstamp_device_thread(tid=424242)
        assert b"".join(chunks).decode() == "OK"
        assert ds.global_device_stats() is reg
        assert reg._dim.count_stats() == parent_cells

    def test_census_registered(self):
        from brpc_tpu.butil import resource_census
        assert "device_lane" in resource_census.registered_names()
        ds.global_device_stats().device_cell("census-peer",
                                             "census-lane").note_open(1)
        snap = resource_census.snapshot()["device_lane"]
        assert "bytes" in snap and "count" in snap


class TestSamplerAttribution:
    def test_attribute_prefers_device_thread_label(self):
        from brpc_tpu.builtin.flight_recorder import (FlightRecorder,
                                                      _bind_sampler_imports)
        _bind_sampler_imports()
        tid = 555001
        ds.stamp_device_thread("device:unit-test", tid=tid)
        try:
            label = FlightRecorder._attribute(tid, {tid: "whatever"})
            assert label == "device:unit-test"
        finally:
            ds.unstamp_device_thread(tid=tid)
        assert FlightRecorder._attribute(
            tid, {tid: "plain"}) == "thread:plain"

    def test_device_poller_busy_samples_attribute(self):
        """The acceptance bar: >= 80% of the device-poller thread's
        BUSY samples attribute to device:* (its pump label), not to a
        thread-name leaf."""
        from brpc_tpu.builtin.flight_recorder import FlightRecorder
        from brpc_tpu.fiber.device_poller import DeviceEventPoller

        class _NeverReady:
            def is_ready(self):
                # a little work per check so the pump samples as busy
                sum(range(200))
                return False

        name = f"device_poller_t{next(_seq)}"
        poller = DeviceEventPoller(name=name)
        for _ in range(8):
            poller.watch(_NeverReady(), lambda: None)
        rec = FlightRecorder()
        rec.ensure_running()
        old_hz = flag("continuous_profiler_hz")
        set_flag("continuous_profiler_hz", 100)
        try:
            time.sleep(0.8)
            m = rec.merged()
        finally:
            set_flag("continuous_profiler_hz", old_hz)
            rec.stop()
            poller.stop()
        dev = sum(n for lbl, n in m["labels"].items()
                  if lbl == f"device:{name}")
        leaf = sum(n for lbl, n in m["labels"].items()
                   if lbl == f"thread:{name}")
        assert dev + leaf >= 3, m["labels"]
        assert dev / (dev + leaf) >= 0.8, m["labels"]
