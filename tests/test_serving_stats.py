"""Serving flight deck (ISSUE 18): token-granular stage spans, the
per-method cell family, batcher iteration telemetry, and the surfaces
they feed.

The contract under test mirrors the device observatory's (PR 12), with
the serving lane's own stage vocabulary: a generation's serving span
carries queue/prefill/decode/emit stamps that TELESCOPE — they sum to
the stream latency by construction, even when a stage was never
reached — and the span is a child of the owning RPC span, so one rpcz
trace walks client -> server -> generation. The /serving pane comes
from ONE builder (HTTP route, builtin twin, supervisor merge), merge
math pools raw reservoirs (never averages percentiles), forked shards
start fresh, and BRPC_TPU_SERVING_STATS=0 produces nothing at all.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, Server, ServerOptions
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.span import global_collector
from brpc_tpu.rpc.stream import StreamOptions
from brpc_tpu.serving import add_generate_service
from brpc_tpu.serving import serving_stats as ss
from brpc_tpu.serving.batcher import ContinuousBatcher, GenRequest
from brpc_tpu.serving.model import TinyDecoder, TinyDecoderConfig

METHOD_KEY = "GenerateService.Generate"
COUNTER_KEYS = ("requests", "admitted", "completed", "evicted", "shed",
                "canceled", "rejected", "tokens_out")


@pytest.fixture(autouse=True)
def _stats_on():
    """Every test starts from a fresh, enabled flight deck (the module
    registry is process-global; leftovers from another test file would
    make counter assertions racy)."""
    set_flag("serving_stats_enabled", True)
    ss._postfork_reset()
    yield
    set_flag("serving_stats_enabled", True)
    set_flag("rpcz_enabled", False)
    ss._postfork_reset()


def _start_server(**kw):
    server = Server(ServerOptions(enable_builtin_services=True))
    kw.setdefault("cache_len", 160)
    kw.setdefault("warmup", True)
    gs = add_generate_service(server, **kw)
    ep = server.start("tcp://127.0.0.1:0")
    return server, gs, ep


def _gen(ch, prompt: str, max_tokens: int, timeout_ms: float = 30000):
    cntl = Controller()
    cntl.timeout_ms = timeout_ms
    return ch.call_sync(
        "GenerateService", "Generate",
        json.dumps({"prompt": prompt,
                    "max_tokens": max_tokens}).encode(), cntl=cntl)


def _serving_spans():
    return [s for s in global_collector.recent(600)
            if s.side == "serving"]


# --------------------------------------------------------- stage spans

class TestStageSpans:
    def test_stages_sum_to_stream_latency_and_inherit_trace(self):
        """The tentpole pin: every generation's serving span explains
        >= 90% of its own latency via queue+prefill+decode+emit (the
        telescoping construction makes it exact), and is parented
        under the owning RPC span with the SAME trace id."""
        server, gs, ep = _start_server()
        try:
            ch = Channel(str(ep))
            assert not _gen(ch, "warm", 2).failed()
            set_flag("rpcz_enabled", True)
            global_collector.clear()
            for i, n in enumerate((4, 24, 8, 16)):
                assert not _gen(ch, f"p{i}", n).failed()
            # a serving span is submitted when the lane has emitted, which
            # on a loaded host can be a beat after the client has its reply
            deadline = time.monotonic() + 5
            while len(_serving_spans()) < 4 and time.monotonic() < deadline:
                time.sleep(0.02)
            spans = _serving_spans()
            assert len(spans) >= 4, [s.side for s in
                                     global_collector.recent(50)]
            # a server span submits on response FLUSH — a beat after
            # the client's call_sync returns; wait for the stragglers
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                server_spans = {s.span_id: s
                                for s in global_collector.recent(600)
                                if s.side == "server"}
                if all(s.parent_span_id in server_spans
                       for s in spans):
                    break
                time.sleep(0.02)
            set_flag("rpcz_enabled", False)
            for s in spans:
                d = s.to_dict()
                total = (d["queue_us"] + d["prefill_us"]
                         + d["decode_us"] + d["emit_us"])
                assert d["latency_us"] > 0
                assert total >= 0.9 * d["latency_us"], d
                # child of the RPC span, same trace
                assert s.parent_span_id != 0
                parent = server_spans.get(s.parent_span_id)
                assert parent is not None, d
                assert parent.trace_id == s.trace_id
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_eviction_annotates_cause(self):
        """A deadline evictee's span says WHY it ended (the cell's
        cause table counts it too) — an incident reader must not have
        to infer eviction from a latency shape."""
        server, gs, ep = _start_server(cache_len=4096)
        try:
            ch = Channel(str(ep))
            assert not _gen(ch, "warm", 2).failed()
            set_flag("rpcz_enabled", True)
            global_collector.clear()
            cntl = _gen(ch, "long", 4000, timeout_ms=400)
            assert cntl.failed()
            assert cntl.error_code == berr.ERPCTIMEDOUT
            # the settle runs on the engine side AFTER the client's
            # deadline fires; keep rpcz on until the span lands
            deadline = time.monotonic() + 5
            ev = []
            while not ev and time.monotonic() < deadline:
                ev = [s for s in _serving_spans()
                      if any("deadline_expired" in a
                             for _, a in s.annotations)]
                time.sleep(0.05)
            set_flag("rpcz_enabled", False)
            assert ev, [s.annotations for s in _serving_spans()]
            row = dict(ss.global_serving_stats().rows())[
                (METHOD_KEY,)].get_value()
            assert row["causes"].get("deadline_expired", 0) >= 1
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_shed_annotates_cause(self):
        """A request refused at the door settles immediately: cause
        queue_full, everything it spent in queue_us, counted shed."""
        server, gs, ep = _start_server(max_batch=1, max_waiting=1,
                                       cache_len=4096)
        try:
            ch = Channel(str(ep))
            assert not _gen(ch, "warm", 2).failed()
            # occupy the slot + the 1-deep queue with streaming hogs,
            # then a third submit must shed
            hogs = []
            for i in range(2):
                c = Controller(); c.timeout_ms = 30000
                hogs.append(ch.call_sync(
                    "GenerateService", "Generate",
                    json.dumps({"prompt": f"hog{i}",
                                "max_tokens": 3000}).encode(),
                    cntl=c,
                    stream_options=StreamOptions(
                        on_received=lambda s, m: None)))
            # both hogs must occupy slot + queue before the overflow
            deadline = time.monotonic() + 10
            while (gs.batcher.running_count()
                   + gs.batcher.waiting_count()) < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            shed = _gen(ch, "overflow", 8, timeout_ms=2000)
            assert shed.failed()
            assert shed.error_code == berr.ELIMIT, shed.error_text
            row = dict(ss.global_serving_stats().rows())[
                (METHOD_KEY,)].get_value()
            assert row["shed"] >= 1
            assert row["causes"].get("queue_full", 0) >= 1
            for h in hogs:
                if getattr(h, "stream", None) is not None:
                    h.stream.close()
            ch.close()
        finally:
            server.stop(); server.join(2)


# ------------------------------------------------------- pane surfaces

class TestPaneSurfaces:
    def test_http_equals_builtin_twin(self):
        """ONE builder: the HTTP /serving page and the builtin RPC
        twin report identical per-method counters (a drift here means
        someone forked the builder)."""
        server, gs, ep = _start_server()
        try:
            ch = Channel(str(ep))
            for i in range(3):
                assert not _gen(ch, f"p{i}", 6).failed()
            import http.client
            conn = http.client.HTTPConnection("127.0.0.1", ep.port,
                                              timeout=10)
            conn.request("GET", "/serving")
            resp = conn.getresponse()
            assert resp.status == 200
            http_page = json.loads(resp.read())
            conn.close()
            cntl = ch.call_sync("builtin", "serving", b"")
            assert not cntl.failed(), cntl.error_text
            rpc_page = json.loads(cntl.response_payload.to_bytes())
            h = http_page["stats"]["methods"][METHOD_KEY]
            r = rpc_page["stats"]["methods"][METHOD_KEY]
            for k in COUNTER_KEYS:
                assert h[k] == r[k], (k, h[k], r[k])
            assert h["completed"] >= 3
            assert http_page["stats"]["steps_total"] > 0
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_merge_pools_reservoirs_never_averages(self):
        """The ShardAggregator discipline on the flight deck: counters
        sum, max* max, causes sum, and the merged p99 is the
        percentile of the POOLED samples — NOT the average of the
        shard p99s (two shards with p99 100 and 10100 must not merge
        to 5100)."""
        def pane(samples, completed, max_ttft):
            return {
                "enabled": True,
                "tokens_per_second_10s": 5.0,
                "methods": {METHOD_KEY: {
                    "requests": completed, "admitted": completed,
                    "completed": completed, "evicted": 0, "shed": 1,
                    "canceled": 0, "rejected": 0,
                    "tokens_out": completed * 4,
                    "max_ttft_us": max_ttft,
                    "causes": {"queue_full": 1},
                    "ttft_samples": samples,
                    "tpot_samples": [1.0] * len(samples),
                }},
                "steps": [{"t_ms": i, "batch": 1}
                          for i in range(3)],
                "steps_total": 3,
            }

        a = pane([100.0] * 99 + [200.0], 100, 200.0)
        b = pane([10100.0] * 100, 100, 10100.0)
        merged = ss.merge_serving_panes([a, b])
        m = merged["methods"][METHOD_KEY]
        assert m["completed"] == 200 and m["tokens_out"] == 800
        assert m["max_ttft_us"] == 10100.0
        assert m["causes"]["queue_full"] == 2
        # pooled percentile: half the pool is 10100, so p99 must sit
        # at 10100 — a count-weighted average of shard p99s (~5150)
        # fails this by construction
        assert m["ttft_p99_us"] == 10100.0, m["ttft_p99_us"]
        assert merged["ttft"]["p99_us"] == 10100.0
        assert merged["tokens_per_second_10s"] == 10.0
        # step rings concat with the reporting shard tagged, bounded
        assert len(merged["steps"]) == 6
        assert {r["shard"] for r in merged["steps"]} == {0, 1}
        assert merged["steps_total"] == 6

    def test_merge_rebounds_reservoirs_by_even_stride(self):
        """Re-exported pooled reservoirs stay bounded at SAMPLE_CAP by
        EVEN STRIDE over the sorted pool — keeping the head would hand
        a downstream pooler a tail-less set whose 'p99' is ~p12."""
        cap = ss.ServingCell.SAMPLE_CAP
        big = list(float(i) for i in range(3 * cap))
        panes = [{
            "enabled": True,
            "methods": {METHOD_KEY: {
                "completed": len(big), "causes": {},
                "ttft_samples": big, "tpot_samples": [],
            }},
            "steps": [], "steps_total": 0,
        }]
        m = ss.merge_serving_panes(panes)["methods"][METHOD_KEY]
        out = m["ttft_samples"]
        assert len(out) == cap
        # the tail survived the rebound
        assert max(out) >= big[-cap // 4]


# ------------------------------------------------- lifecycle + hygiene

class TestLifecycle:
    def test_stats_off_produces_nothing(self):
        """BRPC_TPU_SERVING_STATS=0 is ONE flag check on the request
        path: no trackers, no cells, no step records, no spans."""
        set_flag("serving_stats_enabled", False)
        assert ss.open_generation("S", "M", None) is None
        model = TinyDecoder(TinyDecoderConfig(cache_len=64, seed=3))
        b = ContinuousBatcher(model, max_batch=2, max_waiting=4)
        done = []
        r = GenRequest(list(b"off"), 6,
                       on_finish=lambda r_, s_: done.append(s_))
        r.tracker = ss.open_generation("S", "M", None)
        assert b.submit(r)
        while not done:
            b.step(0)
        reg = ss.global_serving_stats()
        assert reg.steps_recorded() == 0
        assert reg._dim.count_stats() == 0
        assert reg._ttft.count() == 0

    def test_postfork_child_starts_fresh(self):
        from brpc_tpu.butil import postfork
        assert "serving.serving_stats" in postfork.registered_names()
        reg = ss.global_serving_stats()
        reg.serving_cell("fork.Method").note_gen_open()
        ss.stamp_serving_thread("serving:forktest", tid=424243)
        assert reg._dim.count_stats() >= 1

        r, w = os.pipe()
        pid = os.fork()
        if pid == 0:
            try:
                child = ss.global_serving_stats()
                ok = (child is not reg
                      and child._dim.count_stats() == 0
                      and child.steps_recorded() == 0
                      and ss.serving_thread_label(424243) is None)
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
            buf = os.read(r, 4096)
            if not buf:
                break
            chunks.append(buf)
        os.close(r)
        os.waitpid(pid, 0)
        ss.unstamp_serving_thread(tid=424243)
        assert b"".join(chunks).decode() == "OK"
        assert ss.global_serving_stats() is reg

    def test_census_registered(self):
        from brpc_tpu.butil import resource_census
        assert "serving_lane" in resource_census.registered_names()
        snap = resource_census.snapshot()["serving_lane"]
        assert "bytes" in snap and "count" in snap

    def test_step_ring_bounded(self):
        """The ring keeps the LAST serving_step_ring_cap records; the
        total count keeps counting (steps_total tells an operator how
        much history the ring is NOT showing)."""
        saved = flag("serving_step_ring_cap")
        set_flag("serving_step_ring_cap", 16)
        ss._postfork_reset()               # rebuild ring at the new cap
        try:
            model = TinyDecoder(TinyDecoderConfig(cache_len=64,
                                                  seed=3))
            b = ContinuousBatcher(model, max_batch=2, max_waiting=4)
            done = []
            for i in range(2):
                r = GenRequest(list(b"ring"), 20,
                               on_finish=lambda r_, s_:
                               done.append(s_))
                r.tracker = ss.open_generation("S", "M", None)
                assert b.submit(r)
            while len(done) < 2:
                b.step(0)
            reg = ss.global_serving_stats()
            assert reg.steps_recorded() > 16
            recs = reg.step_records(1000)
            assert len(recs) <= 16
            # records re-key into dicts with the full field schema
            assert set(ss.STEP_FIELDS) <= set(recs[-1])
            assert recs[-1]["batch"] >= 1
        finally:
            set_flag("serving_step_ring_cap", saved)
            ss._postfork_reset()


# ------------------------------------------------- sampler attribution

class TestSamplerAttribution:
    def test_attribute_reads_serving_thread_label(self):
        """A thread stamped serving:decode attributes its busy samples
        to the serving lane (resolved via sys.modules on the sampler
        tick — never an import); the existing worker-module pin
        (rpc:GenerateService.Generate during decode slices) stays the
        more specific winner when a module label is active."""
        from brpc_tpu.builtin.flight_recorder import (
            FlightRecorder, _bind_sampler_imports)
        _bind_sampler_imports()
        tid = 555002
        ss.stamp_serving_thread("serving:decode", tid=tid)
        try:
            label = FlightRecorder._attribute(tid, {tid: "whatever"})
            assert label == "serving:decode"
        finally:
            ss.unstamp_serving_thread(tid=tid)
        assert FlightRecorder._attribute(
            tid, {tid: "worker"}) != "serving:decode"

    def test_decode_threads_stamped_during_engine_process(self):
        """E2E: while the engine decodes, SOME thread carries a
        serving:* stamp (warm-up stamps serving:warmup on the start
        thread; process() stamps serving:decode on the winner of the
        decode lock)."""
        server, gs, ep = _start_server(cache_len=4096)
        try:
            ch = Channel(str(ep))
            c = Controller(); c.timeout_ms = 30000
            cntl = ch.call_sync(
                "GenerateService", "Generate",
                json.dumps({"prompt": "stamp me",
                            "max_tokens": 2500}).encode(), cntl=c,
                stream_options=StreamOptions(
                    on_received=lambda s, m: None))
            assert not cntl.failed(), cntl.error_text
            deadline = time.monotonic() + 10
            seen = False
            while not seen and time.monotonic() < deadline:
                seen = any(str(v).startswith("serving:")
                           for v in ss._thread_labels.values())
                time.sleep(0.01)
            if getattr(cntl, "stream", None) is not None:
                cntl.stream.close()
            assert seen, dict(ss._thread_labels)
            ch.close()
        finally:
            server.stop(); server.join(2)
