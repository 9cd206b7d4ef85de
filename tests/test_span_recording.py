"""``rpc.span.recording()``: spans record while the operator's flag is
set OR while a JAX profile runs in the process, and not otherwise.

Every case drives ``ici://`` with a device array each way (the only path
the benchmark's cells measure) on the CPU. The stage arithmetic checked
here is the one ``benchmark/lib/rpc_spans.py`` publishes: seven stages
between the client's ``start_us`` and ``end_us``, a boundary stamped by
two threads taken at the earlier stamp, summing exactly to the call.
"""

import glob
import logging
import os
import sys
import threading
import time
import types

import pytest

from benchmark.lib.rpc_spans import stages_of
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions
from brpc_tpu.rpc import span as span_mod
from brpc_tpu.rpc.service import Service
from brpc_tpu.rpc.span import Span, global_collector, recording

SPANS_A_CALL = {("client", "Dev"): 1, ("server", "Dev"): 1,
                ("device", "device"): 2, ("device", "device-recv"): 2}


@pytest.fixture
def fabric():
    """One server and one channel over ici://, both flags as the
    benchmark has them (rpcz off, device stats on), an empty ring."""
    saved = {n: flag(n) for n in ("rpcz_enabled", "device_stats_enabled")}
    set_flag("rpcz_enabled", False)
    set_flag("device_stats_enabled", True)
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("Dev")

    @svc.method()
    def Echo(cntl, request):
        cntl.response_device_arrays = list(cntl.request_device_arrays)
        return bytes(request)

    server.add_service(svc)
    ep = server.start("ici://127.0.0.1:0#device=0")
    ch = Channel(f"ici://127.0.0.1:{ep.port}",
                 ChannelOptions(timeout_ms=10000))
    import jax.numpy as jnp
    arr = jnp.arange(64, dtype=jnp.float32)

    def call(n=1):
        for _ in range(n):
            cntl = ch.call_sync("Dev", "Echo", b"tag",
                                request_device_arrays=[arr])
            assert not cntl.failed(), cntl.error_text
            assert cntl.response_device_arrays[0].shape == (64,)
    call()                      # the lane's hello, outside every count
    global_collector.clear()
    try:
        yield call
    finally:
        ch.close()
        server.stop()
        server.join(2)
        for n, v in saved.items():
            set_flag(n, v)
        global_collector.clear()


@pytest.fixture
def count_spans(monkeypatch):
    """Counts every Span constructed, submitted or not."""
    made = []
    init = Span.__init__

    def counting(self, *a, **kw):
        made.append(1)
        init(self, *a, **kw)
    monkeypatch.setattr(Span, "__init__", counting)
    return made


def _settled(want, deadline_s=5.0):
    """The ring once it holds ``want`` spans (server and device spans
    trail the client's return by a thread hand-over)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        spans = global_collector.recent(10 ** 6)
        if len(spans) >= want:
            return spans
        time.sleep(0.01)
    return global_collector.recent(10 ** 6)


def _kinds(spans):
    out = {}
    for s in spans:
        key = (s.side, s.service)
        out[key] = out.get(key, 0) + 1
    return out


class TestRecordingOff:
    def test_no_span_no_lock_no_annotation(self, fabric, count_spans,
                                           monkeypatch):
        """Flag off, no profile: a call over ici:// constructs no Span,
        takes no span lock and emits no TraceAnnotation."""
        import jax.profiler  # noqa: F401 - the predicate must see it loaded
        locked, annotated = [], []

        class Watched:
            def __init__(self, name):
                self._name, self._lock = name, threading.Lock()

            def __enter__(self):
                locked.append(self._name)
                return self._lock.__enter__()

            def __exit__(self, *a):
                return self._lock.__exit__(*a)
        monkeypatch.setattr(global_collector, "_lock", Watched("ring"))
        monkeypatch.setattr(span_mod.global_store, "_lock",
                            Watched("store"))
        monkeypatch.setattr(span_mod, "_emit_clock",
                            lambda: annotated.append(1))
        assert not recording()
        fabric(5)
        time.sleep(0.2)         # trailing server work
        assert count_spans == []
        assert locked == [] and annotated == []
        assert global_collector.recent(100) == []

    def test_flag_alone_still_records(self, fabric):
        set_flag("rpcz_enabled", True)
        try:
            assert recording()
            fabric(3)
            spans = _settled(18)
        finally:
            set_flag("rpcz_enabled", False)
        assert _kinds(spans) == {k: 3 * n for k, n in SPANS_A_CALL.items()}
        assert not recording()

    @pytest.mark.parametrize("case", ["jax_absent", "state_missing",
                                      "session_attr_missing"])
    def test_predicate_without_a_profiler(self, case, monkeypatch, caplog):
        """No jax in sys.modules, or a jax whose profiler lost the
        attribute: false, no exception, one log line."""
        monkeypatch.setattr(span_mod, "_profile_state", None)
        fake = types.ModuleType(span_mod._PROFILER_MODULE)
        if case == "jax_absent":
            monkeypatch.delitem(sys.modules, span_mod._PROFILER_MODULE,
                                raising=False)
        else:
            if case == "session_attr_missing":
                fake._profile_state = object()
            monkeypatch.setitem(sys.modules, span_mod._PROFILER_MODULE,
                                fake)
        saved = flag("rpcz_enabled")
        set_flag("rpcz_enabled", False)
        try:
            with caplog.at_level(logging.WARNING, logger=span_mod.__name__):
                assert recording() is False
                assert recording() is False
            warned = [r for r in caplog.records if "rpcz" in r.getMessage()]
            if case == "jax_absent":
                # nothing decided: asked again once jax is loaded
                assert span_mod._profile_state is None and not warned
            else:
                assert span_mod._profile_state is False
                assert len(warned) == 1
            set_flag("rpcz_enabled", True)
            assert recording() is True      # the flag needs no jax
        finally:
            set_flag("rpcz_enabled", saved)

    def test_ring_holds_a_profiles_worth(self):
        from brpc_tpu.butil.flags import list_flags
        default = {n: d for n, _v, d, _h in list_flags()}["rpcz_max_spans"]
        assert default == 16384
        saved = flag("rpcz_max_spans")      # another test may have set it
        set_flag("rpcz_max_spans", default)
        try:
            ring = span_mod.SpanCollector()
            for i in range(default + 10):
                ring._append(Span(trace_id=1, span_id=i + 1))
            assert len(ring.recent(10 ** 6)) == 16384
        finally:
            set_flag("rpcz_max_spans", saved)


class TestRecordingUnderProfile:
    N = 8

    def test_profile_turns_stamps_on_and_off(self, fabric, count_spans,
                                             tmp_path):
        """start_trace -> six spans a call with telescoping stages;
        stop_trace -> later calls create none."""
        import jax
        fabric(2)
        assert count_spans == []
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert recording()
            fabric(self.N)
            spans = _settled(6 * self.N)
        finally:
            jax.profiler.stop_trace()
        assert not recording()
        assert _kinds(spans) == {k: self.N * n
                                 for k, n in SPANS_A_CALL.items()}
        servers = {s.parent_span_id: s for s in spans if s.side == "server"}
        clients = [s for s in spans if s.side == "client"]
        for c in clients:
            s = servers[c.span_id]
            assert s.trace_id == c.trace_id
            client_marks = [c.start_us, c.write_done_us, c.first_byte_us,
                            c.parse_done_us, c.end_us]
            server_marks = [s.received_us, s.dispatch_us, s.parse_done_us,
                            s.handler_start_us, s.handler_end_us,
                            s.serialized_us, s.flushed_us, s.end_us]
            assert all(client_marks) and all(server_marks)
            assert client_marks == sorted(client_marks), client_marks
            assert server_marks == sorted(server_marks), server_marks
            stages = stages_of(c, s)    # asserts both itself, too
            assert len(stages) == 7 and min(stages) >= 0, stages
            assert sum(stages) == c.end_us - c.start_us
        # every device span hangs off a span of its own call
        rpc_ids = {s.span_id for s in spans if s.side != "device"}
        assert all(s.parent_span_id in rpc_ids
                   for s in spans if s.side == "device")
        # the profile is over: nothing is made, nothing is kept
        made, kept = len(count_spans), len(spans)
        fabric(3)
        time.sleep(0.2)
        assert len(count_spans) == made
        assert len(global_collector.recent(10 ** 6)) == kept

    def test_clock_event_maps_a_stamp_to_profile_time(self, fabric,
                                                      tmp_path):
        """One ``rpcz.clock`` event a session: profile time of a stamp =
        event start + (stamp - monotonic_ns)."""
        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            fabric(2)
            with jax.profiler.TraceAnnotation("probe.mark"):
                stamp_ns = time.monotonic_ns()
                time.sleep(0.002)
            fabric(2)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        assert found
        data = jax.profiler.ProfileData.from_file(found[0])
        clocks, probes = [], []
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("rpcz.clock"):
                        clocks.append(ev)
                    elif ev.name.startswith("probe.mark"):
                        probes.append(ev)
        assert len(clocks) == 1, [e.name for e in clocks]
        assert len(probes) == 1
        anchor_ns = int(dict(clocks[0].stats)["monotonic_ns"])
        mapped = clocks[0].start_ns + (stamp_ns - anchor_ns)
        probe = probes[0]
        assert probe.start_ns - 1e6 <= mapped <= \
            probe.start_ns + probe.duration_ns + 1e6, \
            (mapped - probe.start_ns, probe.duration_ns)

    def test_second_profile_gets_its_own_clock(self, fabric, tmp_path,
                                               monkeypatch):
        import jax
        emitted = []
        emit = span_mod._emit_clock
        monkeypatch.setattr(span_mod, "_emit_clock",
                            lambda: (emitted.append(1), emit()))
        for i in range(2):
            jax.profiler.start_trace(str(tmp_path / f"p{i}"))
            try:
                fabric(3)
            finally:
                jax.profiler.stop_trace()
            assert not recording()
        assert len(emitted) == 2


def test_benchmark_and_span_clocks_are_one():
    """The benchmark stamps with perf_counter_ns, the spans with
    monotonic_ns: on Linux both read CLOCK_MONOTONIC."""
    for _ in range(5):
        a = time.perf_counter_ns()
        b = time.monotonic_ns()
        c = time.perf_counter_ns()
        assert a - 1_000_000 <= b <= c + 1_000_000, (a, b, c)
