"""Device lane speed-run tests (ISSUE 19): eager idle-ACKs settling
cells WITHOUT close, coalesced small-batch descriptor frames with exact
cell accounting, the pipelined window surviving chaos delay faults with
nothing leaked or unbalanced, the HBM-pinned staging class falling back
cleanly when jax lacks the transfer runtime, and combo-channel fan-out
lowering to one XLA collective when every sub-channel is device-lane.
"""

import threading
import time

import numpy as np
import pytest

from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions
from brpc_tpu.rpc.service import Service
from brpc_tpu.transport import device_stats as ds
from brpc_tpu.transport import ici

_seq = iter(range(100000))


def _make_server(addr: str):
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("DevSvc")

    @svc.method()
    def EchoDevice(cntl, request):
        cntl.response_device_arrays = [a
                                       for a in cntl.request_device_arrays]
        return b"dev"

    server.add_service(svc)
    ep = server.start(addr)
    return server, ep


@pytest.fixture
def device_stats_on():
    old = flag("device_stats_enabled")
    set_flag("device_stats_enabled", True)
    yield
    set_flag("device_stats_enabled", old)


class _ConnHarness:
    """Raw transport-level pair with manual pumping (test_ici idiom)."""

    def __init__(self, window=8, pool=None):
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
            assert time.monotonic() < deadline, "handshake never completed"
            time.sleep(0.01)

    def _on_conn(self, conn):
        self.server_conn = conn
        self._evt.set()

    @staticmethod
    def pump(conn):
        buf = bytearray(1 << 16)
        try:
            conn.read_into(memoryview(buf))
        except BlockingIOError:
            pass

    @classmethod
    def take(cls, conn, timeout_s=5.0):
        deadline = time.monotonic() + timeout_s
        while True:
            cls.pump(conn)
            batch = conn.take_device_payload()
            if batch is not None:
                return batch
            assert time.monotonic() < deadline, "no lane batch arrived"
            time.sleep(0.01)

    def close(self):
        self.client.close()
        if self.server_conn is not None:
            self.server_conn.close()
        self.listener.stop()


# ----------------------------------------------------- idle-ack settling

class TestIdleAckSettlesWithoutClose:
    def test_cells_balance_on_live_conn(self, device_stats_on):
        """The eager idle-ACK timer must flush the consumed-but-
        unsignaled ack tail: a quiescent lane's cells reach
        transfers == completed + failed with the connection OPEN —
        before ISSUE 19 only close() settled the tail."""
        import jax.numpy as jnp
        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        peer = f"ici://127.0.0.1:{ep.port}"
        ch = Channel(peer, ChannelOptions(timeout_ms=10000))
        try:
            arr = jnp.ones((256,), jnp.float32)
            for _ in range(6):
                cntl = ch.call_sync("DevSvc", "EchoDevice", b"",
                                    request_device_arrays=[arr])
                assert not cntl.failed(), cntl.error_text
            deadline = time.monotonic() + 5.0
            bad = {}
            while True:
                bad = {}
                for (p, lane), cell in ds.global_device_stats().rows():
                    if p != peer:
                        continue
                    v = cell.get_value()
                    if v["transfers"] != v["completed"] + v["failed"]:
                        bad[f"{p}|{lane}"] = v
                if not bad or time.monotonic() >= deadline:
                    break
                time.sleep(0.05)
            assert not bad, f"cells unbalanced without close: {bad}"
            sock = ch._get_socket()
            intro = sock.conn.lane_introspection()
            assert intro["outstanding_batches"] == 0
        finally:
            ch.close()
            server.stop()
            server.join(2)


# --------------------------------------------------- coalesced batches

class TestCoalescedSmallBatches:
    def test_coalesced_round_trip_counts_and_bytes_exact(
            self, device_stats_on):
        """Small lane batches queued unflushed ride ONE coalesced
        descriptor frame at the flush; the receiver FIFO-takes each
        sub-batch intact and the /device cells count every batch and
        every byte exactly (per-sub accounting under the shared
        frame)."""
        import jax.numpy as jnp
        h = _ConnHarness(window=8)
        try:
            n = 4
            peer = f"coal-{next(_seq)}"
            trackers = []
            for i in range(n):
                t = ds.open_transfer(peer, "test-lane", 64,
                                     parent_span=None)
                trackers.append(t)
                h.client.write_device_payload(
                    [jnp.full((16,), i, jnp.float32)], tracker=t,
                    flush=False)
            h.client.flush()
            intro = h.client.lane_introspection()
            assert intro["coalesced_frames"] >= 1, intro
            assert intro["coalesced_batches"] >= 2, intro
            for i in range(n):
                batch = h.take(h.server_conn)
                assert len(batch) == 1
                np.testing.assert_array_equal(
                    np.asarray(batch[0]), np.full((16,), i, np.float32))
            # acks ride back: every tracker settles individually
            deadline = time.monotonic() + 5
            while h.client.outstanding_batches:
                h.pump(h.client)
                assert time.monotonic() < deadline, "acks never returned"
                time.sleep(0.01)
            cell = trackers[0].cell.get_value()
            assert cell["transfers"] == n
            assert cell["completed"] == n
            assert cell["failed"] == 0
            assert cell["bytes_out"] == n * 64
        finally:
            h.close()

    def test_large_batches_do_not_coalesce(self, device_stats_on):
        """Batches above ici_coalesce_bytes keep their own descriptor
        frame — coalescing is strictly a small-payload optimization."""
        import jax.numpy as jnp
        h = _ConnHarness(window=8)
        try:
            big = (int(flag("ici_coalesce_bytes")) // 4) + 32
            for i in range(3):
                h.client.write_device_payload(
                    [jnp.full((big,), i, jnp.float32)], flush=False)
            h.client.flush()
            intro = h.client.lane_introspection()
            assert intro["coalesced_frames"] == 0, intro
            for i in range(3):
                batch = h.take(h.server_conn)
                assert np.asarray(batch[0])[0] == i
        finally:
            h.close()


# ------------------------------------------- pipelined window vs chaos

class TestPipelinedWindowUnderChaos:
    def test_delay_faults_leave_cells_balanced_no_leaks(
            self, device_stats_on):
        """A pipelined multi-flight burst through chaos delay faults:
        calls may slow down but every cell must still balance (without
        close) and the pull-leak counters must not move — delays are
        not losses."""
        import jax.numpy as jnp
        from brpc_tpu import chaos
        from brpc_tpu.chaos import Fault, FaultPlan

        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        peer = f"ici://127.0.0.1:{ep.port}"
        plan = FaultPlan(seed=7)
        for conn_idx in range(4):
            plan.at(peer, conn_idx,
                    Fault("delay", at_byte=64, delay_ms=30))
        chaos.install(plan)
        try:
            ch = Channel(peer, ChannelOptions(timeout_ms=15000,
                                              share_connections=False))
            arr = jnp.ones((512,), jnp.float32)
            cntls = [ch.call("DevSvc", "EchoDevice", b"",
                             request_device_arrays=[arr])
                     for _ in range(12)]
            for c in cntls:
                c.join(15.0)
                assert not c.failed(), c.error_text
            deadline = time.monotonic() + 5.0
            while True:
                bad = {}
                for (p, lane), cell in ds.global_device_stats().rows():
                    if p != peer:
                        continue
                    v = cell.get_value()
                    if v["transfers"] != v["completed"] + v["failed"]:
                        bad[f"{p}|{lane}"] = v
                if not bad or time.monotonic() >= deadline:
                    break
                time.sleep(0.05)
            assert not bad, f"chaos delays unbalanced cells: {bad}"
            # delays are not losses: nothing leaked on this peer
            for (p, lane), cell in ds.global_device_stats().rows():
                if p == peer:
                    v = cell.get_value()
                    assert v["leaked_batches"] == 0, v
            ch.close()
        finally:
            chaos.uninstall()
            server.stop()
            server.join(2)


# ------------------------------------------------ pinned staging class

class TestPinnedStagerFallback:
    def test_pinned_path_stages_and_block_follows_the_array(self):
        """The copy lands through an mlock'd block. The CPU client does
        not copy an aligned host buffer, it aliases it, so here the
        block must stay out of the freelist for as long as the array
        lives (on a TPU the copy is real and the block recycles on
        readiness — chip_smoke.py checks that side by values)."""
        import gc
        import jax
        from brpc_tpu import native
        from brpc_tpu.butil.device_pool import DevicePinnedStager
        if native.alloc_pinned_block(1) is None:
            pytest.skip("native pinned arena unavailable")
        s = DevicePinnedStager()
        assert s.active is True
        base = native.pinned_pool_stats()["classes"][0]["live"]
        a = np.arange(256, dtype=np.float32).reshape(16, 16)
        out = s.land(a, device=jax.devices()[0])
        jax.block_until_ready(out)
        assert s.staged_count == 1
        time.sleep(0.2)     # a readiness-triggered recycle would fire now
        assert native.pinned_pool_stats()["classes"][0]["live"] == base + 1
        np.testing.assert_array_equal(np.asarray(out), a)
        del out
        gc.collect()
        assert native.pinned_pool_stats()["classes"][0]["live"] == base, \
            "pinned block never recycled after the array died"

    def test_landed_arrays_survive_later_landings(self):
        """Regression: on the CPU client the block recycled on the
        array's readiness, and the next payload overwrote the previous
        array through the alias."""
        import jax
        from brpc_tpu import native
        from brpc_tpu.butil.device_pool import DevicePinnedStager
        if native.alloc_pinned_block(1) is None:
            pytest.skip("native pinned arena unavailable")
        s = DevicePinnedStager()
        hosts = [np.arange(4096, dtype=np.float32) + i for i in range(8)]
        landed = []
        for h in hosts:
            arr = s.land(h, device=jax.devices()[0])
            jax.block_until_ready(arr)
            time.sleep(0.02)
            landed.append(arr)
        for h, arr in zip(hosts, landed):
            np.testing.assert_array_equal(np.asarray(arr), h)

    def test_no_native_alloc_returns_none(self):
        """BRPC_TPU_NO_NATIVE (or a missing .so) must degrade to
        None, never raise — the staging helpers branch on it."""
        from brpc_tpu.butil.device_pool import DevicePinnedStager
        import brpc_tpu.native as native

        orig = native.alloc_pinned_block
        native.alloc_pinned_block = lambda n: None
        try:
            s = DevicePinnedStager()
            assert s.active is False      # probe sees no pinned arena
            a = np.arange(16, dtype=np.float32)
            out = s.land(a)
            np.testing.assert_array_equal(np.asarray(out), a)
            assert s.fallback_count == 1
        finally:
            native.alloc_pinned_block = orig

    def test_pinned_staging_block_fallback_is_pageable(self):
        """iobuf's staging helper never fails: pageable memoryview
        when the arena can't serve (oversized here)."""
        from brpc_tpu.butil.iobuf import pinned_staging_block
        st = pinned_staging_block(8 << 20)   # beyond the largest class
        assert st.pinned is False
        st.view[:4] = b"abcd"
        assert bytes(st.view[:4]) == b"abcd"
        st.release()                          # no-op, must not raise


# ------------------------------------------- collective-lowered fan-out

class TestCollectiveLoweredParallelChannel:
    def test_device_fanout_lowers_to_one_collective(self):
        import jax.numpy as jnp
        from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh
        from brpc_tpu.rpc.combo_channels import (ParallelChannel,
                                                 RowScatterMapper)
        from brpc_tpu.rpc.controller import Controller

        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        subs = []
        try:
            mesh = make_rpc_mesh(n_replicas=1, n_shards=8)
            coll = CollectiveChannel(mesh, merge="concat")
            # the scattering mapper with the collecting merger: the one
            # fan-out that computes what a scatter + concat computes
            # (the stock CallMapper sends the WHOLE array to every sub
            # and is not lowered)
            pc = ParallelChannel(call_mapper=RowScatterMapper())
            for _ in range(8):
                sub = Channel(f"ici://127.0.0.1:{ep.port}")
                subs.append(sub)
                pc.add_sub_channel(sub)
            assert all(s.device_lane_kind() == "local-d2d" for s in subs)
            pc.attach_collective(coll,
                                 {("DevSvc", "Scale"): lambda s: s * 3})

            cntl = Controller()
            cntl.request_device_arrays = [jnp.arange(16.0)]
            pc.call("DevSvc", "Scale", b"", cntl=cntl)
            cntl.join(10.0)
            assert not cntl.failed(), cntl.error_text
            assert getattr(cntl, "collective_lowered", False)
            assert pc.collective_fused == 1
            # block i where the fan-out's collecting merger keeps it,
            # and nothing where it keeps nothing
            assert cntl.response_device_arrays == []
            for i in range(8):
                np.testing.assert_allclose(
                    np.asarray(cntl.sub_device_arrays[i][0]),
                    np.arange(2 * i, 2 * i + 2) * 3.0)

            # an unmapped method on the same channel fans out: block i
            # of the request reaches sub i through the lane
            c2 = Controller()
            c2.request_device_arrays = [jnp.arange(16.0)]
            pc.call("DevSvc", "EchoDevice", b"", cntl=c2)
            c2.join(10.0)
            assert not c2.failed(), c2.error_text
            assert pc.collective_fused == 1    # unchanged
            assert pc.collective_fallbacks == 0
            assert c2.sub_responses.count(b"dev") == 8
            for i in range(8):
                np.testing.assert_allclose(
                    np.asarray(c2.sub_device_arrays[i][0]),
                    np.arange(2 * i, 2 * i + 2))
        finally:
            for s in subs:
                s.close()
            server.stop()
            server.join(2)

    def test_unmapped_method_falls_through(self):
        """A method without a registered shard function must take the
        per-sub fan-out even with a collective attached."""
        import jax.numpy as jnp
        from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh
        from brpc_tpu.rpc.combo_channels import ParallelChannel
        from brpc_tpu.rpc.controller import Controller

        server, ep = _make_server("ici://127.0.0.1:0#device=0")
        subs = []
        try:
            mesh = make_rpc_mesh(n_replicas=1, n_shards=8)
            pc = ParallelChannel()
            for _ in range(8):
                sub = Channel(f"ici://127.0.0.1:{ep.port}")
                subs.append(sub)
                pc.add_sub_channel(sub)
            pc.attach_collective(CollectiveChannel(mesh),
                                 {("DevSvc", "Other"): lambda s: s})
            cntl = Controller()
            cntl.request_device_arrays = [jnp.arange(8.0)]
            pc.call("DevSvc", "EchoDevice", b"", cntl=cntl)
            cntl.join(10.0)
            assert not cntl.failed(), cntl.error_text
            assert not getattr(cntl, "collective_lowered", False)
            assert pc.collective_fused == 0
            assert sum(1 for x in cntl.sub_device_arrays if x) == 8
        finally:
            for s in subs:
                s.close()
            server.stop()
            server.join(2)
