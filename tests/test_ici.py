"""ici:// device data plane tests (reference: rdma/rdma_endpoint.h
state machine + window flow control, rdma/block_pool.cpp size classes).

Covers: in-process D2D echo, cross-device placement, window stall +
ACK-driven resume, recv-pool budget + finalizer release, out-of-credit
error, and REAL cross-process transfer (PjRt pull lane and the staged
fallback) via a subprocess server."""

import gc
import os
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from brpc_tpu.butil.device_pool import (BLOCK_CLASSES, DeviceRecvPool,
                                        round_to_class)
from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.rpc import Channel, Server
from brpc_tpu.transport import ici

_name_seq = iter(range(10_000))


def make_echo_server():
    from brpc_tpu.rpc.service import Service
    server = Server()
    svc = Service("EchoService")

    @svc.method()
    def Echo(cntl, request):
        return bytes(request)

    @svc.method()
    def EchoDevice(cntl, request):
        cntl.response_device_arrays = [a * 2
                                       for a in cntl.request_device_arrays]
        return b"dev"

    server.add_service(svc)
    return server


# ---------------------------------------------------------- device pool

class TestDeviceRecvPool:
    def test_round_to_class(self):
        assert round_to_class(1) == BLOCK_CLASSES[0]
        assert round_to_class(8 << 10) == 8 << 10
        assert round_to_class((8 << 10) + 1) == 64 << 10
        assert round_to_class(1 << 20) == 2 << 20
        assert round_to_class((2 << 20) + 1) == 4 << 20   # region extend

    def test_reserve_release(self):
        pool = DeviceRecvPool(capacity_bytes=1 << 20)
        f = pool.reserve(100)
        assert pool.used == 8 << 10
        pool.release(f)
        assert pool.used == 0

    def test_exhaustion_raises(self):
        pool = DeviceRecvPool(capacity_bytes=16 << 10)
        pool.reserve(8 << 10)
        pool.reserve(8 << 10)
        with pytest.raises(MemoryError):
            pool.reserve(1, timeout_s=0.05)

    def test_oversized_payload_rejected(self):
        pool = DeviceRecvPool(capacity_bytes=1 << 20)
        with pytest.raises(MemoryError):
            pool.reserve(2 << 20, timeout_s=0.05)

    def test_blocked_reserve_wakes_on_release(self):
        pool = DeviceRecvPool(capacity_bytes=8 << 10)
        f = pool.reserve(1)
        got = []

        def waiter():
            got.append(pool.reserve(1, timeout_s=5))

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        pool.release(f)
        t.join(5)
        assert got and got[0] == 8 << 10

    def test_finalizer_release_under_the_pools_own_lock(self):
        """Regression: release() is a weakref finalizer, and the
        collector can run it inside reserve() on the thread that holds
        the pool lock. With a plain Lock that thread deadlocked on
        itself (chip_smoke's first run from a clean checkout hung so)."""
        pool = DeviceRecvPool(capacity_bytes=64 << 10)
        f = pool.reserve(1)
        done = []

        def reenter():
            with pool._freed:           # where reserve() holds the lock
                pool.release(f)         # what the finalizer does
            done.append(pool.used)

        t = threading.Thread(target=reenter, daemon=True)
        t.start()
        t.join(5)
        assert done == [0], "release() under the pool lock deadlocked"

    def test_try_reserve(self):
        pool = DeviceRecvPool(capacity_bytes=8 << 10)
        assert pool.try_reserve(1) == 8 << 10
        assert pool.try_reserve(1) is None


# --------------------------------------------------------- in-process e2e

class TestIciLocal:
    def test_e2e_device_roundtrip(self):
        import jax.numpy as jnp
        server = make_echo_server()
        ep = server.start("ici://127.0.0.1:0#device=5")
        try:
            ch = Channel(f"ici://127.0.0.1:{ep.port}#reply_device=2")
            arr = jnp.arange(64, dtype=jnp.float32)
            cntl = ch.call_sync("EchoService", "EchoDevice", b"",
                                request_device_arrays=[arr])
            assert not cntl.failed(), cntl.error_text
            out = cntl.response_device_arrays[0]
            assert hasattr(out, "devices")    # stayed a device array
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(arr) * 2)
        finally:
            server.stop()
            server.join(2)

    def test_request_lands_on_server_device(self):
        import jax
        devs = jax.devices()
        server = make_echo_server()
        ep = server.start("ici://127.0.0.1:0#device=5")
        got = {}
        svc = server.services()["EchoService"]

        def WhereAmI(cntl, request):
            got["devices"] = cntl.request_device_arrays[0].devices()
            return b"ok"

        svc.register_method("WhereAmI", WhereAmI)
        try:
            ch = Channel(f"ici://127.0.0.1:{ep.port}")
            arr = jax.device_put(
                jax.numpy.ones((128,), jax.numpy.float32), devs[0])
            cntl = ch.call_sync("EchoService", "WhereAmI", b"",
                                request_device_arrays=[arr])
            assert not cntl.failed(), cntl.error_text
            assert devs[5] in got["devices"]
        finally:
            server.stop()
            server.join(2)

    def test_response_lands_on_reply_device(self):
        import jax
        import jax.numpy as jnp
        devs = jax.devices()
        server = make_echo_server()
        ep = server.start("ici://127.0.0.1:0#device=3")
        try:
            ch = Channel(f"ici://127.0.0.1:{ep.port}#reply_device=6")
            arr = jnp.ones((32,), jnp.float32)
            cntl = ch.call_sync("EchoService", "EchoDevice", b"",
                                request_device_arrays=[arr])
            assert not cntl.failed(), cntl.error_text
            out = cntl.response_device_arrays[0]
            assert devs[6] in out.devices()
        finally:
            server.stop()
            server.join(2)


# ------------------------------------------------- window / flow control

class _ConnHarness:
    """Raw transport-level pair with manual pumping (no event loop)."""

    def __init__(self, window=2, pool=None):
        self.tr = ici.IciTransport(window=window, pool=pool)
        self.server_conn = None
        self._evt = threading.Event()
        self.listener = self.tr.listen(
            str2endpoint("ici://127.0.0.1:0"), self._on_conn)
        self.client = self.tr.connect(
            str2endpoint(f"ici://127.0.0.1:{self.listener.endpoint.port}"))
        assert self._evt.wait(5), "no server conn"
        # pump both sides until hellos land
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
        """Pump until a lane batch is available, then take it (the
        assembled stack's input fiber does the pumping via read_into;
        take itself never touches the TCP socket)."""
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


class TestWindowFlowControl:
    def test_window_stall_and_ack_resume(self):
        import jax.numpy as jnp
        h = _ConnHarness(window=2)
        try:
            for i in range(3):
                h.client.write_device_payload(
                    [jnp.full((4,), i, jnp.float32)])
            # third batch is gated: only 2 un-ACKed batches may fly
            assert h.client.outstanding_batches == 2
            assert any(it[0] == "lane" for it in h.client._outq)
            # receiver consumes both -> bare ACK (2 >= window//2)
            b0 = h.take(h.server_conn)
            b1 = h.take(h.server_conn)
            assert np.asarray(b0[0])[0] == 0 and np.asarray(b1[0])[0] == 1
            # ack reaches the sender: window reopens, third batch flies
            deadline = time.monotonic() + 5
            while h.client.outstanding_batches != 1:
                h.pump(h.client)
                assert time.monotonic() < deadline, "window never reopened"
                time.sleep(0.01)
            assert not any(it[0] == "lane" for it in h.client._outq)
            b2 = h.take(h.server_conn)
            assert np.asarray(b2[0])[0] == 2
        finally:
            h.close()

    def test_stalled_sender_requests_writable(self):
        import jax.numpy as jnp
        h = _ConnHarness(window=1)
        try:
            h.client.write_device_payload([jnp.zeros((4,), jnp.float32)])
            h.client.write_device_payload([jnp.ones((4,), jnp.float32)])
            assert h.client.outstanding_batches == 1
            fired = threading.Event()
            h.client._on_writable_cb = fired.set
            h.client._want_writable = True
            h.take(h.server_conn)                   # consumes + acks
            deadline = time.monotonic() + 5
            while not fired.is_set():
                h.pump(h.client)
                assert time.monotonic() < deadline, "writable never fired"
                time.sleep(0.01)
        finally:
            h.close()

    def test_recv_pool_budget_reserved_and_finalized(self):
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=4 << 20)
        h = _ConnHarness(window=4, pool=pool)
        try:
            h.client.write_device_payload([jnp.zeros((16,), jnp.float32)])
            batch = h.take(h.server_conn)
            assert batch is not None
            assert pool.used == 8 << 10          # one small-class block
            del batch
            gc.collect()
            deadline = time.monotonic() + 5
            while pool.used != 0:
                gc.collect()
                assert time.monotonic() < deadline, "finalizer never ran"
                time.sleep(0.05)
        finally:
            h.close()

    def test_out_of_credit_pool_error(self):
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=8 << 10)
        h = _ConnHarness(window=4, pool=pool)
        try:
            held = pool.reserve(1)               # someone owns the budget
            h.client.write_device_payload([jnp.zeros((16,), jnp.float32)])
            # shrink the take-side wait so the test is fast
            orig = pool.reserve
            pool.reserve = lambda n, timeout_s=10.0: orig(n, timeout_s=0.05)
            deadline = time.monotonic() + 5
            while not h.server_conn._lane:
                h.pump(h.server_conn)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            with pytest.raises(MemoryError):
                h.server_conn.take_device_payload()
            pool.reserve = orig
            pool.release(held)
        finally:
            h.close()


class TestByteBudgetWindow:
    def test_hello_advertises_budget(self):
        pool = DeviceRecvPool(capacity_bytes=32 << 10)
        h = _ConnHarness(window=4, pool=pool)
        try:
            assert h.client.peer_info["budget"] == 32 << 10
            assert h.server_conn.peer_info["budget"] == 32 << 10
        finally:
            h.close()

    def test_byte_budget_gates_sender(self):
        """The sender derives its effective window from the peer's
        advertised byte budget: a batch window of 4 still only lets two
        8K-footprint batches fly against a 16K budget
        (rdma_endpoint.h:235-241 — window sized from pre-posted rbufs)."""
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=16 << 10)
        h = _ConnHarness(window=4, pool=pool)
        try:
            for i in range(3):
                h.client.write_device_payload(
                    [jnp.full((16,), i, jnp.float32)])
            assert h.client.outstanding_batches == 2
            assert any(it[0] == "lane" for it in h.client._outq)
            b0 = h.take(h.server_conn)
            b1 = h.take(h.server_conn)
            assert np.asarray(b0[0])[0] == 0 and np.asarray(b1[0])[0] == 1
            del b0, b1
            gc.collect()
            deadline = time.monotonic() + 5
            while h.client.outstanding_batches != 1:
                h.pump(h.client)
                assert time.monotonic() < deadline, "budget never reopened"
                time.sleep(0.01)
            b2 = h.take(h.server_conn)
            assert np.asarray(b2[0])[0] == 2
        finally:
            h.close()

    def test_midsize_batch_goes_alone(self):
        """A batch over the per-connection budget but within the peer's
        pool capacity is admissible — it flies alone once the lane
        drains instead of failing."""
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=8 << 20)
        h = _ConnHarness(window=1, pool=pool)   # budget = 1 x 2MB
        try:
            # 4MB floats: footprint 4MB > 2MB budget, <= 8MB capacity
            h.client.write_device_payload(
                [jnp.zeros((1 << 20,), jnp.float32)])
            assert h.client.outstanding_batches == 1
            b = h.take(h.server_conn)
            assert b is not None and b[0].nbytes == 4 << 20
        finally:
            h.close()

    def test_oversized_batch_fails_loudly(self):
        """A batch bigger than the peer's whole budget could NEVER be
        admitted (pool.reserve rejects footprints over capacity) — the
        sender must fail it at the source, not wedge the lane."""
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=16 << 10)
        h = _ConnHarness(window=4, pool=pool)
        try:
            # 64K of floats -> 64K-class footprint > 16K budget
            with pytest.raises(ConnectionError, match="exceeds the"):
                h.client.write_device_payload(
                    [jnp.zeros((16 << 10,), jnp.float32)])
        finally:
            h.close()


class TestPoisonedLane:
    def test_pre_hello_oversized_poisons_connection(self):
        """An unsendable batch that slips past the write-time check
        (peer unknown) poisons the whole connection at flush time — no
        later frame may follow it, or the receiver would FIFO-match
        another RPC's arrays to the dead RPC's envelope."""
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=16 << 10)
        tr = ici.IciTransport(window=4, pool=pool)
        holder = []
        evt = threading.Event()
        listener = tr.listen(
            str2endpoint("ici://127.0.0.1:0"),
            lambda c: (holder.append(c), evt.set()))
        client = tr.connect(
            str2endpoint(f"ici://127.0.0.1:{listener.endpoint.port}"))
        try:
            if client.peer_info is None:
                # 64K floats -> 64K footprint > 16K pool capacity, but
                # the peer is unknown yet so the write is accepted
                client.write_device_payload(
                    [jnp.zeros((16 << 10,), jnp.float32)])
                deadline = time.monotonic() + 5
                while (client._poisoned is None
                       and time.monotonic() < deadline):
                    try:
                        _ConnHarness.pump(client)
                    except ConnectionError:
                        break
                    time.sleep(0.01)
                assert client._poisoned is not None
                with pytest.raises(ConnectionError):
                    client.write(memoryview(b"x"))
                with pytest.raises(ConnectionError):
                    client.write_device_payload(
                        [jnp.zeros((4,), jnp.float32)])
        finally:
            client.close()
            evt.wait(5)
            for c in holder:
                c.close()
            listener.stop()


class TestLaneLifecycle:
    def test_close_reclaims_local_exchange_after_grace(self):
        """Entries survive close() for a grace period (the peer may
        still take a just-flushed descriptor), then the sweep drops
        them."""
        import jax.numpy as jnp
        h = _ConnHarness(window=4)
        h.client.write_device_payload([jnp.zeros((4,), jnp.float32)])
        uids = list(h.client._issued_uids)
        assert uids and all(u in ici._local_exchange for u in uids)
        h.close()
        # still takeable within the grace window
        assert all(u in ici._local_exchange for u in uids)
        # after the grace deadline the sweep reclaims
        ici._sweep_reclaim(now=time.monotonic() + ici._reclaim_grace_s() + 1)
        assert all(u not in ici._local_exchange for u in uids)

    def test_staged_lane_reserves_pool(self):
        """The staged fallback is subject to the same HBM admission as
        the pull path — a peer without a transfer server can't escape
        the budget."""
        import jax.numpy as jnp
        pool = DeviceRecvPool(capacity_bytes=4 << 20)
        h = _ConnHarness(window=4, pool=pool)
        try:
            # make the client see a cross-process peer with no pull
            # support: the next lane batch goes out as F_STAGED
            h.client.peer_info = dict(h.client.peer_info,
                                      proc="elsewhere", can_pull=False)
            h.client.write_device_payload([jnp.zeros((16,), jnp.float32)])
            batch = h.take(h.server_conn)
            assert batch is not None
            assert pool.used == 8 << 10
            del batch
            gc.collect()
            deadline = time.monotonic() + 5
            while pool.used != 0:
                gc.collect()
                assert time.monotonic() < deadline, "finalizer never ran"
                time.sleep(0.05)
        finally:
            h.close()

    def test_transfer_lane_status_exposed(self):
        s = ici.transfer_lane_status()
        assert s == "up" or s.startswith("down") or s == "not started"


# ------------------------------------------------------- cross process

def _spawn_server(extra_env=None):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)      # script sets its own
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "ici_echo_server.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
    try:
        port = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server died: {proc.stderr.read()[-2000:]}")
        assert port, "server never printed its port"
    except BaseException:
        # don't orphan the child when startup fails before the caller's
        # try/finally takes ownership
        proc.kill()
        proc.wait(10)
        raise
    return proc, port


class TestIciCrossProcess:
    def _roundtrip(self, extra_env=None, expect_lane=None):
        proc, port = _spawn_server(extra_env)
        try:
            import jax.numpy as jnp
            ch = Channel(f"ici://127.0.0.1:{port}#reply_device=4")
            arr = jnp.arange(256, dtype=jnp.float32)
            cntl = ch.call_sync("EchoService", "EchoDevice", b"",
                                cntl=None, request_device_arrays=[arr])
            assert not cntl.failed(), cntl.error_text
            out = cntl.response_device_arrays[0]
            assert hasattr(out, "devices")
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(arr) * 2)
            if expect_lane is not None:
                sock = ch._socket
                assert sock.conn.lane_kind == expect_lane
            ch.close()
        finally:
            proc.terminate()
            proc.wait(10)

    def test_cross_process_pjrt_pull(self):
        """Device payload crosses a process boundary via PjRt pull DMA —
        no numpy round-trip on the data path (VERDICT #1's done bar)."""
        self._roundtrip(expect_lane="pjrt-pull")

    def test_cross_process_staged_fallback(self):
        env = {"BRPC_TPU_ICI_FORCE_STAGED": "1"}
        old = os.environ.get("BRPC_TPU_ICI_FORCE_STAGED")
        os.environ["BRPC_TPU_ICI_FORCE_STAGED"] = "1"
        try:
            self._roundtrip(extra_env=env, expect_lane="staged")
        finally:
            if old is None:
                os.environ.pop("BRPC_TPU_ICI_FORCE_STAGED", None)
            else:
                os.environ["BRPC_TPU_ICI_FORCE_STAGED"] = old


# ------------------------------------------------------------- framing

class TestFraming:
    def test_descriptor_roundtrip(self):
        import jax.numpy as jnp
        arrs = [jnp.zeros((3, 4), jnp.float32),
                jnp.ones((7,), jnp.int32)]
        wire = ici._encode_descriptor(77, arrs)
        uid, specs = ici._decode_descriptor(wire)
        assert uid == 77
        assert specs[0] == {"dtype": "float32", "shape": (3, 4),
                            "nbytes": 48}
        assert specs[1]["shape"] == (7,)

    def test_frame_header_carries_ack(self):
        hdr = ici._HDR.pack(ici.F_BYTES, 12345, 4)
        ftype, ack, length = ici._HDR.unpack(hdr)
        assert (ftype, ack, length) == (0, 12345, 4)


class TestLaneLifecycleSoak:
    def test_connect_transfer_close_cycles_return_to_baseline(self):
        """Verdict r4 task: cycle connect/transfer/close many times and
        assert the same-process exchange and the recv pool return to
        baseline — a long-lived server must not accumulate pinned
        entries from dead connections (block_pool.cpp:271-340 freelist
        hygiene). Grace shortened via the ici_reclaim_grace_s flag so
        expired entries reclaim within the test's patience."""
        import jax.numpy as jnp
        from brpc_tpu.butil.flags import flag, set_flag

        old_grace = flag("ici_reclaim_grace_s")
        set_flag("ici_reclaim_grace_s", 0.2)
        server = make_echo_server()
        ep = server.start(f"ici://127.0.0.1:0#device=0")
        try:
            arr = jnp.arange(256, dtype=jnp.float32)
            for i in range(60):
                ch = Channel(f"ici://127.0.0.1:{ep.port}")
                cntl = ch.call_sync("EchoService", "EchoDevice", b"",
                                    request_device_arrays=[arr])
                assert not cntl.failed(), f"cycle {i}: {cntl.error_text}"
                ch.close()
            # wait past the grace, then force a sweep: every closed
            # connection's exchange entries must be gone
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                ici._sweep_reclaim()
                with ici._local_lock:
                    n = len(ici._local_exchange)
                if n == 0:
                    break
                time.sleep(0.1)
            with ici._local_lock:
                leftover = len(ici._local_exchange)
            assert leftover == 0, \
                f"{leftover} exchange entries pinned after 60 cycles"
            assert not ici._reclaim_queue, \
                f"reclaim queue not drained: {len(ici._reclaim_queue)}" 
        finally:
            set_flag("ici_reclaim_grace_s", old_grace)
            server.stop()
            server.join(2)

    def test_pull_leak_circuit_breaker(self):
        """Global cap: once the process-wide leaked-pull estimate
        crosses it, EVERY peer refuses the pull lane (bounded HBM
        footprint; the transfer API has no cancel so degradation is
        the only bound)."""
        old = ici._leaked_pull_bytes[0]
        old_logged = ici._leak_breaker_logged[0]
        try:
            ici._leaked_pull_bytes[0] = ici._LEAK_GLOBAL_CAP_BYTES + 1
            assert ici._pull_lane_allowed("any-peer") is False
            assert ici._pull_lane_allowed() is False
            ici._leaked_pull_bytes[0] = 0
            assert ici._pull_lane_allowed("any-peer") is True
        finally:
            ici._leaked_pull_bytes[0] = old
            ici._leak_breaker_logged[0] = old_logged

    def test_pull_leak_breaker_per_peer_epoch(self):
        """The round-4 ratchet fix: one flapping peer crossing the
        per-epoch cap degrades ONLY itself — a second peer keeps the
        pull lane, and the flapper's restart (fresh epoch uuid in its
        hello) recovers it. The global counter keeps every byte (dead
        epochs' registrations stay pinned; no honest decay exists)."""
        old_global = ici._leaked_pull_bytes[0]
        saved = dict(ici._leaked_by_epoch)
        try:
            ici._leaked_pull_bytes[0] = 0
            ici._leaked_by_epoch.clear()
            flapper, healthy = "epoch-A1", "epoch-B"
            # flap peer A past its per-epoch cap in three closes
            with ici._local_lock:
                for _ in range(3):
                    ici._note_leaked(flapper,
                                     ici._LEAK_CAP_BYTES // 2 + 1)
            assert ici._pull_lane_allowed(flapper) is False
            # the healthy peer is untouched
            assert ici._pull_lane_allowed(healthy) is True
            # peer A restarts: its new process uuid is a new epoch with
            # a clean record — the breaker recovers on reconnect
            assert ici._pull_lane_allowed("epoch-A2") is True
            # the global estimate still carries the dead epoch's bytes
            assert ici._leaked_pull_bytes[0] >= ici._LEAK_CAP_BYTES
            # per-epoch bookkeeping stays bounded
            with ici._local_lock:
                for i in range(5000):
                    ici._note_leaked(f"ep-{i}", 1)
            assert len(ici._leaked_by_epoch) <= 4096
        finally:
            ici._leaked_pull_bytes[0] = old_global
            ici._leaked_by_epoch.clear()
            ici._leaked_by_epoch.update(saved)
