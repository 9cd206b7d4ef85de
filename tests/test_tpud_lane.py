"""tpud:// staged lane from inside one process: the tracked
``write_device_payload`` stamps the ``staged-dcn`` cell, a ``device_put``
that raises is counted and loud, a full out-buffer keeps batch and
envelope a pair, and a batch that never left settles its tracker."""

import socket as pysocket
import threading
import time

import numpy as np
import pytest

from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                          Service)
from brpc_tpu.transport import device_stats, syscall_stats, tpud
from brpc_tpu.transport.tcp import TcpConn


@pytest.fixture
def stats_on():
    old = flag("device_stats_enabled")
    set_flag("device_stats_enabled", True)
    yield
    set_flag("device_stats_enabled", old)


def _serve(handler):
    svc = Service("Lane")
    svc.register_method("Twice", handler)
    server = Server(ServerOptions(enable_builtin_services=False))
    server.add_service(svc)
    return server, server.start("tpud://127.0.0.1:0#device=0")


def _twice(cntl, request):
    cntl.response_device_arrays = [
        np.asarray(a) * 2 for a in cntl.request_device_arrays]
    return bytes(request)


def _tpud_counters():
    snap = syscall_stats.snapshot()
    return {k: snap[k] for k in syscall_stats.TPUD_COUNTERS}


def _staged_cells():
    return {k: r for k, r in device_stats.device_page_payload(
        samples=0)["cells"].items() if k.endswith("|staged-dcn")}


def _conn_pair():
    lis = pysocket.socket()
    lis.bind(("127.0.0.1", 0))
    lis.listen(1)
    port = lis.getsockname()[1]
    a = pysocket.create_connection(("127.0.0.1", port))
    b, _ = lis.accept()
    lis.close()
    ep = str2endpoint(f"tcp://127.0.0.1:{port}")
    return (tpud.TpudConn(TcpConn(a, ep, ep), ep, ep, None),
            tpud.TpudConn(TcpConn(b, ep, ep), ep, ep, None))


def test_tracked_batch_stamps_stage_wire_and_recv(stats_on):
    """The Socket hands the batch's tracker through: stage ends at the
    encode, wire when TCP has the last byte, the take (decode and put)
    is the receiver's recv time; every cell balances. The writer is a
    keep_write fiber, as before (the gathered write in the claiming
    context was measured and lost: PERF.md section 6, PR 37)."""
    import jax  # noqa: F401 - with jax loaded the take puts on a device

    server, ep = _serve(_twice)
    ch = Channel(str(ep), ChannelOptions(timeout_ms=20000))
    before = syscall_stats.snapshot()
    cells_before = _staged_cells()
    try:
        x = np.arange(1 << 18, dtype=np.float32)        # 1 MB
        for i in range(4):
            cntl = ch.call_sync("Lane", "Twice", b"t%d" % i,
                                request_device_arrays=[x + i])
            assert not cntl.failed(), cntl.error_text
            assert np.array_equal(np.asarray(cntl.response_device_arrays[0]),
                                  (x + i) * 2)
        assert ch._socket._lane_tracked and ch._socket._conn_flush is None
        assert ch._socket.write_fiber_spawns >= 1 \
            and ch._socket.write_inplace == 0
        deadline = time.monotonic() + 5
        while True:
            used = [r for k, r in _staged_cells().items()
                    if k not in cells_before]
            if all(r["completed"] == 4 for r in used) \
                    or time.monotonic() >= deadline:
                break
            time.sleep(0.01)
        assert len(used) == 2          # the client's cell and the server's
        for row in used:
            assert row["transfers"] == row["completed"] == 4
            assert not row["failed"] and not row["leaked_bytes"]
            assert row["stage_us_sum"] > 0 and row["wire_us_sum"] > 0
            assert row["recv_us_sum"] > 0 and row["recv_transfers"] == 4
        after = syscall_stats.snapshot()
        assert after["tpud_batches_out"] - before["tpud_batches_out"] == 8
        assert after["tpud_batches_in"] - before["tpud_batches_in"] == 8
        assert after["tpud_bytes_out"] - before["tpud_bytes_out"] > 8 << 20
        assert after["tpud_decode_us"] > before["tpud_decode_us"]
        assert after["tpud_put_us"] > before["tpud_put_us"]
    finally:
        ch.close()
        server.stop()
        server.join(2)


def test_a_device_put_that_raises_is_counted_and_loud(monkeypatch):
    """With jax loaded a handler never gets numpy in a device array's
    place unnoticed: the put's failure is counted and fails the
    connection, and the caller sees a failed call."""
    import jax

    handled = []

    def handler(cntl, request):
        handled.append([type(a).__name__
                        for a in cntl.request_device_arrays])
        return _twice(cntl, request)

    server, ep = _serve(handler)
    ch = Channel(str(ep), ChannelOptions(timeout_ms=3000, max_retry=0))
    try:
        x = np.arange(64, dtype=np.float32)
        ok = ch.call_sync("Lane", "Twice", b"a", request_device_arrays=[x])
        assert not ok.failed(), ok.error_text
        assert handled == [["ArrayImpl"]]
        before = _tpud_counters()["tpud_put_fallbacks"]

        def boom(*_a, **_kw):
            raise RuntimeError("no room on the device")
        monkeypatch.setattr(jax, "device_put", boom)
        bad = ch.call_sync("Lane", "Twice", b"b", request_device_arrays=[x])
        monkeypatch.undo()
        assert bad.failed()
        assert len(handled) == 1            # the handler never ran on numpy
        assert _tpud_counters()["tpud_put_fallbacks"] == before + 1
    finally:
        ch.close()
        server.stop()
        server.join(2)


def test_a_full_out_buffer_keeps_batch_and_envelope_a_pair(monkeypatch):
    """The server stops reading until the client's out-buffer has
    refused frames (``tpud_out_full``). A refused batch is refused
    BEFORE anything is staged: its call fails with the refusal and its
    envelope stays home; a refused envelope waits for the writable event
    behind its batch. So every call that comes back good has its OWN
    array, and no batch is left to pair with a later call's envelope."""
    monkeypatch.setattr(tpud, "_MAX_OUT", 1 << 20)
    server, ep = _serve(_twice)
    ch = Channel(str(ep), ChannelOptions(timeout_ms=60000, max_retry=0))
    n, results, done = 40, {}, threading.Event()
    try:
        warm = ch.call_sync("Lane", "Twice", b"w", request_device_arrays=[
            np.zeros(4, np.int32)])
        assert not warm.failed(), warm.error_text
        (srv_sock,) = server.connections()
        srv_sock.conn._inner.pause_read_events()    # the reader stalls
        before = _tpud_counters()["tpud_out_full"]

        def on_done(i):
            def cb(cntl):
                results[i] = (cntl.failed(), cntl.error_text, None
                              if cntl.failed() else
                              np.asarray(cntl.response_device_arrays[0]))
                if len(results) == n:
                    done.set()
            return cb
        for i in range(n):          # 40 x 512 KB: more than TCP holds
            ch.call("Lane", "Twice", b"c%d" % i, done=on_done(i),
                    request_device_arrays=[
                        np.full(1 << 17, i + 1, dtype=np.int32)])
        deadline = time.monotonic() + 10
        while _tpud_counters()["tpud_out_full"] == before:
            assert time.monotonic() < deadline, "no frame was refused"
            time.sleep(0.01)
        srv_sock.conn._inner.resume_read_events()
        assert done.wait(60), f"{len(results)} of {n} calls came back"
        good = 0
        for i in range(n):
            failed, text, out = results[i]
            if failed:
                assert "out-buffer full" in text, text
                continue
            good += 1
            assert out.shape == (1 << 17,) and out[0] == 2 * (i + 1) \
                and out[-1] == 2 * (i + 1)
        assert good >= 8            # what TCP and 1 MB of buffer held
        # the connection is whole: the next call pairs with its own batch
        last = ch.call_sync("Lane", "Twice", b"z", request_device_arrays=[
            np.full(8, 77, dtype=np.int32)])
        assert not last.failed(), last.error_text
        assert np.asarray(last.response_device_arrays[0])[0] == 154
    finally:
        ch.close()
        server.stop()
        server.join(2)


def test_a_batch_that_never_left_fails_its_tracker(stats_on, monkeypatch):
    """Conn level: the mark of a staged batch settles as completed when
    TCP has its last byte, as failed when the conn closes first; a
    refusal stages nothing and leaves the tracker open."""
    a, b = _conn_pair()
    try:
        cell = device_stats.global_device_stats().device_cell(
            "test-peer", "staged-dcn")
        x = np.arange(1024, dtype=np.float32)
        t1 = device_stats.open_transfer("test-peer", "staged-dcn", x.nbytes,
                                        cell=cell)
        a.write_device_payload([x], tracker=t1, flush=False)
        assert cell.get_value()["completed"] == 0     # nothing pushed yet
        a.write(memoryview(b"envelope"))              # pushes both
        row = cell.get_value()
        assert row["transfers"] == 1 and row["completed"] == 1
        buf = bytearray(64)
        deadline = time.monotonic() + 5
        while True:
            try:
                n = b.read_into(memoryview(buf))
                break
            except BlockingIOError:
                assert time.monotonic() < deadline
                time.sleep(0.001)
        assert bytes(buf[:n]) == b"envelope"
        (got,) = b.take_device_payload()
        assert np.array_equal(np.asarray(got), x)
        assert b.take_device_payload() is None
        # a refusal: nothing staged, the tracker still open
        monkeypatch.setattr(tpud, "_MAX_OUT", -1)
        t2 = device_stats.open_transfer("test-peer", "staged-dcn", x.nbytes,
                                        cell=cell)
        with pytest.raises(BlockingIOError):
            a.write_device_payload([x], tracker=t2, flush=False)
        assert not a._marks and cell.get_value()["completed"] == 1
        monkeypatch.undo()
        # staged, then the conn closes before a flush
        a.write_device_payload([x], tracker=t2, flush=False)
        a.close()
        row = cell.get_value()
        assert row["transfers"] == 2 and row["failed"] == 1
        assert row["completed"] + row["failed"] == row["transfers"]
    finally:
        a.close()
        b.close()
