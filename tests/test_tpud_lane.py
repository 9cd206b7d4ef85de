"""tpud:// staged lane from inside one process: the tracked
``write_device_payload`` stamps the ``staged-dcn`` cell, a ``device_put``
that raises is counted and loud, a full out-buffer keeps batch and
envelope a pair, and a batch that never left settles its tracker."""

import socket as pysocket
import struct
import sys
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


# ------------------------------------------- the batch's bytes, uncopied

def _wire(arrays) -> bytes:
    """The frame as the format spells it, field by field: the envelope,
    the count, then per array its dtype's name, rank, shape, length and
    C-order bytes, all big-endian."""
    body = len(arrays).to_bytes(2, "big")
    for arr in arrays:
        arr = np.asarray(arr)
        dt = str(arr.dtype).encode()
        body += bytes([len(dt)]) + dt + bytes([arr.ndim])
        body += b"".join(d.to_bytes(8, "big") for d in arr.shape)
        body += arr.nbytes.to_bytes(8, "big") + arr.tobytes()
    return tpud._HDR.pack(tpud._F_DEVICE, len(body)) + body


def _frame(arrays, keep=True):
    segs, length, copied = tpud._batch_segments(arrays, keep)
    return ([tpud._HDR.pack(tpud._F_DEVICE, length)] + segs, length,
            copied)


def _copied():
    snap = syscall_stats.snapshot()
    return snap["tpud_copied_bytes_out"], snap["tpud_copied_bytes_in"]


@pytest.mark.parametrize("rank", [0, 1, 2, 3])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int8", "bool"])
def test_the_frames_segments_join_to_the_encoded_batch(dtype, rank):
    """The wire format is byte for byte ``_HDR`` + ``_encode_device_batch``
    (which ici:// keeps using), whatever dtype and rank: the segments
    are the fields as bytes and each array's data by reference."""
    shape = (3, 4, 5)[:rank]
    arr = (np.arange(int(np.prod(shape))) % 5).astype(
        tpud._np_dtype(dtype)).reshape(shape)
    arr.flags.writeable = False
    arrays = [arr, np.zeros((0, 2), np.float32), arr]
    segs, length, copied = _frame(arrays)
    assert b"".join(bytes(s) for s in segs) == _wire(arrays)
    assert length == len(_wire(arrays)) - tpud._HDR.size and copied == 0
    assert any(isinstance(s, memoryview) for s in segs) == (arr.nbytes > 0)
    assert tpud._encode_device_batch(arrays) == _wire(arrays)[
        tpud._HDR.size:]


def test_the_wire_format_is_the_fixed_bytes():
    """One batch against its bytes written out by hand: count, then
    per array dtype length and name, rank, shape, length, data."""
    arrays = [np.array([[1.0, 2.0]], np.float32), np.array(True)]
    want = (b"\x00\x02"
            b"\x07float32\x02" + (1).to_bytes(8, "big")
            + (2).to_bytes(8, "big") + (8).to_bytes(8, "big")
            + b"\x00\x00\x80\x3f\x00\x00\x00\x40"
            b"\x04bool\x00" + (1).to_bytes(8, "big") + b"\x01")
    assert tpud._encode_device_batch(arrays) == want
    assert [np.array_equal(g, w) and g.dtype == w.dtype for g, w in zip(
        tpud._decode_device_batch(want), arrays)] == [True, True]


def test_a_non_contiguous_array_is_made_contiguous_once():
    base = np.arange(80, dtype=np.int32).reshape(8, 10)
    base.flags.writeable = False
    cut = base[:, ::3]
    segs, _length, copied = _frame([cut, base])
    assert b"".join(bytes(s) for s in segs) == _wire([cut, base])
    assert copied == cut.nbytes


class _FakeInner:
    """The TCP conn under a TpudConn, scripted. Reads: ``feed(n)`` lets
    the next ``n`` bytes of ``rx`` be read, then EAGAIN; past its end,
    EOF. Writes: ``takes`` gives what each ``writev`` takes, an EAGAIN
    between any two; what was taken is ``tx``."""
    level_triggered = True

    def __init__(self, rx=b"", takes=None):
        self.rx, self.pos, self.avail = memoryview(rx), 0, 0
        self.takes, self.tx, self.eagain = takes, bytearray(), False
        self.writes = 0

    def pause_read_events(self):
        pass

    resume_read_events = request_writable_event = close = pause_read_events

    def feed(self, n):
        self.avail = n

    def read_into(self, mv):
        if self.pos == len(self.rx):
            return 0
        n = min(len(mv), self.avail, len(self.rx) - self.pos)
        if not n:
            raise BlockingIOError
        mv[:n] = self.rx[self.pos:self.pos + n]
        self.pos += n
        self.avail -= n
        return n

    def peek_closed(self):
        return self.pos == len(self.rx)

    def writev(self, views):
        self.writes += 1
        if self.takes is not None:
            self.eagain = not self.eagain
            if self.eagain:
                raise BlockingIOError
        data = b"".join(bytes(v) for v in views)
        n = len(data) if self.takes is None else min(len(data),
                                                     next(self.takes))
        self.tx += data[:n]
        return n


def _ep():
    return str2endpoint("tcp://127.0.0.1:1")


@pytest.mark.parametrize("how", ["read_only", "writeable", "non_contiguous"])
def test_only_an_array_its_owner_can_change_is_copied(how):
    """A read-only array goes to the kernel by reference; a writeable
    one is snapshotted once at the hand-over (counted), so changing it
    right after does not change what the peer decodes."""
    x = np.arange(4096, dtype=np.float32).reshape(64, 64)
    want = x.copy()
    arr = {"read_only": x, "writeable": x, "non_contiguous": x[:, ::2]}[how]
    if how == "read_only":
        x.flags.writeable = False
    inner = _FakeInner()
    conn = tpud.TpudConn(inner, _ep(), _ep(), None)
    hello = bytes(inner.tx)
    out0, _ = _copied()
    conn.write_device_payload([arr], flush=False)
    assert _copied()[0] - out0 == (0 if how == "read_only" else arr.nbytes)
    if how != "read_only":
        x[:] = -1                   # the owner changes it under the send
    conn._flush()
    stream = bytes(inner.tx[len(hello):])
    body = stream[tpud._HDR.size:]
    (got,) = tpud._decode_device_batch(body)
    assert np.array_equal(got, want if how == "read_only" else
                          (want if how == "writeable" else want[:, ::2]))


@pytest.mark.parametrize("dtype,in_place", [
    ("float32", False), ("bfloat16", True), ("int8", True)])
def test_a_decoded_array_is_aligned_for_its_dtype(dtype, in_place,
                                                  monkeypatch):
    """float32 at rank 2 starts at byte 35 of its batch, so the decode
    gives it an aligned buffer of its own and counts the copy in
    ``tpud_copied_bytes_in``; bf16 at rank 2 (byte 36) and int8 are
    read in place, in the frame's buffer and in ``bytes`` alike."""
    monkeypatch.delitem(sys.modules, "jax", raising=False)  # numpy back
    x = (np.arange(64 * 64) % 7).astype(tpud._np_dtype(dtype))
    x = x.reshape(64, 64)
    stream = tpud._HDR.pack(tpud._F_HELLO, 2) + b"{}" + _wire([x])
    inner = _FakeInner(stream)
    inner.feed(len(stream))
    conn = tpud.TpudConn(inner, _ep(), _ep(), None)
    conn._pump()
    _, in0 = _copied()
    (got,) = conn.take_device_payload()
    assert got.flags.aligned and np.array_equal(got, x)
    assert got.flags.owndata != in_place
    assert _copied()[1] - in0 == (0 if in_place else x.nbytes)
    (dec,) = tpud._decode_device_batch(_wire([x])[tpud._HDR.size:])
    assert dec.flags.aligned and np.array_equal(dec, x)


def _split_stream(seed):
    """A peer's stream: its hello, then batches with app frames between
    them; each app frame is the number of batches before it."""
    rng = np.random.default_rng(seed)
    parts = [tpud._HDR.pack(tpud._F_HELLO, 2) + b"{}"]
    batches = []
    for size in (5, 3000, 10000, 0, 6000, 9000, 17):
        if size:
            arrays = [rng.integers(0, 255, size, np.uint8)]
            if size == 6000:
                arrays.append(np.arange(12, dtype=np.float32).reshape(3, 4))
            batches.append(arrays)
            parts.append(_wire(arrays))
        msg = struct.pack(">Q", len(batches))
        parts.append(tpud._HDR.pack(tpud._F_BYTES, len(msg)) + msg)
    return b"".join(parts), batches


@pytest.mark.parametrize("piece", ["1", "5", "7", "65536", "random"])
def test_a_stream_cut_anywhere_delivers_every_batch_and_byte_in_order(
        piece, monkeypatch):
    """The stream arrives in pieces of 1, 5, 7 and 64 KB and seeded random
    sizes (a 4 KB scratch, so a batch fills its own buffer across
    reads and pumps): every batch and every app byte is delivered, in
    order; a batch is filed before the app bytes behind it reach the
    app buffer; a half-filled frame keeps ``peek_closed`` False; the
    only batch bytes copied are the heads the header's read took in."""
    monkeypatch.setattr(tpud, "_READ", 4096)
    monkeypatch.delitem(sys.modules, "jax", raising=False)  # numpy back
    stream, batches = _split_stream(38)
    rng = np.random.default_rng(380)
    inner = _FakeInner(stream)
    conn = tpud.TpudConn(inner, _ep(), _ep(), None)
    _, in0 = _copied()
    app, taken, buf, half = bytearray(), [], bytearray(64), 0
    while inner.pos < len(stream):
        n = int(rng.integers(1, 9000)) if piece == "random" else int(piece)
        inner.feed(n)
        conn._pump()
        if conn._frame is not None:
            half += 1
            assert conn.peek_closed() is False
        filed = len(taken) + len(conn._lane)
        while conn._appbuf:
            k = conn.read_into(memoryview(buf))
            app += buf[:k]
        for pos in range(0, len(app) - len(app) % 8, 8):
            (before,) = struct.unpack_from(">Q", app, pos)
            assert before <= filed       # its batch was filed first
        while (got := conn.take_device_payload()) is not None:
            taken.append(got)
    assert conn.peer_info == {} and (half > 0) == (piece != "65536")
    assert len(taken) == len(batches)
    for got, want in zip(taken, batches):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and np.array_equal(g, w)
            assert g.flags.writeable        # numpy without jax: its own
    assert [struct.unpack_from(">Q", app, p)[0]
            for p in range(0, len(app), 8)] == [1, 2, 3, 3, 4, 5, 6]
    assert conn.read_into(memoryview(buf)) == 0 and conn.peek_closed()
    lengths = [len(_wire(b)) - tpud._HDR.size for b in batches]
    assert _copied()[1] - in0 <= sum(min(n, 4096) for n in lengths)


@pytest.mark.parametrize("most", [1, 7, 5000, "random"])
def test_partial_sends_keep_the_stream_and_stamp_each_batch_at_its_end(
        most, monkeypatch):
    """``sendmsg`` takes 1..N bytes a call with EAGAIN between calls:
    the bytes that leave are the frames, whole and in order; a batch's
    tracker is stamped when its last byte has left, not before;
    ``close()`` fails the trackers of batches not wholly sent; and the
    out-buffer refuses a batch on its byte count before staging it."""
    rng = np.random.default_rng(381)
    most = 3000 if most == "random" else most
    takes = iter(lambda: int(rng.integers(1, most + 1)), None)
    inner = _FakeInner(takes=takes)
    conn = tpud.TpudConn(inner, _ep(), _ep(), None)
    stamps = []

    class Tracker:
        def __init__(self, end):
            self.end = end

        def lane_encoded(self):
            pass

        def lane_flushed(self):
            stamps.append((self.end, len(inner.tx)))

        def lane_acked(self):
            pass

        def lane_failed(self, why):
            stamps.append((self.end, None))

    want = bytearray(tpud._HDR.pack(tpud._F_HELLO, len(
        tpud._hello_payload(None))) + tpud._hello_payload(None))
    for i in range(6):
        x = np.full(700 + i, i, np.uint8)
        x.flags.writeable = False
        want += _wire([x])
        conn.write_device_payload([x], tracker=Tracker(len(want)),
                                  flush=False)
        conn.write(memoryview(b"env%d" % i))
        want += tpud._HDR.pack(tpud._F_BYTES, 4) + b"env%d" % i
    while not (conn._flush() and conn._flush()):
        pass
    assert bytes(inner.tx) == bytes(want) and not conn._out
    assert conn._out_bytes == 0
    # stamped by the send that took the batch's last byte
    assert len(stamps) == 6 and all(end <= at < end + most
                                    for end, at in stamps)
    # refused on the byte count, before anything is staged
    monkeypatch.setattr(tpud, "_MAX_OUT", 2000)
    inner.takes = iter(lambda: 0, None)     # nothing leaves any more
    x = np.zeros(1500, np.uint8)
    x.flags.writeable = False
    conn.write_device_payload([x], tracker=Tracker(-1), flush=False)
    conn.write_device_payload([x], tracker=Tracker(-2), flush=False)
    staged = conn._out_bytes
    assert staged > 2000
    with pytest.raises(BlockingIOError):
        conn.write_device_payload([x], tracker=Tracker(-3), flush=False)
    assert conn._out_bytes == staged and len(conn._marks) == 2
    conn.close()
    assert stamps[6:] == [(-1, None), (-2, None)]
