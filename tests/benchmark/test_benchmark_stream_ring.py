"""The streaming ring: the real service file against the plain
reference on four virtual CPU devices at small sizes, what must make the
cell's ``correct`` false, the reference and its credit model by
themselves, and the join of a frame's two span halves."""

import threading
from types import SimpleNamespace

import numpy as np

from bench_testlib import last_line, run_cell

CELL = "streaming_echo.ring_2mb_w8"


def test_ring_of_four_matches_the_reference():
    """Order, exactly once, values and placement, through the service
    the cell runs."""
    import jax

    from benchmark.lib.loader import Cell, load_module
    from benchmark.lib.stamps import Stamps, tag_of
    from benchmark.reference.stream_ring import ring_reference
    from benchmark.run import Context
    from brpc_tpu.butil.flags import flag, set_flag

    spans_before = flag("rpcz_max_spans")
    cell = Cell(CELL, rehearse=True)
    stamps = Stamps(trace=False)
    dep = load_module("services", cell.config["service"]).build(
        Context(cell, 11, jax.devices()[:4], stamps, ""))
    frames = 40
    try:
        dep.prepare()
        dep.start()
        assert dep.warm() == dep.pool
        came, all_in = [], threading.Event()

        def done(c):
            came.append(c)
            if len(came) == frames:
                all_in.set()
        seqs = list(range(dep.first_seq, dep.first_seq + frames))
        for seq in seqs:
            dep.call(seq, done)
        assert all_in.wait(30)
        assert [c.seq for c in came] == seqs        # in order, once each
        want = ring_reference(
            [(tag_of(s), dep.frames[s % dep.pool]) for s in seqs], 4)
        for c, (tag, expected) in zip(came, want):
            (got,) = dep.response_arrays(c.seq, c)
            assert c.tag == tag and got.devices() == {jax.devices()[0]}
            np.testing.assert_array_equal(
                np.asarray(got).astype(np.float32), np.asarray(expected))
            dep.verify(c.seq, c, [got])
        assert dep.finish() == 0 and dep.describe()["ring_problems"] == []
        # every peer stamped every frame once, on its own chip's turn
        by_seq = {}
        for seq, peer, _t0, _t1 in stamps.handlers:
            by_seq.setdefault(seq, []).append(peer)
        assert all(by_seq[s] == [1, 2, 3, 0] for s in seqs)
        edges = dep.describe()["edges"]
        assert all(e["data_frames_out"] == e["data_frames_in"]
                   == dep.pool + frames and e["credit_parks"] == 0
                   for e in edges)
    finally:
        dep.close()
        set_flag("rpcz_max_spans", spans_before)


def test_a_corrupted_frame_is_not_correct():
    proc = run_cell(CELL, "--inject", "corrupt_response")
    res = last_line(proc)
    assert res["correct"] is False and res["failed"] > 0
    assert proc.returncode != 0
    assert "differ from the reference" in proc.stdout


def test_reference_and_credit_model():
    import jax.numpy as jnp

    from benchmark.reference.stream_ring import credit_model, ring_reference

    a = np.arange(-8, 8, dtype=np.float32).reshape(4, 4)
    out = ring_reference([(b"t0", jnp.asarray(a, jnp.bfloat16)),
                          (b"t1", jnp.asarray(-a, jnp.bfloat16))], 4)
    assert [t for t, _ in out] == [b"t0", b"t1"]
    np.testing.assert_array_equal(np.asarray(out[0][1]), a + 4)
    np.testing.assert_array_equal(np.asarray(out[1][1]), -a + 4)
    assert out[0][1].dtype == jnp.float32
    # the cell's data (integers to +-100) through the ring in bf16 equal
    # the reference exactly; in the next precision below, a float8, they
    # do not: the exact limit tells the two apart
    x = jnp.arange(-100, 101, dtype=jnp.float32)
    (_, want), = ring_reference([(b"t", x.astype(jnp.bfloat16))], 4)

    def through_ring(dtype):
        y = x.astype(dtype)
        for _ in range(4):
            y = y + jnp.asarray(1, dtype)
        return np.asarray(y.astype(jnp.float32))
    np.testing.assert_array_equal(through_ring(jnp.bfloat16),
                                  np.asarray(want))
    assert np.abs(through_ring(jnp.float8_e4m3fn)
                  - np.asarray(want)).max() >= 1
    # a window of 4 under grants in sixteens: only the frame that takes
    # the last credit brings a grant
    assert credit_model(64, 4, 16) == {
        "grant_frames": 16, "credit_parks": 15, "ungranted_frames_max": 4}
    # the default window: a grant every sixteen, the writer never dry
    # for long: 64 out, then one park a grant
    assert credit_model(64, 64, 16) == {
        "grant_frames": 4, "credit_parks": 0, "ungranted_frames_max": 64}
    assert credit_model(8, 64, 16)["grant_frames"] == 0


def test_frame_halves_join_into_three_stages():
    from benchmark.lib.stream_frames import STAGES, join_frames, stages_of

    def send(seq, start, done, sid=7, err=0):
        return SimpleNamespace(side="stream", service="stream-send",
                               stream_id=sid, frame_seq=seq, start_us=start,
                               write_done_us=done, error_code=err)

    def recv(seq, received, deliver, sid=7):
        return SimpleNamespace(side="stream", service="stream-recv",
                               stream_id=sid, frame_seq=seq,
                               received_us=received, deliver_start_us=deliver)
    assert STAGES == ("write", "wire", "deliver")
    # the writer stamped first: all three stages
    assert stages_of(send(1, 100, 150), recv(1, 180, 200)) == (50, 30, 20)
    # the receiver had the frame before the writer's callback ran: the
    # boundary is the earlier stamp and the wire reads 0
    assert stages_of(send(2, 100, 190), recv(2, 180, 200)) == (80, 0, 20)
    spans = [send(1, 100, 150), recv(1, 180, 200),
             send(2, 300, 390), recv(2, 380, 400),
             send(3, 500, 550),                     # no receiving half
             send(4, 600, 650, err=-1), recv(4, 660, 670),
             send(5, 700, 750, sid=8), recv(5, 760, 770),   # other stream
             recv(9, 10, 20),                       # no sending half
             SimpleNamespace(side="client")]        # a call's span
    kept, dropped = join_frames(spans)
    assert kept == [(50, 30, 20), (80, 0, 20)] and dropped == 3
    kept, dropped = join_frames(spans, start_us=250, end_us=450)
    assert kept == [(80, 0, 20)] and dropped == 0
