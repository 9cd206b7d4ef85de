"""A ParallelChannel call lowered to one XLA collective gives what the
same call gives through the fan-out (ISSUE 30): which pairs of mapper
and merger lower, what a lowered call leaves behind (span, /device
cell, /vars counters), what falls back, and the merge order of
``on_sub_done`` (ROADMAP D14 (3)). CPU, virtual devices, seeded
small-integer bf16 data: every product and four-way sum is exact."""

import logging
import threading
import time

import numpy as np
import pytest

from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import (CallMapper, Channel, ChannelOptions, Controller,
                          ParallelChannel, ResponseMerger, RowScatterMapper,
                          Server, ServerOptions, Service, SumMerger)
from brpc_tpu.rpc import combo_channels
from brpc_tpu.rpc import span as span_mod
from brpc_tpu.transport import device_stats

N = 4
ROWS, COLS = 8, 16


def _reference(request_np: np.ndarray, n: int = N) -> np.ndarray:
    rows = request_np.shape[0] // n
    return sum(request_np[i * rows:(i + 1) * rows].astype(np.float32) * 2
               for i in range(n))


def _request(seed: int, device, rows: int = N * ROWS):
    import jax
    import jax.numpy as jnp

    x = jax.random.randint(jax.random.PRNGKey(seed), (rows, COLS), -8, 9)
    return jax.device_put(x.astype(jnp.bfloat16), device)


class Fabric:
    """Four servers, server i on device i, whose ``Shard`` answers its
    request array times 2 and the request's bytes; one ParallelChannel
    over four sub channels that reply to device 0."""

    def __init__(self, mapper=None, merger=None, reply_device=0):
        import jax

        self.devices = jax.devices()[:N]
        self.seen = []              # (shard, devices of its request)
        self.servers, self.subs = [], []
        self.channel = ParallelChannel(fail_limit=1, call_mapper=mapper,
                                       response_merger=merger)
        for i in range(N):
            srv = Server(ServerOptions(enable_builtin_services=False))
            svc = Service("Mesh")
            svc.register_method("Shard", self._shard(i))
            svc.register_method("Other", self._shard(i))
            srv.add_service(svc)
            self.servers.append(srv)
            ep = srv.start(f"ici://127.0.0.1:0#device={i}")
            sub = Channel(f"ici://127.0.0.1:{ep.port}"
                          f"#reply_device={reply_device}",
                          ChannelOptions(timeout_ms=20000, max_retry=0))
            self.subs.append(sub)
            self.channel.add_sub_channel(sub)

    def _shard(self, idx):
        def shard(cntl, request):
            arrs = cntl.request_device_arrays
            self.seen.append((idx, [a.devices() for a in arrs]))
            cntl.response_device_arrays = [a * 2 for a in arrs]
            return bytes(request)
        return shard

    def arm(self, fn=None, merge="concat"):
        from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh

        self.collective = CollectiveChannel(
            make_rpc_mesh(1, N, devices=self.devices), merge=merge)
        self.channel.attach_collective(
            self.collective, {("Mesh", "Shard"): fn or (lambda s: s * 2)})

    def call(self, request, method="Shard", payload=b"tag", done=None):
        cntl = Controller()
        if request is not None:
            cntl.request_device_arrays = [request]
        cntl = self.channel.call("Mesh", method, payload, cntl=cntl,
                                 done=done)
        assert cntl.join(20), "the call did not complete"
        assert not cntl.failed(), (cntl.error_text, cntl.sub_errors)
        return cntl

    def close(self):
        for sub in self.subs:
            sub.close()
        for srv in self.servers:
            srv.stop()
            srv.join(5)


@pytest.fixture
def fabric():
    made = []

    def make(*args, **kw):
        made.append(Fabric(*args, **kw))
        return made[-1]
    old = flag("device_stats_enabled")
    set_flag("device_stats_enabled", True)
    yield make
    set_flag("device_stats_enabled", old)
    for f in made:
        f.close()


def _collective_cell():
    page = device_stats.device_page_payload(samples=0)
    rows = [row for key, row in page["cells"].items()
            if key.endswith("|" + combo_channels.COLLECTIVE_LANE)]
    return {k: sum(r[k] for r in rows)
            for k in ("transfers", "completed", "failed", "bytes_out")} \
        if rows else {"transfers": 0, "completed": 0, "failed": 0,
                      "bytes_out": 0}


def _settled(before, calls, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        cell = _collective_cell()
        if cell["completed"] + cell["failed"] \
                - before["completed"] - before["failed"] >= calls:
            return cell
        time.sleep(0.01)
    return _collective_cell()


# ------------------------------------------------- the two paths agree

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_fanout_and_lowered_give_the_reference_bit_for_bit(fabric, seed):
    f = fabric(RowScatterMapper(), SumMerger())
    request = _request(seed, f.devices[0])
    assert request.committed and request.devices() == {f.devices[0]}
    want = _reference(np.asarray(request))

    plain = f.call(request)                     # lowering not armed
    assert not getattr(plain, "collective_lowered", False)
    assert sorted(i for i, _ in f.seen) == list(range(N))
    # block i reached shard i on device i
    assert all(devs == [{f.devices[i]}] for i, devs in f.seen)
    assert plain.sub_responses == [b"tag"] * N

    f.arm()
    lowered = f.call(request)
    assert lowered.collective_lowered
    assert len(f.seen) == N                     # no handler ran again
    for cntl in (plain, lowered):
        (out,) = cntl.response_device_arrays
        assert out.devices() == {f.devices[0]}      # the reply device
        assert out.dtype == request.dtype
        np.testing.assert_array_equal(
            np.asarray(out).astype(np.float32), want)
    assert f.channel.collective_fused == 1
    assert f.channel.collective_fallbacks == 0


@pytest.mark.parametrize("where", ["device_2", "device_off_mesh",
                                   "uncommitted", "sharded"])
def test_a_request_anywhere_lowers_to_the_reference(fabric, where):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    f = fabric(RowScatterMapper(), SumMerger())
    f.arm()
    if where == "device_2":         # scattered inside the program
        request = _request(3, f.devices[2])
    elif where == "device_off_mesh":    # put to the mesh first
        request = _request(3, jax.devices()[N + 1])
    elif where == "uncommitted":
        request = jnp.asarray(np.asarray(_request(3, f.devices[0])))
        assert not request.committed
    else:
        request = jax.device_put(
            _request(3, f.devices[0]),
            NamedSharding(f.collective.mesh, P("shard")))
    cntl = f.call(request)
    assert cntl.collective_lowered
    (out,) = cntl.response_device_arrays
    assert out.devices() == {f.devices[0]}
    np.testing.assert_array_equal(np.asarray(out).astype(np.float32),
                                  _reference(np.asarray(request)))
    assert f.channel.collective_fallbacks == 0


def test_reply_device_other_than_the_source(fabric):
    f = fabric(RowScatterMapper(), SumMerger(), reply_device=1)
    request = _request(4, f.devices[0])
    plain = f.call(request)
    f.arm()
    lowered = f.call(request)
    assert lowered.collective_lowered
    for cntl in (plain, lowered):
        (out,) = cntl.response_device_arrays
        assert out.devices() == {f.devices[1]}
        np.testing.assert_array_equal(
            np.asarray(out).astype(np.float32),
            _reference(np.asarray(request)))


def _fields(cntl) -> dict:
    """What a finished ParallelChannel call holds that a stock merger or
    the channel fills, ``sub_responses`` aside; an array as where it is,
    its type and its values."""
    def arr(a):
        return (a.devices(), str(a.dtype),
                np.asarray(a).astype(np.float32).tolist())
    return {"failed": cntl.failed(), "error_code": cntl.error_code,
            "sub_errors": cntl.sub_errors,
            "response_device_arrays": [
                arr(a) for a in cntl.response_device_arrays],
            "sub_device_arrays": [
                part if part is None else [arr(a) for a in part]
                for part in cntl.sub_device_arrays]}


@pytest.mark.parametrize("reply_device", [0, 1])
@pytest.mark.parametrize("merger", ["sum", "collect"])
def test_a_lowered_call_fills_the_fields_its_fanout_fills(fabric, merger,
                                                          reply_device):
    f = fabric(RowScatterMapper(), SumMerger() if merger == "sum" else None,
               reply_device=reply_device)
    request = _request(5, f.devices[0])
    plain = f.call(request)
    f.arm()
    lowered = f.call(request)
    assert lowered.collective_lowered
    assert not getattr(plain, "collective_lowered", False)
    assert _fields(lowered) == _fields(plain)
    # the one field a lowering cannot fill: no handler ran, so no reply
    # bytes exist
    assert plain.sub_responses == [b"tag"] * N
    assert lowered.sub_responses == [None] * N
    reply = {f.devices[reply_device]}
    doubled = np.asarray(request).astype(np.float32) * 2
    if merger == "sum":             # the sum, and the replies given up
        (out,) = lowered.response_device_arrays
        assert out.devices() == reply
        assert lowered.sub_device_arrays == [None] * N
    else:                           # block i, one array a sub, no sum
        assert lowered.response_device_arrays == []
        for i, (block,) in enumerate(lowered.sub_device_arrays):
            assert block.devices() == reply
            np.testing.assert_array_equal(
                np.asarray(block).astype(np.float32),
                doubled[i * ROWS:(i + 1) * ROWS])


def test_the_lowered_program_is_named_after_service_and_method(fabric):
    f = fabric(RowScatterMapper(), SumMerger())
    f.arm()
    request = _request(6, f.devices[0])
    f.call(request)
    f.call(_request(7, f.devices[0]))       # same shape: nothing new
    (key,) = f.collective._compiled
    assert key[2] == "collective_Mesh_Shard"
    placed, _src = f.collective.scatter(request)
    text = f.collective._compiled[key].lower(placed).as_text()
    assert "@jit_collective_Mesh_Shard" in text
    assert f.collective._compiled[key]._cache_size() == 1


# ------------------------------------------- the scatter in the program

def _lowered(f, request):
    """The one program a fabric's collective holds after a lowered call
    of ``request``, with the argument it ran on."""
    (key,) = f.collective._compiled
    placed, src = f.collective.scatter(request)
    assert src == key[3]
    return key, f.collective._compiled[key], placed


def _collectives(text: str, op: str) -> int:
    """How many instructions of the compiled module are ``op`` (or its
    async start)."""
    return sum(1 for line in text.splitlines()
               if f" {op}(" in line or f" {op}-start(" in line)


@pytest.mark.parametrize("src", [0, 2])
@pytest.mark.parametrize("merger", ["sum", "collect"])
def test_the_scatter_is_one_collective_and_no_collective_permute(
        fabric, merger, src):
    """ISSUE 34: the scatter is ONE all-to-all; the three
    collective-permutes from the source and their selects are gone,
    for any source and for both pairs that lower."""
    f = fabric(RowScatterMapper(), SumMerger() if merger == "sum" else None)
    f.arm()
    request = _request(20 + src, f.devices[src])
    assert f.call(request).collective_lowered
    key, fn, placed = _lowered(f, request)
    assert key[1] == ("sum" if merger == "sum" else "concat")
    assert key[3] == src                # scattered inside the program
    text = fn.lower(placed).compile().as_text()
    assert _collectives(text, "all-to-all") == 1
    assert "collective-permute" not in text
    assert "ragged-all-to-all" not in text
    # the merge is what it was: one all-reduce for the sum, none for
    # the blocks the out-spec stitches
    assert _collectives(text, "all-reduce") == (1 if merger == "sum" else 0)


@pytest.mark.parametrize("src", [0, 2])
@pytest.mark.parametrize("merger", ["sum", "collect"])
def test_one_program_a_shape_named_after_service_and_method(fabric, merger,
                                                            src):
    f = fabric(RowScatterMapper(), SumMerger() if merger == "sum" else None)
    f.arm()
    for seed in (30, 31, 32):           # same shape, same source
        assert f.call(_request(seed, f.devices[src])).collective_lowered
    key, fn, placed = _lowered(f, _request(30, f.devices[src]))
    assert key[2] == "collective_Mesh_Shard"
    assert "@jit_collective_Mesh_Shard" in fn.lower(placed).as_text()
    assert fn._cache_size() == 1        # compiled once for the shape
    # a second shape is a second entry of the same program, not a new one
    assert f.call(_request(33, f.devices[src],
                           rows=2 * N * ROWS)).collective_lowered
    assert list(f.collective._compiled) == [key]
    assert fn._cache_size() == 2


# patterns a move must not touch: -0.0, +-inf, NaNs of both signs, the
# smallest subnormal, 1.0. XLA:CPU widens a bf16 collective to float32
# and back, whatever the collective, which keeps every value and a
# NaN's sign and quiets its payload: the bf16 NaNs here are the two
# quiet ones, and the float32 case, which no backend widens, carries a
# quiet and a signalling NaN with payload bits. On the chip (builder's
# run, PR 34; PERF.md section 6) the all-to-all brings bf16 payloads
# through too, where a select gives 0x7FC0 for every NaN and 0 for a
# subnormal
_PATTERNS = {
    "bfloat16": (np.uint16, [0x8000, 0x7F80, 0xFF80, 0x7FC0, 0xFFC0,
                             0x0001, 0x3F80, 0xBF80]),
    "float32": (np.uint32, [0x80000000, 0x7F800000, 0xFF800000, 0x7FC00001,
                            0xFFA5A5A5, 0x7F800001, 0x00000001, 0x3F800000]),
}


@pytest.mark.parametrize("src", [0, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_scatter_is_a_move_every_bit_pattern_arrives_unchanged(
        fabric, dtype, src):
    """Through a ``concat`` call with the identity as the shard's body:
    block j of the request is on shard j's device with every bit
    pattern as the caller wrote it (nothing added in from the
    stand-ins, no other type on the wire)."""
    import jax
    import jax.numpy as jnp

    f = fabric(RowScatterMapper(), None)
    f.arm(fn=lambda s: s)
    uint, patterns = _PATTERNS[dtype]
    bits = np.resize(np.array(patterns, dtype=uint), (N * ROWS, COLS)).copy()
    # every row its own rotation, so no two blocks are alike
    for r in range(N * ROWS):
        bits[r] = np.roll(bits[r], r)
    request = jax.device_put(bits.view(jnp.dtype(dtype)), f.devices[src])
    assert request.dtype == jnp.dtype(dtype)

    def bits_of(a):     # the buffer's bytes, no device op in between
        return np.asarray(a).view(uint)

    np.testing.assert_array_equal(bits_of(request), bits)
    cntl = f.call(request)
    assert cntl.collective_lowered
    for i, (block,) in enumerate(cntl.sub_device_arrays):
        assert block.dtype == request.dtype
        np.testing.assert_array_equal(bits_of(block),
                                      bits[i * ROWS:(i + 1) * ROWS])
    # and before the channel brought them to the reply device: block j
    # is the piece the program left on shard j's own device
    out = f.collective.call(lambda s: s, request, merge="concat")
    for shard in out.addressable_shards:
        j = f.devices.index(shard.device)
        assert shard.index[0] == slice(j * ROWS, (j + 1) * ROWS)
        np.testing.assert_array_equal(bits_of(shard.data),
                                      bits[j * ROWS:(j + 1) * ROWS])


# ------------------------------------------------------ what fans out

class _MyScatter(RowScatterMapper):
    pass


class _MySum(SumMerger):
    pass


class _Keep(ResponseMerger):
    pass


@pytest.mark.parametrize("case", [
    "stock_broadcast_mapper", "mapper_subclass", "merger_subclass",
    "custom_merger", "unmapped_method", "host_payload"])
def test_a_pair_or_call_the_lowering_cannot_express_fans_out(fabric, case):
    mapper = {"stock_broadcast_mapper": CallMapper(),
              "host_payload": CallMapper(),
              "mapper_subclass": _MyScatter()}.get(case, RowScatterMapper())
    merger = {"merger_subclass": _MySum(), "custom_merger": _Keep(),
              "host_payload": None}.get(case, SumMerger())
    f = fabric(mapper, merger)
    f.arm()
    request = _request(8, f.devices[0])
    if case == "host_payload":
        cntl = f.call(None, payload=b"host only")
        assert cntl.sub_responses == [b"host only"] * N
    else:
        method = "Other" if case == "unmapped_method" else "Shard"
        cntl = f.call(request, method=method)
        if case == "stock_broadcast_mapper":
            # the whole array to every sub: 4 x (request x 2) summed
            np.testing.assert_array_equal(
                np.asarray(cntl.response_device_arrays[0]).astype(
                    np.float32),
                np.asarray(request).astype(np.float32) * 2 * N)
    assert not getattr(cntl, "collective_lowered", False)
    assert sorted(i for i, _ in f.seen) == list(range(N))
    assert f.channel.collective_fused == 0
    assert f.channel.collective_fallbacks == 0      # none was tried


@pytest.mark.parametrize("case", ["host_payload", "two_arrays",
                                  "rows_do_not_divide"])
def test_a_call_the_scattering_mapper_cannot_map_fails_unlowered(fabric,
                                                                 case):
    from brpc_tpu.rpc import errno_codes as berr

    f = fabric(RowScatterMapper(), SumMerger())
    f.arm()
    cntl = Controller()
    if case == "two_arrays":
        cntl.request_device_arrays = [_request(8, f.devices[0])] * 2
    elif case == "rows_do_not_divide":
        cntl.request_device_arrays = [
            _request(8, f.devices[0], rows=N * ROWS + 1)]
    fired = []
    cntl = f.channel.call("Mesh", "Shard", b"host only", cntl=cntl,
                          done=fired.append)
    assert cntl.join(20) and cntl.failed() and fired == [cntl]
    assert cntl.error_code == berr.EREQUEST
    assert "call mapper failed" in cntl.error_text
    assert f.seen == []
    assert f.channel.collective_fused == 0
    assert f.channel.collective_fallbacks == 0


def test_a_lowering_that_raises_is_logged_once_counted_and_fans_out(
        fabric, caplog):
    def broken(s):
        raise RuntimeError("no such program")

    f = fabric(RowScatterMapper(), SumMerger())
    f.arm(fn=broken)
    before = _collective_cell()
    fallbacks = combo_channels._fallbacks_var.get_value()
    request = _request(9, f.devices[0])
    with caplog.at_level(logging.ERROR, logger="brpc_tpu.rpc"):
        for _ in range(3):
            cntl = f.call(request)
            assert not getattr(cntl, "collective_lowered", False)
            np.testing.assert_array_equal(
                np.asarray(cntl.response_device_arrays[0]).astype(
                    np.float32), _reference(np.asarray(request)))
    assert f.channel.collective_fallbacks == 3
    assert f.channel.collective_fused == 0
    assert combo_channels._fallbacks_var.get_value() == fallbacks + 3
    logged = [r for r in caplog.records
              if "collective lowering of Mesh.Shard failed" in r.getMessage()]
    assert len(logged) == 1
    cell = _collective_cell()       # each try: one transfer, failed
    assert cell["transfers"] - before["transfers"] == 3
    assert cell["failed"] - before["failed"] == 3


# --------------------------------------- what a lowered call leaves behind

def test_done_fires_once_and_join_returns(fabric):
    f = fabric(RowScatterMapper(), SumMerger())
    f.arm()
    fired = []
    cntl = f.call(_request(10, f.devices[0]), done=fired.append)
    assert fired == [cntl] and cntl.collective_lowered
    time.sleep(0.1)                 # the waiter's callback is no done=
    assert fired == [cntl]


def test_span_device_cell_and_counters_of_lowered_calls(fabric):
    from brpc_tpu.bvar.variable import dump_exposed

    def exposed():
        return dict(dump_exposed("parallel_collective_"))

    f = fabric(RowScatterMapper(), SumMerger())
    for var in (combo_channels._fused_var, combo_channels._fallbacks_var,
                combo_channels._bytes_var):
        var.hide()
    assert exposed() == {}          # listed once a collective is attached
    f.arm()
    request = _request(11, f.devices[0])
    start = _collective_cell()
    f.call(request)                 # compiles; spans do not record yet
    assert not span_mod.recording()
    ring_before = len(span_mod.global_collector.recent(1 << 30))
    before = _settled(start, 1)
    counters = exposed()
    assert set(counters) == {"parallel_collective_fused",
                             "parallel_collective_fallbacks",
                             "parallel_collective_bytes"}
    old = flag("rpcz_enabled")
    set_flag("rpcz_enabled", True)
    try:
        calls = 3
        for _ in range(calls):
            f.call(request)
        cell = _settled(before, calls)
    finally:
        set_flag("rpcz_enabled", old)
    # /device: one settled transfer a call in the mesh's collective cell
    assert cell["transfers"] - before["transfers"] == calls
    assert cell["completed"] - before["completed"] == calls
    assert cell["failed"] == before["failed"]
    assert cell["bytes_out"] - before["bytes_out"] == calls * request.nbytes
    # /vars
    after = {k[len("parallel_collective_"):]: v - counters[k]
             for k, v in exposed().items()}
    assert after == {"fused": calls, "fallbacks": 0,
                     "bytes": calls * request.nbytes}
    # rpcz: one client span a call, stamped in order
    spans = [s for s in span_mod.global_collector.recent(1 << 30)
             if s.side == "client" and s.service == "Mesh"
             and any("collective lowered" in t for _us, t in s.annotations)]
    assert len(spans) == calls
    assert len(span_mod.global_collector.recent(1 << 30)) \
        == ring_before + calls              # and no other span
    for s in spans:
        # the annotation names the program's scatter (ISSUE 34)
        assert [t for _us, t in s.annotations] == [
            "collective lowered: all-to-all scatter in the program + sum "
            "over 4 shards, no sub call"]
        assert s.method == "Shard" and s.request_size == request.nbytes
        assert s.remote_side.startswith("mesh://")
        assert 0 < s.start_us <= s.write_done_us <= s.dispatch_us \
            <= s.first_byte_us <= s.end_us
        assert s.error_code == 0


def test_one_waiter_thread_says_its_role(fabric):
    from brpc_tpu.butil import thread_cpu

    f = fabric(RowScatterMapper(), SumMerger())
    f.arm()
    before = _collective_cell()
    request = _request(12, f.devices[0])
    for _ in range(5):
        f.call(request)
    _settled(before, 5)
    waiters = [t for t in threading.enumerate()
               if t.name == "collective_wait"]
    assert len(waiters) == 1
    assert thread_cpu._roles.get(waiters[0].ident) == "device_wait"


# ------------------------------------------------ merge before the count

def test_a_call_cannot_complete_before_its_last_merge(fabric):
    """ROADMAP D14 (3): the sub call that is counted last used to
    complete the call while another thread was still inside merge()."""
    merged = []

    class SlowFirst(ResponseMerger):
        def merge(self, final_cntl, sub_index, sub_cntl):
            if sub_index == 0:
                time.sleep(0.3)     # the others are counted meanwhile
            merged.append(sub_index)

    f = fabric(RowScatterMapper(), SlowFirst())
    at_done = []
    f.call(_request(13, f.devices[0]),
           done=lambda _c: at_done.append(sorted(merged)))
    assert at_done == [list(range(N))]


def test_a_merge_that_raises_fails_its_sub_call(fabric):
    class Picky(ResponseMerger):
        def merge(self, final_cntl, sub_index, sub_cntl):
            if sub_index == 2:
                raise ValueError("not this one")

    f = fabric(RowScatterMapper(), Picky())
    cntl = Controller()
    cntl.request_device_arrays = [_request(14, f.devices[0])]
    cntl = f.channel.call("Mesh", "Shard", b"", cntl=cntl)
    assert cntl.join(20) and cntl.failed()      # fail_limit 1
    assert "merger failed: not this one" in cntl.sub_errors[2][1]
