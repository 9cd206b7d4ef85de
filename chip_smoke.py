"""chip_smoke.py — the quickest proof that the fabric still starts on the chip.

One process (the only one that touches JAX) drives the normal entry
points once — ``Server``, ``Channel``, ``ParallelChannel``,
``add_generate_service``, ``flash_attention`` — at the upstream sweep's
sizes (``example/rdma_performance``, 4 B-4 MB) with device-resident
state, checks VALUES against plain numpy / the repo's own oracles, and
ends its stdout with two JSON lines: the report (what every phase
observed, ending ``"claim": null``) and, last, the verdict the driver
reads, ``{"ok": ..., "device": {"platform", "kind", "count"}}`` with
exactly those keys. Exit status 0 iff every phase that applies to the
device count held.

    python chip_smoke.py                     # on a TPU host
    python chip_smoke.py --require-chips 4   # on a four-chip host
    python chip_smoke.py --rehearse          # CPU, tiny sizes, tier-1

Without ``--rehearse`` a platform other than ``tpu`` exits 2 before any
result is printed: nothing here falls back to the CPU. Wall times in the
summary are set-up information, not metrics; this script claims nothing
(``"claim": null``).
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import os
import struct
import sys
import threading
import time
import traceback

PHASES = ("fabric", "serving", "kernel", "four_chips")
WALL_LIMIT_S = 1100          # the driver allows 1200 s, compile included

# what a phase is sized to: the real column is the contract, the
# rehearsal column only debugs the command on the CPU
SIZES = {
    "payload_bytes": {"real": (4, 4 << 10, 64 << 10, 1 << 20, 4 << 20),
                      "rehearse": (4, 4 << 10, 64 << 10)},
    # __graft_entry__.entry: two 32 MB bf16 weight matrices resident on
    # the device, 2 MB request, 2 MB response
    "mlp": {"real": dict(d_model=2048, d_ff=8192, batch=512),
            "rehearse": dict(d_model=64, d_ff=256, batch=16)},
    "attachment_bytes": {"real": 1 << 20, "rehearse": 64 << 10},
    # (shape, dtype, causal): B1 H8 S4096 D128 bf16 causal, the
    # examples/long_context shape, one ragged length
    "kernel": {"real": (((1, 8, 4096, 128), "bfloat16", True),
                        ((8, 2048, 64), "float32", False),
                        ((1000, 128), "float32", True)),
               "rehearse": (((1, 2, 256, 64), "bfloat16", True),
                            ((2, 128, 64), "float32", False),
                            ((100, 64), "float32", True))},
    # the roadmap's R4/R6 expert block: 2048 x 1024 bf16 = 4 MB a shard
    "shard_block": {"real": (2048, 1024), "rehearse": (64, 32)},
    "collective": {"real": dict(d_model=2048, rows_per_shard=512,
                                seq=4096, head_dim=128),
                   "rehearse": dict(d_model=32, rows_per_shard=4,
                                    seq=32, head_dim=8)},
}
INFLIGHT = 8
BURST = 16                   # calls per size at INFLIGHT depth
# bf16 carries 8 significant bits: one ulp is 2^-5 for 4 <= |y| < 8, the
# largest outputs these shapes produce; two ulps cover the bf16 rounding
# of the hidden layer feeding the second matmul
BF16_ATOL = 2 ** -4
# attention on f32 operands at the TPU's default matmul precision (one
# bf16 pass on the MXU) against the oracle at "highest"; bf16 operands
# additionally round the probabilities and the output to bf16
ATTN_ATOL = {"float32": 2e-2, "bfloat16": 4e-2}


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


class Smoke:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.col = "rehearse" if rehearse else "real"

    def size(self, key: str):
        return SIZES[key][self.col]


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------- fabric

def _make_fabric_service(smoke: Smoke, server_device):
    """Echo + Step (the jitted __graft_entry__.entry body over resident
    weights) + Open (stream). The handlers record what they saw so the
    phase can assert placement, not just completion."""
    import jax

    import __graft_entry__ as graft
    from brpc_tpu.rpc import Service
    from brpc_tpu.rpc.stream import StreamOptions, stream_accept

    step_fn, (x0, w_in, w_out) = graft.entry(**smoke.size("mlp"))
    step = jax.jit(step_fn)
    seen = {"misplaced": [], "stream": [], "stream_arrays": []}
    svc = Service("Smoke")

    def note_placement(arrs):
        for a in arrs:
            if a.devices() != {server_device}:
                seen["misplaced"].append(str(a.devices()))

    @svc.method()
    def Echo(cntl, request):
        arrs = cntl.request_device_arrays or []
        note_placement(arrs)
        if arrs:
            cntl.response_device_arrays = list(arrs)
        if cntl.request_attachment.size:
            cntl.response_attachment = cntl.request_attachment
        return bytes(request)

    @svc.method()
    def Step(cntl, request):
        arrs = cntl.request_device_arrays
        note_placement(arrs)
        y, checksum = step(arrs[0], w_in, w_out)
        cntl.response_device_arrays = [y]
        return struct.pack("<f", float(checksum))

    @svc.method()
    def Open(cntl, request):
        def on_received(s, m):
            note_placement(m.device_arrays)
            seen["stream_arrays"].extend(m.device_arrays)
            seen["stream"].append(m.payload.to_bytes())
            s.write_nowait(b"ack:" + m.payload.to_bytes())
        st = stream_accept(cntl, StreamOptions(on_received=on_received))
        return b"opened" if st is not None else b"no-stream"

    return svc, seen, (x0, w_in, w_out)


def _burst(ch, method: str, make_request, verify, total: int,
           inflight: int, timeout_s: float = 300.0) -> None:
    """``total`` calls at ``inflight`` depth through ``done=`` callbacks;
    each completion verifies its own response and issues the next."""
    lock = threading.Lock()
    state = {"issued": 0, "done": 0}
    errors: list = []
    finished = threading.Event()

    def issue() -> None:
        with lock:
            if state["issued"] >= total:
                return
            i = state["issued"]
            state["issued"] += 1
        arrs, expect = make_request(i)

        def _done(cntl) -> None:
            try:
                check(not cntl.failed(),
                      f"{method} call {i} failed: {cntl.error_text}")
                verify(cntl, expect)
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(f"{type(e).__name__}: {e}")
            with lock:
                state["done"] += 1
                last = state["done"] >= total
            if last:
                finished.set()
            else:
                issue()

        ch.call("Smoke", method, b"", done=_done,
                request_device_arrays=arrs)

    for _ in range(min(inflight, total)):
        issue()
    check(finished.wait(timeout_s),
          f"{method} burst hung: {state} after {timeout_s}s")
    check(not errors, f"{method} burst: {errors[:3]}")


def _cells_balance(timeout_s: float = 10.0) -> dict:
    """What tools/device_obs_smoke.py asserts on the CPU: on a live conn
    every (peer, lane) cell settles to issued == acked with nothing
    failed and nothing leaked."""
    from brpc_tpu.transport import device_stats as ds

    deadline = time.monotonic() + timeout_s
    while True:
        page = ds.device_page_payload()
        bad = {k: (row["transfers"], row["completed"], row["failed"])
               for k, row in page["cells"].items()
               if row["transfers"] != row["completed"] or row["failed"]}
        if not bad or time.monotonic() >= deadline:
            break
        time.sleep(0.05)
    check(not bad, f"/device cells out of balance: {bad}")
    totals = page["totals"]
    check(totals["leaked_bytes"] == 0, f"leaked bytes: {totals}")
    check(totals["transfers"] > 0, "no device transfer was counted")
    return {"cells": len(page["cells"]), **totals}


def _stager_check(smoke: Smoke, device) -> dict:
    """The pinned H2D stager with real bytes. In one process the ici://
    lane is ``local-d2d`` and never reaches it (it is the receive side
    of the cross-process staged lane), so this drives the process-wide
    stager object directly: many landings back to back, every array
    kept alive, so a block recycled before its copy was consumed would
    show as a wrong value."""
    import numpy as np

    import jax
    from brpc_tpu.butil.device_pool import global_pinned_stager

    stager = global_pinned_stager()
    before = (stager.staged_count, stager.fallback_count)
    landed = []
    for nbytes in smoke.size("payload_bytes"):
        for i in range(12):
            host = (np.arange(max(1, nbytes // 4), dtype=np.float32)
                    + np.float32(i))
            landed.append((host, stager.land(host, device=device)))
    jax.block_until_ready([a for _, a in landed])
    for host, arr in landed:
        check(arr.devices() == {device}, "staged array off its device")
        np.testing.assert_array_equal(np.asarray(arr), host)
    return {"active": stager.active,
            "staged_count": stager.staged_count - before[0],
            "fallback_count": stager.fallback_count - before[1],
            "landings": len(landed)}


@contextlib.contextmanager
def _served(svc, listen: str, dial: str, options):
    """A Server for ``svc`` on ``listen`` and a Channel dialled at it."""
    from brpc_tpu.rpc import Channel, Server, ServerOptions

    server = Server(ServerOptions(enable_builtin_services=False))
    server.add_service(svc)
    ep = server.start(listen)
    ch = Channel(dial.format(port=ep.port), options)
    try:
        yield ch
    finally:
        ch.close()
        server.stop()
        server.join(5)


def phase_fabric(smoke: Smoke) -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp
    from brpc_tpu import fiber
    from brpc_tpu.butil.flags import set_flag
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc import ChannelOptions, Controller
    from brpc_tpu.rpc.stream import StreamOptions
    from brpc_tpu.transport import ici

    set_flag("device_stats_enabled", True)
    dev0 = jax.devices()[0]
    seen: dict = {"transports": {}}
    opts = ChannelOptions(timeout_ms=180000)     # first calls compile
    svc, handler_seen, (x0, w_in, w_out) = _make_fabric_service(smoke, dev0)

    born = jax.jit(lambda i, n: jnp.arange(n, dtype=jnp.float32) + i,
                   static_argnums=1)

    def echo_request(nbytes):
        n = max(1, nbytes // 4)

        def make(i):
            # born on the device, never on the host
            return ([born(jnp.float32(i), n)],
                    np.arange(n, dtype=np.float32) + np.float32(i))
        return make

    def echo_verify(cntl, expect):
        out = cntl.response_device_arrays[0]
        check(out.devices() == {dev0},
              f"response on {out.devices()}, wanted {dev0}")
        np.testing.assert_array_equal(np.asarray(out), expect)

    # the service step's float32 numpy reference. The step is row-wise,
    # so the reference of a rolled request is the rolled reference.
    x_np, w_in_np, w_out_np = (np.asarray(a).astype(np.float32)
                               for a in (x0, w_in, w_out))
    ref0 = np.maximum(x_np @ w_in_np, 0) @ w_out_np + x_np
    roll = jax.jit(lambda x, i: jnp.roll(x, i, axis=0))
    worst = [0.0]

    def step_request(i):
        return [roll(x0, i)], np.roll(ref0, i, axis=0)

    def step_verify(cntl, ref):
        y = cntl.response_device_arrays[0]
        check(y.devices() == {dev0}, f"step response on {y.devices()}")
        y_np = np.asarray(y).astype(np.float32)
        check(y_np.shape == ref.shape and np.isfinite(y_np).all(),
              "step output has the wrong shape or is not finite")
        # bf16 output of a bf16 MLP against float32 numpy
        err = float(np.max(np.abs(y_np - ref)))
        worst[0] = max(worst[0], err)
        check(err <= BF16_ATOL,
              f"step output off by {err} (atol {BF16_ATOL})")
        # the handler's on-device checksum of the same y. XLA may sum y
        # before its rounding to bf16 (XLA:CPU folds the convert pair
        # away): N rounding errors of at most 2^-9 |y| add like a
        # random walk, 8 sigma allowed
        (checksum,) = struct.unpack("<f", cntl.response_payload.to_bytes())
        host_sum = float(y_np.sum(dtype=np.float64))
        slack = 2 ** -6 * float(np.sqrt((y_np.astype(np.float64)
                                         ** 2).sum()))
        check(abs(checksum - host_sum) <= slack,
              f"checksum {checksum} vs host {host_sum} (slack {slack})")

    for scheme, listen, dial, want_lane in (
            ("ici", "ici://127.0.0.1:0#device=0",
             "ici://127.0.0.1:{port}#reply_device=0", "local-d2d"),
            ("tpu", "tpu://chip-smoke:1#device=0",
             "tpu://chip-smoke:1#device=0&reply_device=0", "loopback-d2d")):
        with _served(svc, listen, dial, opts) as ch:
            def sync(method, tag, arrs, expect, verify):
                cntl = ch.call_sync("Smoke", method, tag,
                                    request_device_arrays=arrs)
                check(not cntl.failed(), f"{scheme} {method} {tag!r} "
                                         f"failed: {cntl.error_text}")
                verify(cntl, expect)

            t_seen = seen["transports"][scheme] = {"sizes": {}}
            for nbytes in smoke.size("payload_bytes"):
                make = echo_request(nbytes)
                sync("Echo", b"device-born", *make(1000), echo_verify)
                # one host numpy request: H2D staged by the lane
                host = np.arange(max(1, nbytes // 4),
                                 dtype=np.float32) * np.float32(0.5)
                sync("Echo", b"host", [host], host, echo_verify)
                # INFLIGHT deep through done= callbacks
                _burst(ch, "Echo", make, echo_verify, BURST, INFLIGHT)
                t_seen["sizes"][str(nbytes)] = "ok"
            t_seen["lane_kind"] = ch._get_socket().conn.lane_kind
            check(t_seen["lane_kind"] == want_lane,
                  f"{scheme} lane is {t_seen['lane_kind']}, "
                  f"wanted {want_lane}")

            # the jitted service step over device-resident weights
            worst[0] = 0.0
            sync("Step", b"", *step_request(0), step_verify)
            _burst(ch, "Step", step_request, step_verify, INFLIGHT,
                   INFLIGHT)
            t_seen["step_max_abs_err"] = worst[0]
            check(not handler_seen["misplaced"],
                  f"{scheme} handler saw arrays off {dev0}: "
                  f"{handler_seen['misplaced'][:3]}")

            if scheme == "ici":
                # eight streaming frames with acks over the same lane,
                # the last with a device array of the step's own size
                acks: list = []
                scntl = ch.call_sync(
                    "Smoke", "Open", b"", stream_options=StreamOptions(
                        on_received=lambda s, m: acks.append(
                            m.payload.to_bytes())))
                check(not scntl.failed(), scntl.error_text)
                stream = scntl.stream
                frames = [f"seq-{i}".encode() for i in range(8)]

                async def writer():
                    for f in frames[:-1]:
                        assert await stream.write(f)
                    assert await stream.write(
                        frames[-1], device_arrays=[roll(x0, 1)])
                check(fiber.spawn(writer).join(30), "stream writer hung")
                deadline = time.monotonic() + 30
                while (len(handler_seen["stream"]) < 8 or len(acks) < 8) \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
                check(handler_seen["stream"] == frames,
                      f"stream frames: {handler_seen['stream']}")
                check(acks == [b"ack:" + f for f in frames],
                      f"stream acks: {acks}")
                (framed,) = handler_seen["stream_arrays"]
                np.testing.assert_array_equal(
                    np.asarray(framed).astype(np.float32),
                    np.roll(x_np, 1, axis=0))
                check(not handler_seen["misplaced"],
                      f"a stream frame's array off {dev0}: "
                      f"{handler_seen['misplaced'][:3]}")
                t_seen["stream_device_frame_bytes"] = int(framed.nbytes)
                stream.close()
                t_seen["stream_frames_acked"] = len(acks)
                seen["device_cells"] = _cells_balance()

    seen["transfer_lane"] = ici.transfer_lane_status()
    check(seen["transfer_lane"] == "up",
          f"ici transfer lane is {seen['transfer_lane']!r}, wanted 'up'")
    seen["device_cells_final"] = _cells_balance()
    seen["stager"] = _stager_check(smoke, dev0)
    check(seen["stager"]["active"] and seen["stager"]["staged_count"] > 0,
          f"pinned stager did not stage: {seen['stager']}")

    # one host-attachment echo over tcp://
    with _served(svc, "tcp://127.0.0.1:0", "tcp://127.0.0.1:{port}",
                 opts) as ch:
        blob = np.random.RandomState(21).bytes(
            smoke.size("attachment_bytes"))
        cntl = Controller()
        cntl.request_attachment = IOBuf()
        cntl.request_attachment.append(blob)
        cntl = ch.call_sync("Smoke", "Echo", b"att", cntl=cntl)
        check(not cntl.failed(), f"tcp attachment: {cntl.error_text}")
        check(cntl.response_attachment.to_bytes() == blob,
              "tcp attachment came back different")
        seen["tcp_attachment_bytes"] = len(blob)
    return seen


# --------------------------------------------------------------- serving

def phase_serving(smoke: Smoke) -> dict:
    """Four concurrent streaming Generate calls; each token stream must
    equal TinyDecoder.generate for the same prompt on the same device.
    TinyDecoder (dim 32): this proves the batcher's prefill and decode
    programs run here with the cache on the device, nothing more (the
    benchmark's Moonlight cell runs the real model). No shard group:
    forked workers cannot share the chip."""
    from brpc_tpu.rpc import Channel, ChannelOptions, Controller, Server
    from brpc_tpu.rpc.stream import StreamOptions
    from brpc_tpu.serving import (TinyDecoder, TinyDecoderConfig,
                                  add_generate_service)

    max_tokens = 24
    prompts = ["hi", "the quick brown fox", "a" * 40,
               "continuous batching over streaming rpc, " * 2]
    server = Server()
    gs = add_generate_service(server)
    ep = server.start("tcp://127.0.0.1:0")
    ch = Channel(f"tcp://127.0.0.1:{ep.port}",
                 ChannelOptions(timeout_ms=120000))
    streams = []
    try:
        for prompt in prompts:
            got = {"tokens": [], "done": None}

            def on_frame(s, m, got=got):
                p = m.payload.to_bytes()
                if p[:1] == b"t":
                    got["tokens"].append(p[1])
                elif p[:1] in (b"d", b"e"):
                    got["done"] = p

            cntl = Controller()
            cntl.timeout_ms = 120000
            cntl = ch.call(
                "GenerateService", "Generate",
                json.dumps({"prompt": prompt,
                            "max_tokens": max_tokens}).encode(),
                cntl=cntl,
                stream_options=StreamOptions(on_received=on_frame))
            streams.append((prompt, cntl, got))
        for _, cntl, _ in streams:
            check(cntl.join(120) and not cntl.failed(),
                  f"Generate failed: {cntl.error_text}")
        deadline = time.monotonic() + 120
        while any(g["done"] is None for _, _, g in streams) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        stats = gs.batcher.stats_snapshot()
        oracle = TinyDecoder(TinyDecoderConfig(
            cache_len=gs.batcher.cache_len))
        for prompt, cntl, got in streams:
            check(got["done"] is not None and got["done"][:1] == b"d",
                  f"stream for {prompt[:12]!r} ended {got['done']!r}")
            want = oracle.generate(list(prompt.encode()), max_tokens)
            check(got["tokens"] == want,
                  f"prompt {prompt[:12]!r}: streamed {got['tokens']} "
                  f"!= oracle {want}")
            cntl.stream.close()
    finally:
        ch.close()
        server.stop()
        server.join(5)
    check(stats["decode_steps"] > 0, "the batcher took no decode step")
    return {"streams": len(streams),
            "tokens_each": max_tokens,
            "decode_steps": stats["decode_steps"],
            "batch_size_hist": stats["batch_size_hist"],
            "completed": stats["completed"]}


# ---------------------------------------------------------------- kernel

def phase_kernel(smoke: Smoke) -> dict:
    """flash_attention's Pallas kernel, compiled by Mosaic (interpreted
    only in rehearsal), against attention_reference at "highest"."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.butil.jax_runtime import ensure_compile_cache
    from brpc_tpu.ops import attention_reference, flash_attention

    ensure_compile_cache()
    backend = "pallas_interpret" if smoke.rehearse else "pallas"
    seen = {"backend": backend, "shapes": {}}
    for shape, dtype, causal in smoke.size("kernel"):
        dt = jnp.dtype(dtype)
        q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(dt)
                   for key in jax.random.split(jax.random.PRNGKey(7), 3))
        fn = jax.jit(lambda q, k, v, causal=causal: flash_attention(
            q, k, v, causal=causal, backend=backend))
        compiled = fn.lower(q, k, v).compile()
        if not smoke.rehearse:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{shape}: no Mosaic custom call in the compiled "
                  "program — the kernel did not run as a kernel")
        out = jax.block_until_ready(compiled(q, k, v))
        with jax.default_matmul_precision("highest"):
            ref = attention_reference(q, k, v, causal=causal)
        check(out.shape == shape and out.dtype == dt,
              f"{shape}: output {out.shape} {out.dtype}")
        check(bool(jnp.isfinite(out.astype(jnp.float32)).all()),
              f"{shape}: output not finite")
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        atol = ATTN_ATOL[dtype]
        seen["shapes"][f"{'x'.join(map(str, shape))} {dtype} "
                       f"causal={causal}"] = {"max_abs_err": err,
                                              "atol": atol}
        check(err <= atol, f"{shape} {dtype}: off by {err} (atol {atol})")
    if not smoke.rehearse:
        # backend=None must pick the kernel here, not the lax path
        auto = jax.jit(lambda q, k, v: flash_attention(q, k, v))
        check("tpu_custom_call" in auto.lower(q, k, v).compile().as_text(),
              "flash_attention(backend=None) did not select Pallas on "
              f"{jax.default_backend()}")
        seen["auto_backend"] = "pallas"
    return seen


# ------------------------------------------------------------ four chips

def phase_four_chips(smoke: Smoke) -> dict:
    """Four ici://...#device=i servers in the one process; nothing may
    collapse onto chip 0. Stock ParallelChannel fan-out, then a
    scattering, summing channel over the same connections, fanned out
    and lowered to one collective, then the collective pipeline at real
    shapes."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh
    from brpc_tpu.rpc import (Channel, ChannelOptions, Controller, Server,
                              ServerOptions, Service)
    from brpc_tpu.rpc.combo_channels import (ParallelChannel,
                                             RowScatterMapper, SumMerger)

    n = 4
    devs = jax.devices()[:n]
    rows, cols = smoke.size("shard_block")
    misplaced: list = []
    servers, subs = [], []
    pch = ParallelChannel()
    # block i of the request's rows to shard i, the replies summed where
    # sub 0 replies (chip 0): the pair attach_collective lowers
    reduce_ch = ParallelChannel(call_mapper=RowScatterMapper(),
                                response_merger=SumMerger())

    def make_shard(idx, factor):
        def Shard(cntl, request):
            arrs = cntl.request_device_arrays
            for a in arrs:
                if a.devices() != {devs[idx]}:
                    misplaced.append(f"shard {idx}: {a.devices()}")
            cntl.response_device_arrays = [a * factor for a in arrs]
            return f"shard-{idx}".encode()
        return Shard

    try:
        for i in range(n):
            srv = Server(ServerOptions(enable_builtin_services=False))
            svc = Service("Mesh")
            svc.register_method("Shard", make_shard(i, i + 1))
            svc.register_method("Double", make_shard(i, 2))
            srv.add_service(svc)
            servers.append(srv)
            ep = srv.start(f"ici://127.0.0.1:0#device={i}")
            sub = Channel(f"ici://127.0.0.1:{ep.port}#reply_device={i}",
                          ChannelOptions(timeout_ms=120000))
            subs.append(sub)
            pch.add_sub_channel(sub)
            reduce_ch.add_sub_channel(sub)

        # small integers: every product and 4-way sum is exact in bf16
        block = jax.random.randint(jax.random.PRNGKey(3), (rows, cols),
                                   -8, 9).astype(jnp.bfloat16)
        block_np = np.asarray(block).astype(np.float32)

        # 1) stock fan-out: the whole 4 MB block to every shard
        cntl = Controller()
        cntl.request_device_arrays = [block]
        cntl = pch.call("Mesh", "Shard", b"go", cntl=cntl)
        check(cntl.join(120) and not cntl.failed(),
              f"fan-out: {cntl.error_text} {cntl.sub_errors}")
        check(cntl.sub_responses == [f"shard-{i}".encode()
                                     for i in range(n)],
              f"fan-out responses {cntl.sub_responses}")
        landed = []
        for i, arrs in enumerate(cntl.sub_device_arrays):
            check(bool(arrs), f"shard {i} returned no array")
            check(arrs[0].devices() == {devs[i]},
                  f"shard {i} response on {arrs[0].devices()}, "
                  f"wanted {devs[i]}")
            np.testing.assert_array_equal(
                np.asarray(arrs[0]).astype(np.float32), block_np * (i + 1))
            landed.append(str(devs[i]))
        check(not misplaced, f"requests off their chip: {misplaced[:4]}")

        # 2) an allreduce over the same connections: a 16 MB request
        # committed to chip 0, block i (4 MB) to shard i, every shard
        # answers its block times 2, the sum on chip 0. Once through the
        # fan-out, then lowered to ONE collective: the same call, the
        # same answer, no message sent
        big = jax.device_put(
            jnp.concatenate([block * (i + 1) for i in range(n)], axis=0),
            devs[0])
        want = sum(block_np * (i + 1) * 2 for i in range(n))

        def reduce_call(lowered: bool):
            cntl = Controller()
            cntl.request_device_arrays = [big]
            cntl = reduce_ch.call("Mesh", "Double", b"go", cntl=cntl)
            check(cntl.join(120) and not cntl.failed(),
                  f"allreduce: {cntl.error_text} {cntl.sub_errors}")
            check(getattr(cntl, "collective_lowered", False) == lowered,
                  f"call lowered={not lowered}, wanted {lowered}")
            out = cntl.response_device_arrays[0]
            check(out.devices() == {devs[0]},
                  f"allreduce result on {out.devices()}, wanted {devs[0]}")
            np.testing.assert_array_equal(
                np.asarray(out).astype(np.float32), want)

        reduce_call(lowered=False)
        check(not misplaced, f"blocks off their chip: {misplaced[:4]}")
        mesh = make_rpc_mesh(1, n, devices=devs)
        reduce_ch.attach_collective(CollectiveChannel(mesh),
                                    {("Mesh", "Double"): lambda s: s * 2})
        calls = 3
        for _ in range(calls):
            reduce_call(lowered=True)
        check(reduce_ch.collective_fused == calls and
              reduce_ch.collective_fallbacks == 0,
              f"collective_fused={reduce_ch.collective_fused} "
              f"fallbacks={reduce_ch.collective_fallbacks}")
    finally:
        for sub in subs:
            sub.close()
        for srv in servers:
            srv.stop()
            srv.join(5)

    # 3) the collective pipeline (ring_scan, all_to_all_reshard,
    # ring_attention, ...) at real shapes, every result checked
    steps = graft.collective_steps(make_rpc_mesh(1, n, devices=devs),
                                   **smoke.size("collective"))
    check(steps["devices"] == sorted(d.id for d in devs),
          f"ring attention ran on {steps['devices']}")
    return {"responses_landed_on": landed,
            "shard_block_bytes": rows * cols * 2,
            "collective_fused": reduce_ch.collective_fused,
            "collective_fallbacks": reduce_ch.collective_fallbacks,
            "collective_steps": steps}


# ------------------------------------------------------------------ main

def _versions() -> dict:
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = None
    return out


def _cache_entries(path) -> int:
    try:
        return len(os.listdir(path)) if path else 0
    except OSError:
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, pallas_interpret: debugs the "
                         "command, proves nothing about the chip")
    ap.add_argument("--require-chips", type=int, default=1,
                    help="fewer devices than this is an error")
    ap.add_argument("--only", default="",
                    help="comma-separated phases (debugging; the summary "
                         "says which ran): " + ",".join(PHASES))
    ap.add_argument("--inject-failure", default="", choices=("",) + PHASES,
                    help="rehearsal only: make this phase fail, to prove "
                         "the exit status follows")
    args = ap.parse_args(argv)
    if args.inject_failure and not args.rehearse:
        ap.error("--inject-failure needs --rehearse")
    only = tuple(p for p in args.only.split(",") if p)
    for p in only:
        if p not in PHASES:
            ap.error(f"unknown phase {p!r}")

    faulthandler.enable()
    # a hang must end as stacks and a non-zero status, inside the limit
    faulthandler.dump_traceback_later(WALL_LIMIT_S, exit=True)

    if args.rehearse:
        # an explicit rehearsal: never touch a chip, and give the
        # four-chip phase its four (virtual) devices
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from brpc_tpu import native
    from brpc_tpu.butil.jax_runtime import ensure_compile_cache
    from brpc_tpu.native import fastcore

    cache_dir = ensure_compile_cache()
    cache = {"dir": cache_dir, "entries_before": _cache_entries(cache_dir),
             "hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device}")
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {device['platform']!r}, "
              "not a TPU; nothing here falls back to the CPU "
              "(--rehearse is the explicit CPU run)", file=sys.stderr)
        return 2
    if device["count"] < args.require_chips:
        print(f"chip_smoke: {device['count']} device(s), "
              f"--require-chips {args.require_chips}", file=sys.stderr)
        return 2

    summary = {
        "ok": False,
        "device": device,
        "rehearsal": args.rehearse,
        "versions": _versions(),
        "native": native.available(),
        "fastcore": fastcore.available(),
        "compile_cache": cache,
        "phases": {},
    }
    smoke = Smoke(args.rehearse)
    runners = {"fabric": phase_fabric, "serving": phase_serving,
               "kernel": phase_kernel, "four_chips": phase_four_chips}
    for name in PHASES:
        if only and name not in only:
            continue
        if name == "four_chips" and device["count"] < 4:
            summary["phases"][name] = {"ok": None,
                                       "skipped": "fewer than 4 devices"}
            continue
        t0 = time.monotonic()
        log(f"phase {name} ...")
        try:
            if args.inject_failure == name:
                raise AssertionError("injected failure (--inject-failure)")
            seen = runners[name](smoke)
            summary["phases"][name] = {"ok": True, **seen}
        except Exception as e:  # noqa: BLE001 - one summary always
            traceback.print_exc(file=sys.stderr)
            summary["phases"][name] = {
                "ok": False, "error": f"{type(e).__name__}: {e}"[:1500]}
        summary["phases"][name]["wall_s"] = round(time.monotonic() - t0, 1)
        log(f"phase {name}: "
            f"{'ok' if summary['phases'][name]['ok'] else 'FAILED'}")

    problems = [n for n, p in summary["phases"].items() if p["ok"] is False]
    if only:
        summary["only"] = list(only)
    if not (summary["native"] and summary["fastcore"]):
        problems.append("native core did not load")
    cache["entries_after"] = _cache_entries(cache_dir)
    summary["problems"] = problems
    summary["ok"] = not problems
    summary["wall_s"] = round(time.monotonic() - _T0, 1)
    summary["claim"] = None
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(summary, default=str))
    # the last line is the driver's contract: these keys and no others
    print(json.dumps({"ok": summary["ok"], "device": device}), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    # the normal interpreter exit, not os._exit: a teardown abort shows
    sys.exit(main())
