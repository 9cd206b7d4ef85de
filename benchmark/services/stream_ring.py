"""The ``streaming_echo`` deployment: upstream's streaming_echo example
as a ring of N peers on N chips, device arrays as frames.

Peer i has a ``Server`` on chip i and one ``Channel`` to peer (i+1) mod
N, on which it opens one stream in set-up by a unary ``Open`` call
(``stream_options=`` on the caller, ``stream_accept`` in the handler).
Data runs clockwise. Peer 0 injects frame ``seq`` with ``write_nowait``:
an 8-byte tag as the frame's host payload and one array born on chip 0.
Each acceptor's ``on_received`` (peer 1, 2, ... then 0) adds one on its
own chip and, except peer 0, forwards tag and result on its own stream
with ``await stream.write`` from the drainer fiber, so back-pressure
passes from hop to hop. Peer 0's ``on_received`` completes the frame.
The reverse direction of every stream carries flow control only.

A "call" of the driver is one frame's circuit: injected at peer 0 to
its result ready on chip 0. Everything goes through ``Stream.write``/
``write_nowait``, ``stream_accept`` and ``Socket.write``; a write that
returns False is a failed frame, counted, never retried.

Traffic keys: ``pool`` (distinct seeded frames, used in rotation) and
``hops`` (the peers of the ring, checked against the layout)."""

from __future__ import annotations

import threading

from benchmark.lib import stream_frames
from benchmark.lib.fabric import Fabric, fresh
from benchmark.lib.stamps import now_ns, seq_of, tag_of
from benchmark.lib.verify import DeviceVerifier
from benchmark.reference import stream_ring as reference

SERVICE = "Ring"
CIRCUIT_WAIT_S = 20.0
# the ring of rpcz spans holds 16,384 by default; a traced window's last
# 2 s leave four spans a hop (two halves, device, device-recv)
RPCZ_SPANS = 1 << 16


def build(ctx):
    return RingDeployment(ctx)


class Ring(Fabric):
    """N servers, and channel i to server (i+1) mod N replying to chip
    i; lanes asserted and everything closed as ``Fabric`` does."""

    def __init__(self, layout: dict, services: list):
        from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                                  ServerOptions)

        self.layout = layout
        self.servers, self.channels = [], []
        self.combo = None
        opts = ChannelOptions(**layout["channel_options"])
        try:
            ports = []
            for listen, svc in zip(layout["servers"], services):
                srv = Server(ServerOptions(enable_builtin_services=False))
                srv.add_service(svc)
                self.servers.append(srv)
                ports.append(srv.start(listen).port)
            for i in range(len(ports)):
                self.channels.append(Channel(layout["dial"].format(
                    port=ports[(i + 1) % len(ports)], device=i), opts))
        except Exception:
            self.close()
            raise


class Circuit:
    """One frame on its way round: what the driver gets as ``cntl``."""

    __slots__ = ("seq", "done", "tag", "array", "error")

    def __init__(self, seq: int, done):
        self.seq = seq
        self.done = done
        self.tag = self.array = self.error = None


class RingDeployment:
    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = ctx.cell.traffic
        self.n = len(ctx.cell.config["layout"]["servers"])
        if int(self.traffic["hops"]) != self.n:
            raise ValueError(f"the traffic says {self.traffic['hops']} hops, "
                             f"the layout has {self.n} peers")
        self.devices = ctx.devices[:self.n]
        self.pool = int(self.traffic.get("pool", 8))
        self.stamps = ctx.stamps
        self.verifier = DeviceVerifier(batch=8)
        self.ring = None
        # warm-up uses the sequence numbers below this one
        self.first_seq = self.pool
        self.out = [None] * self.n          # peer i's stream to peer i+1
        self.accepted = [None] * self.n     # peer i's stream from peer i-1
        self.expect = [0] * self.n          # the next tag peer i must see
        self.pending: dict = {}             # seq -> Circuit under way
        self.problems: list = []

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        import jax
        import jax.numpy as jnp

        from brpc_tpu.butil.flags import set_flag

        set_flag("rpcz_max_spans", RPCZ_SPANS)
        rows, cols = self.ctx.cell.sizes["frame"]
        pool = self.pool
        dtype = jnp.dtype(self.ctx.cell.sizes["dtype"])

        def make(key):
            # small integers: x + N is exact in bf16 (8 bits of
            # mantissa hold every integer to 256) and not in a float8,
            # so the exact comparison tells the two precisions apart
            big = jax.random.randint(key, (pool, rows, cols), -100, 101)
            big = big.astype(dtype)
            return tuple(big[i] for i in range(pool))
        with jax.default_device(self.devices[0]):
            self.frames = jax.jit(make)(jax.random.PRNGKey(self.ctx.seed))
            want = reference.ring_reference(
                [(tag_of(i), f) for i, f in enumerate(self.frames)], self.n)
            self.expected = [a for _tag, a in want]
        # one program; a hop's input is committed to its chip, so each
        # chip builds it once, in warm()
        self.body = jax.jit(lambda x: x + jnp.asarray(1, x.dtype))
        self.verifier.declare("ring", 0)
        if self.ctx.inject == "corrupt_response":
            self._corrupt = jax.jit(lambda a: a + jnp.asarray(1, a.dtype))

    def start(self) -> None:
        from brpc_tpu.rpc import Service
        from brpc_tpu.rpc.stream import StreamOptions, stream_accept

        services = []
        for i in range(self.n):
            svc = Service(SERVICE)

            def open_(cntl, request, i=i):
                self.accepted[i] = stream_accept(cntl, StreamOptions(
                    on_received=self._make_hop(i)))
                if self.accepted[i] is None:
                    raise RuntimeError("Open carried no stream")
                return b"accepted"
            svc.register_method("Open", open_)
            services.append(svc)
        self.ring = Ring(self.ctx.cell.config["layout"], services)
        for i, ch in enumerate(self.ring.channels):
            # default StreamOptions: what a user gets
            cntl = ch.call_sync(SERVICE, "Open", b"",
                                stream_options=StreamOptions())
            if cntl.failed() or cntl.stream is None:
                raise RuntimeError(f"peer {i} could not open its stream: "
                                   f"{cntl.error_text}")
            self.out[i] = cntl.stream

    # -------------------------------------------------------------- hops
    def _make_hop(self, idx: int):
        device = self.devices[idx]
        span = self.stamps.span
        handlers = self.stamps.handlers

        def check(msg):
            """(seq, tag, x + 1 on this chip) of a frame just delivered,
            after the order and placement checks."""
            tag = msg.payload.to_bytes()
            seq = seq_of(tag)
            if seq != self.expect[idx]:
                self.problems.append(f"peer {idx} saw frame {seq}, the "
                                     f"next in order is {self.expect[idx]}")
            self.expect[idx] = seq + 1
            arrs = msg.device_arrays
            if len(arrs) != 1 or arrs[0].devices() != {device}:
                self.problems.append(
                    f"peer {idx} saw frame {seq} with {len(arrs)} arrays"
                    f" on {[a.devices() for a in arrs]}")
            y = self.body(arrs[0])
            if (self.ctx.inject == "corrupt_response" and idx == 2
                    and seq >= self.first_seq and seq % 7 == 3):
                y = self._corrupt(y)
            return seq, tag, y

        async def forward(stream, msg):
            t0 = now_ns()
            # the annotation is the thread's own: it may not span the
            # await, after which the fiber can run on another thread
            with span("bench.handler"):
                seq, tag, y = check(msg)
            sent = await self.out[idx].write(tag, device_arrays=[y])
            handlers.append((seq, idx, t0, now_ns()))
            if not sent:
                self._complete(seq, error=f"peer {idx}'s write returned "
                               "False")

        def arrive(stream, msg):
            t0 = now_ns()
            with span("bench.handler"):
                seq, tag, y = check(msg)
            handlers.append((seq, idx, t0, now_ns()))
            self._complete(seq, tag=tag, array=y)
        return arrive if idx == 0 else forward

    def _complete(self, seq: int, tag=None, array=None, error=None) -> None:
        c = self.pending.pop(seq, None)
        if c is None:
            self.problems.append(f"frame {seq} came round a second time, "
                                 "or was never injected")
            return
        c.tag, c.array, c.error = tag, array, error
        c.done(c)

    # ------------------------------------------------------------ client
    def call(self, seq: int, done) -> None:
        """Inject frame ``seq`` at peer 0; ``done(circuit)`` fires on the
        fabric's thread when it has come round."""
        if seq == self.first_seq:
            stream_frames.mark_window_start()
        self.pending[seq] = Circuit(seq, done)
        frame = fresh(self.frames[seq % self.pool])
        if not self.out[0].write_nowait(tag_of(seq), device_arrays=[frame]):
            self._complete(seq, error="peer 0's write_nowait returned False")

    def call_sync(self, seq: int):
        ready = threading.Event()
        got = []
        self.call(seq, lambda c: (got.append(c), ready.set()))
        if not ready.wait(CIRCUIT_WAIT_S):
            raise RuntimeError(f"frame {seq} did not come round")
        return got[0]

    def ready_now(self, c) -> bool:
        return c.array is not None and c.array.is_ready()

    def response_arrays(self, seq: int, c) -> list:
        if c.error:
            raise RuntimeError(f"frame failed: {c.error}")
        if c.tag != tag_of(seq):
            raise AssertionError("the frame carries another frame's tag")
        return [c.array]

    def verify(self, seq: int, c, arrs) -> None:
        self.verifier.add("ring", arrs[0], self.expected[seq % self.pool])

    def warm(self) -> int:
        for seq in range(self.pool):
            c = self.call_sync(seq)
            arrs = self.response_arrays(seq, c)
            self.verifier.warm("ring", arrs[0],
                               self.expected[seq % self.pool])
        return self.pool

    def finish(self) -> int:
        stream_frames.mark_window_end()
        for name, streams in (("out", self.out), ("accepted", self.accepted)):
            for i, s in enumerate(streams):
                if s.closed or s.remote_closed:
                    self.problems.append(f"peer {i}'s {name} stream closed")
        if self.pending:
            self.problems.append(f"{len(self.pending)} frames never came "
                                 f"round: {sorted(self.pending)[:4]}")
        # problems are in describe(); each counts as a bad response
        return self.verifier.finish() + len(self.problems)

    def describe(self) -> dict:
        out = {"lanes": self.ring.assert_lanes(), "peers": self.n,
               "pool": self.pool, "ring_problems": self.problems[:8]}
        if hasattr(self.out[0], "counters"):
            # edge i: the writer's stream at peer i and the acceptor's at
            # peer i+1 each count their own side
            out["edges"] = [
                {k: v + r.counters()[k] for k, v in w.counters().items()}
                for w, r in zip(self.out,
                                self.accepted[1:] + self.accepted[:1])]
        return out

    def close(self) -> None:
        for s in self.out + self.accepted:
            if s is not None:
                s.close()
        if self.ring is not None:
            self.ring.close()
