"""The ``longtail_echo`` deployment: upstream's fixed-QPS benchmark with
1% long-tail requests (apache/brpc docs/cn/benchmark.md), rebuilt on
``ici://``: one ``Server`` on one chip, N ``Channel``s each on its own
connection, arrivals on the callers' clock.

``Echo`` returns the request's device arrays. ``SlowStep`` is a sync
handler that waits for a dependency: it runs ``Perf.Step``'s program
(``jit_perf_step``, the same resident weights: call i uses layer i mod
layers) once on the request's array, then holds its thread until
``long_hold_ms`` after handler entry, then answers with the result.
``Hold`` is ``Perf``'s: one slow reply a connection in set-up, for the
lane's window grant.

Traffic keys: ``connections``, ``long_share``, ``long_hold_ms``,
``payload_bytes`` (the short sizes), ``pool`` (distinct seeded requests
per size and for SlowStep, used in rotation); the rate is the driver's.

What ``correct`` holds a run to, and why these limits:
- echo bit exact (limit 0: nothing computes);
- ``SlowStep`` within ``STEP_ATOL`` = 2^-4 of the float32 reference at
  matmul precision "highest": ``Perf.Step``'s limit for ``Perf.Step``'s
  program and widths (two bf16 ulps at |y| < 8; services/perf.py);
- every tag back and every payload on the reply device (the driver's
  completion thread, ``response_arrays``);
- the handlers' stamps equal to the schedule: each sequence number of
  the window handled exactly once, as its kind, on its connection (a
  dropped, doubled, merged or misrouted arrival is another workload);
- every ``SlowStep`` handler ran at least ``long_hold_ms`` by the
  benchmark's stamps around it (a shorter hold is a different workload,
  not a faster one)."""

from __future__ import annotations

import math
import time

from benchmark.lib.fabric import Fabric, fresh
from benchmark.lib.stamps import now_ns, seq_of, tag_of
from benchmark.lib.verify import DeviceVerifier
from benchmark.reference import longtail as reference
from benchmark.services.perf import HOLD_S, STEP_ATOL

SERVICE = "LongTail"
FIRST_SEQ = 4096        # warm-up uses the sequence numbers below it
SHORT, LONG = 0, 1      # the ``shard`` field of a handler's stamp


def build(ctx):
    return LongTailDeployment(ctx)


class LongTailDeployment:
    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = ctx.cell.traffic
        self.layout = ctx.cell.config["layout"]
        self.connections = int(self.traffic["connections"])
        if self.connections != int(self.layout["connections"]):
            raise ValueError("traffic and layout disagree on connections")
        self.long_share = float(self.traffic["long_share"])
        self.hold_s = float(self.traffic["long_hold_ms"]) / 1e3
        self.payload_bytes = [int(n) for n in self.traffic["payload_bytes"]]
        self.pool = int(self.traffic.get("pool", 8))
        self.device = ctx.devices[0]
        self.stamps = ctx.stamps
        self.verifier = DeviceVerifier()
        self.fabric = None
        self.first_seq = FIRST_SEQ
        self._plan: list = []
        self._plan_base = FIRST_SEQ
        self._conn_of: dict = {}    # a caller's address -> its connection
        self._seen: list = []       # (seq, caller's address) per handler

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        """Requests, weights and expectations, made on the device from
        the seed."""
        import jax
        import jax.numpy as jnp

        st = self.ctx.cell.sizes["step"]
        b, dm, dff = st["batch"], st["d_model"], st["d_ff"]
        layers, pool = int(st["layers"]), self.pool
        dtype = jnp.dtype(st["dtype"])
        sizes = self.payload_bytes

        def make(key):
            ke, kx, k1, k2 = jax.random.split(key, 4)
            echo = []
            for k, n in zip(jax.random.split(ke, len(sizes)), sizes):
                rows = jax.random.randint(k, (pool, n), 0, 256,
                                          dtype=jnp.int32).astype(jnp.uint8)
                echo.append(tuple(rows[i] for i in range(pool)))
            xs = jax.random.normal(kx, (pool, b, dm), dtype)
            w_in = (jax.random.normal(k1, (layers, dm, dff), dtype)
                    * 0.02).astype(dtype)
            w_out = (jax.random.normal(k2, (layers, dff, dm), dtype)
                     * 0.02).astype(dtype)
            return (tuple(echo), tuple(xs[i] for i in range(pool)),
                    tuple(w_in[i] for i in range(layers)),
                    tuple(w_out[i] for i in range(layers)))
        self.requests, self.xs, self.w_in, self.w_out = jax.jit(make)(
            jax.random.PRNGKey(self.ctx.seed))
        self.layers = layers

        def perf_step(x, w_in, w_out):
            h = jnp.maximum(x @ w_in, 0)
            return h @ w_out + x
        # Perf.Step's program under Perf.Step's name: jit_perf_step
        self.step = jax.jit(perf_step)
        ref = jax.jit(reference.slow_step_reference)
        # call i uses input i mod pool and layer i mod layers
        self.expected = {
            (i % pool, i % layers): ref(self.xs[i % pool],
                                        self.w_in[i % layers],
                                        self.w_out[i % layers])
            for i in range(math.lcm(pool, layers))}
        for si in range(len(sizes)):
            self.verifier.declare(("echo", si), 0)
        self.verifier.declare("slow_step", STEP_ATOL)
        if self.ctx.inject == "corrupt_response":
            self._corrupt = jax.jit(lambda a: a + jnp.asarray(1, a.dtype))

    def start(self) -> None:
        from brpc_tpu.rpc import Channel, ChannelOptions, Service

        svc = Service(SERVICE)
        svc.register_method("Echo", self.stamps.wrap_handler(
            self._echo, shard=SHORT))
        svc.register_method("SlowStep", self.stamps.wrap_handler(
            self._slow_step, shard=LONG))
        svc.register_method("Hold", self._hold)
        # Fabric dials one channel a server; the other connections are
        # channels of the same options to the same server, closed with it
        self.fabric = Fabric(self.layout, [svc])
        dial = self.layout["dial"].format(
            port=self.fabric.servers[0].endpoint.port)
        opts = ChannelOptions(**self.layout["channel_options"])
        while len(self.fabric.channels) < self.connections:
            self.fabric.channels.append(Channel(dial, opts))

    # ---------------------------------------------------------- handlers
    def _corrupted(self, seq: int) -> bool:
        return (self.ctx.inject == "corrupt_response"
                and seq >= self.first_seq and seq % 7 == 3)

    def _echo(self, cntl, request):
        seq = seq_of(request)
        self._seen.append((seq, str(cntl.remote_side)))
        arrs = list(cntl.request_device_arrays or ())
        if arrs and self._corrupted(seq):
            arrs = [self._corrupt(a) for a in arrs]
        cntl.response_device_arrays = arrs
        return bytes(request)

    def _slow_step(self, cntl, request):
        t0 = now_ns()
        seq = seq_of(request)
        self._seen.append((seq, str(cntl.remote_side)))
        layer = seq % self.layers
        y = self.step(cntl.request_device_arrays[0], self.w_in[layer],
                      self.w_out[layer])
        if self._corrupted(seq):
            y = self._corrupt(y)
        # the dependency this handler waits for: its thread is held
        # until hold_s after handler entry, whatever the enqueue took
        left = self.hold_s - (now_ns() - t0) / 1e9
        if left > 0:
            time.sleep(left)
        cntl.response_device_arrays = [y]
        return bytes(request)

    def _hold(self, cntl, request):
        """``Perf.Hold``: a reply slower than ``ici_idle_ack_ms``, once a
        connection in set-up, so that the server's bare ACK brings the
        lane's window grant. It also tells the service which caller's
        address is which connection."""
        self._conn_of[str(cntl.remote_side)] = seq_of(request)
        time.sleep(HOLD_S)
        cntl.response_device_arrays = list(cntl.request_device_arrays or ())
        return bytes(request)

    # ------------------------------------------------------------ client
    def plan(self, rate: float, seconds: float) -> list:
        """The arrivals of a window that starts now: arrival i is the
        call ``first_seq + i``."""
        self._plan = reference.schedule(
            self.ctx.seed, rate, seconds, self.connections,
            self.long_share, len(self.payload_bytes))
        self._plan_base = self.first_seq
        return self._plan

    def _request(self, seq: int, arrival):
        """(method, request array, expected response, verifier key)."""
        if arrival.long:
            key = (seq % self.pool, seq % self.layers)
            return ("SlowStep", self.xs[key[0]], self.expected[key],
                    "slow_step")
        x = self.requests[arrival.size][seq % self.pool]
        return "Echo", x, reference.echo_reference(x), ("echo", arrival.size)

    def _arrival(self, seq: int):
        return self._plan[seq - self._plan_base]

    def call(self, seq: int, done) -> None:
        arrival = self._arrival(seq)
        method, x, _e, _k = self._request(seq, arrival)
        self.fabric.channels[arrival.conn].call(
            SERVICE, method, tag_of(seq), done=done,
            request_device_arrays=[fresh(x)])

    def ready_now(self, cntl) -> bool:
        return all(a.is_ready() for a in cntl.response_device_arrays or ())

    def response_arrays(self, seq: int, cntl) -> list:
        """The response's device arrays, after the host-side checks: the
        call did not fail, the response carries this request's tag, and
        the payload is on the reply device."""
        if cntl.failed():
            raise RuntimeError(f"call failed: {cntl.error_code} "
                               f"{cntl.error_text}")
        if cntl.response_payload.to_bytes() != tag_of(seq):
            raise AssertionError("response carries another request's tag")
        arrs = cntl.response_device_arrays
        if not arrs or len(arrs) != 1:
            raise AssertionError(f"response has {len(arrs or ())} arrays")
        if arrs[0].devices() != {self.device}:
            raise AssertionError(f"response on {arrs[0].devices()}, "
                                 f"wanted {self.device}")
        return arrs

    def verify(self, seq: int, cntl, arrs) -> None:
        _m, _x, expected, key = self._request(seq, self._arrival(seq))
        self.verifier.add(key, arrs[0], expected)

    def warm(self) -> int:
        """On every connection: one ``Hold`` (whose tag is the
        connection's number), every short size and one ``SlowStep``
        through the fabric and the verifier (both compile here, in
        set-up). Returns the calls made."""
        calls = 0
        shapes = [reference.Arrival(0.0, 0, False, si)
                  for si in range(len(self.payload_bytes))]
        shapes.append(reference.Arrival(0.0, 0, True, 0))
        for conn, channel in enumerate(self.fabric.channels):
            cntl = channel.call_sync(
                SERVICE, "Hold", tag_of(conn),
                request_device_arrays=[fresh(self.requests[0][0])])
            if cntl.failed():
                raise RuntimeError(f"Hold failed: {cntl.error_text}")
            for arrival in shapes:
                seq = calls = calls + 1
                method, x, expected, key = self._request(seq, arrival)
                cntl = channel.call_sync(SERVICE, method, tag_of(seq),
                                         request_device_arrays=[fresh(x)])
                arrs = self.response_arrays(seq, cntl)
                self.verifier.warm(key, arrs[0], expected)
        if calls >= FIRST_SEQ:
            raise AssertionError("warm-up ran into the window's numbers")
        held = len(self.fabric.servers[0].connections())
        if held != self.connections or \
                sorted(self._conn_of.values()) != list(range(held)):
            raise AssertionError(
                f"the server holds {held} connections from "
                f"{len(self._conn_of)} callers, the configuration says "
                f"{self.connections}, each its own")
        return calls + self.connections

    def finish(self) -> int:
        """Holds the handlers' stamps to the window's schedule (what
        fails is a failed call, with its reason) and returns the
        responses that differ from the reference."""
        base, plan = self._plan_base, self._plan
        stamped: dict = {}
        for seq, kind, t0, t1 in self.stamps.handlers:
            if seq >= base:
                stamped.setdefault(seq, []).append((kind, t0, t1))
        conn_seen = {seq: self._conn_of.get(addr)
                     for seq, addr in self._seen if seq >= base}
        for seq in sorted(set(stamped) - set(range(base, base + len(plan)))):
            self.stamps.fail(seq, "handled but never scheduled")
        for i, arrival in enumerate(plan):
            seq = base + i
            runs = stamped.get(seq, ())
            if len(runs) != 1:
                self.stamps.fail(seq, f"scheduled once, handled "
                                 f"{len(runs)} times")
                continue
            kind, t0, t1 = runs[0]
            if kind != (LONG if arrival.long else SHORT):
                self.stamps.fail(seq, "handled as the other kind")
            elif conn_seen.get(seq) != arrival.conn:
                self.stamps.fail(seq, f"scheduled on connection "
                                 f"{arrival.conn}, handled on "
                                 f"{conn_seen.get(seq)}")
            elif arrival.long and t1 - t0 < \
                    float(self.traffic["long_hold_ms"]) * 1e6:
                self.stamps.fail(seq, f"SlowStep held {(t1 - t0) / 1e6:.3f}"
                                 f" ms, under long_hold_ms")
        return self.verifier.finish()

    def describe(self) -> dict:
        return {"lanes": self.fabric.assert_lanes(),
                "connections": self.connections, "pool": self.pool}

    def close(self) -> None:
        if self.fabric is not None:
            self.fabric.close()
