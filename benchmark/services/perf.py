"""The ``Perf`` deployment: upstream's rdma_performance echo rebuilt on
``ici://`` plus the repo's flagship device step, on one chip.

``Echo`` returns the request's device arrays. ``Step`` runs the residual
ReLU MLP of ``__graft_entry__.entry`` over weights resident on the
device and returns only the device array: no host sync in the handler,
so the payload is born, consumed and answered on the device. ``Step``
holds ``layers`` resident layers of the same widths; call i uses layer
i mod layers.

Traffic keys: ``method`` ("Echo" or "Step"), ``pool`` (distinct seeded
requests per size, used in rotation), and for Echo ``payload_bytes``
(the sizes a call draws from, uniformly by seed)."""

from __future__ import annotations

import math
import random
import time

from benchmark.lib.fabric import Fabric, fresh
from benchmark.lib.stamps import seq_of, tag_of
from benchmark.lib.verify import DeviceVerifier
from benchmark.reference import perf as reference

SERVICE = "Perf"
# bf16 carries 8 significant bits: one ulp is 2^-5 for 4 <= |y| < 8, the
# largest outputs these widths produce; two ulps cover the bf16 rounding
# of the hidden layer that feeds the second matmul (PR 21's tolerance)
STEP_ATOL = 2 ** -4
N_PICKS = 4093          # the seeded size sequence repeats after this many
HOLD_S = 0.01           # well over ici_idle_ack_ms (2 ms)


def build(ctx):
    return PerfDeployment(ctx)


class PerfDeployment:
    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = ctx.cell.traffic
        self.sizes = ctx.cell.sizes
        self.method = self.traffic["method"]
        self.pool = int(self.traffic.get("pool", 8))
        self.device = ctx.devices[0]
        self.stamps = ctx.stamps
        self.verifier = DeviceVerifier()
        self.fabric = None
        # warm-up uses the sequence numbers below this one
        self.first_seq = N_PICKS

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        """Requests, weights and expectations, made on the device from
        the seed."""
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(self.ctx.seed)
        if self.method == "Echo":
            self.payload_bytes = [int(n) for n in
                                  self.traffic["payload_bytes"]]
            pool = self.pool

            def make(key):
                keys = jax.random.split(key, len(self.payload_bytes))
                out = []
                for k, n in zip(keys, self.payload_bytes):
                    rows = jax.random.randint(k, (pool, n), 0, 256,
                                              dtype=jnp.int32)
                    rows = rows.astype(jnp.uint8)
                    out.append(tuple(rows[i] for i in range(pool)))
                return tuple(out)
            self.requests = jax.jit(make)(key)
            rng = random.Random(self.ctx.seed)
            self.picks = [rng.randrange(len(self.payload_bytes))
                          for _ in range(N_PICKS)]
            for si in range(len(self.payload_bytes)):
                self.verifier.declare(("echo", si), 0)
            if self.ctx.inject == "corrupt_response":
                self._corrupt = jax.jit(lambda a: a + jnp.uint8(1))
        elif self.method == "Step":
            st = self.sizes["step"]
            b, dm, dff = st["batch"], st["d_model"], st["d_ff"]
            layers, pool = int(st["layers"]), self.pool
            dtype = jnp.dtype(st["dtype"])

            def make(key):
                kx, k1, k2 = jax.random.split(key, 3)
                xs = jax.random.normal(kx, (pool, b, dm), dtype)
                w_in = (jax.random.normal(k1, (layers, dm, dff), dtype)
                        * 0.02).astype(dtype)
                w_out = (jax.random.normal(k2, (layers, dff, dm), dtype)
                         * 0.02).astype(dtype)
                return (tuple(xs[i] for i in range(pool)),
                        tuple(w_in[i] for i in range(layers)),
                        tuple(w_out[i] for i in range(layers)))
            self.xs, self.w_in, self.w_out = jax.jit(make)(key)
            self.layers = layers

            def perf_step(x, w_in, w_out):
                h = jnp.maximum(x @ w_in, 0)
                return h @ w_out + x
            # the name the trace reduction looks for: jit_perf_step
            self.step = jax.jit(perf_step)
            ref = jax.jit(reference.step_reference)
            # call i uses input i mod pool and layer i mod layers
            self.period = math.lcm(pool, layers)
            self.expected = [ref(self.xs[i % pool], self.w_in[i % layers],
                                 self.w_out[i % layers])
                             for i in range(self.period)]
            self.verifier.declare("step", STEP_ATOL)
            if self.ctx.inject == "corrupt_response":
                self._corrupt = jax.jit(lambda a: a + jnp.asarray(1, a.dtype))
        else:
            raise ValueError(f"Perf has no method {self.method!r}")

    def start(self) -> None:
        from brpc_tpu.rpc import Service

        svc = Service(SERVICE)
        svc.register_method("Echo", self.stamps.wrap_handler(self._echo))
        svc.register_method("Step", self.stamps.wrap_handler(self._step))
        svc.register_method("Hold", self._hold)
        self.fabric = Fabric(self.ctx.cell.config["layout"], [svc])
        self.channel = self.fabric.channels[0]

    # ---------------------------------------------------------- handlers
    def _corrupted(self, seq: int) -> bool:
        return (self.ctx.inject == "corrupt_response"
                and seq >= self.first_seq and seq % 7 == 3)

    def _echo(self, cntl, request):
        arrs = list(cntl.request_device_arrays or ())
        if arrs and self._corrupted(seq_of(request)):
            arrs = [self._corrupt(a) for a in arrs]
        cntl.response_device_arrays = arrs
        return bytes(request)

    def _hold(self, cntl, request):
        """A reply slower than ``ici_idle_ack_ms``, once in set-up: the
        server then sends a bare ACK, the only frame that carries the
        lane's window grant (2x the hello window). A connection that has
        only played ping-pong faster than that keeps the hello window
        of 32, and more callers than the window deadlock it (PERF.md
        section 6, finding 2)."""
        time.sleep(HOLD_S)
        cntl.response_device_arrays = list(cntl.request_device_arrays or ())
        return bytes(request)

    def _step(self, cntl, request):
        seq = seq_of(request)
        layer = seq % self.layers
        y = self.step(cntl.request_device_arrays[0], self.w_in[layer],
                      self.w_out[layer])
        if self._corrupted(seq):
            y = self._corrupt(y)
        cntl.response_device_arrays = [y]
        return bytes(request)

    # ------------------------------------------------------------ client
    def _request(self, seq: int):
        """(request array, expected response, verifier key)."""
        if self.method == "Echo":
            si = self.picks[seq % N_PICKS]
            x = self.requests[si][seq % self.pool]
            return x, reference.echo_reference(x), ("echo", si)
        return (self.xs[seq % self.pool],
                self.expected[seq % self.period], "step")

    def call(self, seq: int, done) -> None:
        self.channel.call(SERVICE, self.method, tag_of(seq), done=done,
                          request_device_arrays=[
                              fresh(self._request(seq)[0])])

    def call_sync(self, seq: int):
        return self.channel.call_sync(
            SERVICE, self.method, tag_of(seq),
            request_device_arrays=[fresh(self._request(seq)[0])])

    def ready_now(self, cntl) -> bool:
        """Whether the response's payload is ready, without waiting
        (the driver asks on the fabric's callback thread)."""
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
        _x, expected, key = self._request(seq)
        self.verifier.add(key, arrs[0], expected)

    def warm(self) -> int:
        """Every shape this traffic uses once through the fabric and
        the verifier (both compile here, in set-up). Returns the calls
        made; the window's sequence numbers start after them."""
        if self.method == "Echo":
            # one call per (size, pool slot) is not needed: a size is a shape
            seqs, seen = [], set()
            for s in range(N_PICKS):
                if self.picks[s] not in seen:
                    seen.add(self.picks[s])
                    seqs.append(s)
                if len(seen) == len(self.payload_bytes):
                    break
        else:
            seqs = list(range(self.period))
        for seq in seqs:
            cntl = self.call_sync(seq)
            arrs = self.response_arrays(seq, cntl)
            _x, expected, key = self._request(seq)
            self.verifier.warm(key, arrs[0], expected)
        cntl = self.channel.call_sync(
            SERVICE, "Hold", tag_of(0),
            request_device_arrays=[fresh(self._request(seqs[0])[0])])
        if cntl.failed():
            raise RuntimeError(f"Hold failed: {cntl.error_text}")
        return len(seqs) + 1

    def finish(self) -> int:
        return self.verifier.finish()

    def describe(self) -> dict:
        return {"lanes": self.fabric.assert_lanes(),
                "method": self.method, "pool": self.pool}

    def close(self) -> None:
        if self.fabric is not None:
            self.fabric.close()

