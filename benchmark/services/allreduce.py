"""The ``parallel_allreduce`` deployment: a tensor allreduce as the
stock ``ParallelChannel`` fan-out, N lane RPCs a call.

N servers in the one process, server i on chip i; the caller on chip 0
holds one ``ParallelChannel`` over N sub channels that all reply to
chip 0. A call sends one request on chip 0 through a row-slicing
``CallMapper`` (block i to shard i), shard i answers its block times 2
on chip i, and a summing ``ResponseMerger`` adds the N replies on
chip 0.

Traffic keys: ``pool`` (distinct seeded requests, used in rotation)."""

from __future__ import annotations

import threading

from benchmark.lib.fabric import Fabric
from benchmark.lib.stamps import seq_of, tag_of
from benchmark.lib.verify import DeviceVerifier
from benchmark.reference import allreduce as reference

SERVICE = "Mesh"
METHOD = "Shard"
MERGE_WAIT_S = 5.0


def build(ctx):
    return AllreduceDeployment(ctx)


class AllreduceDeployment:
    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = ctx.cell.traffic
        self.n = len(ctx.cell.config["layout"]["servers"])
        self.devices = ctx.devices[:self.n]
        self.pool = int(self.traffic.get("pool", 4))
        self.stamps = ctx.stamps
        self.verifier = DeviceVerifier(batch=4)
        self.fabric = None
        # warm-up uses the sequence numbers below this one
        self.first_seq = self.pool
        self.misplaced: list = []
        self.late_merges = 0

    def prepare(self) -> None:
        import jax
        import jax.numpy as jnp

        rows, cols = self.ctx.cell.sizes["shard_block"]
        n, pool = self.n, self.pool
        dtype = jnp.dtype(self.ctx.cell.sizes["dtype"])

        def make(key):
            # small integers: every product and N-way sum is exact in bf16
            big = jax.random.randint(key, (pool, n * rows, cols), -8, 9)
            big = big.astype(dtype)
            return tuple(big[i] for i in range(pool))
        with jax.default_device(self.devices[0]):
            self.requests = jax.jit(make)(jax.random.PRNGKey(self.ctx.seed))
            ref = jax.jit(reference.allreduce_reference, static_argnums=1)
            self.expected = [ref(r, n) for r in self.requests]
        # the mapper's slice of block i, the shard's body, the merger's sum
        self.take_block = [
            jax.jit(lambda big, i=i: jax.lax.slice_in_dim(
                big, i * rows, (i + 1) * rows, axis=0))
            for i in range(n)]
        self.shard_body = jax.jit(lambda s: s * 2)
        self.sum_replies = jax.jit(lambda *parts: sum(parts[1:], parts[0]))
        self.verifier.declare("allreduce", 0)
        if self.ctx.inject == "corrupt_response":
            self._corrupt = jax.jit(lambda a: a + jnp.asarray(1, a.dtype))

    def start(self) -> None:
        from brpc_tpu.rpc import Service
        from brpc_tpu.rpc.combo_channels import (CallMapper, ResponseMerger,
                                                 SubCall)

        dep = self

        class SliceRows(CallMapper):
            def map(self, sub_index, nsub, service, method, request, cntl):
                big = cntl.request_device_arrays[0]
                return SubCall(service, method, request, device_arrays=[
                    dep.take_block[sub_index](big)])

        class SumOnCaller(ResponseMerger):
            """Keeps the replies by shard; whoever brings the last one
            sums them on the caller's chip."""

            def merge(self, final_cntl, sub_index, sub_cntl):
                st = final_cntl.__dict__["bench_merge"]
                part = sub_cntl.response_device_arrays[0]
                if sub_cntl.response_payload.to_bytes() != st["tag"]:
                    st["error"] = "a shard answered another request's tag"
                if part.devices() != {dep.devices[0]}:
                    st["error"] = (f"shard {sub_index} replied on "
                                   f"{part.devices()}")
                with st["lock"]:
                    st["parts"][sub_index] = part
                    last = all(p is not None for p in st["parts"])
                if last:
                    st["sum"] = dep.sum_replies(*st["parts"])
                    st["ready"].set()

        services = []
        for i in range(self.n):
            svc = Service(SERVICE)
            svc.register_method(METHOD, self.stamps.wrap_handler(
                self._make_shard(i), shard=i))
            services.append(svc)
        self.fabric = Fabric(self.ctx.cell.config["layout"], services,
                             call_mapper=SliceRows(),
                             response_merger=SumOnCaller())

    def _make_shard(self, idx: int):
        device = self.devices[idx]

        def shard(cntl, request):
            s = cntl.request_device_arrays[0]
            if s.devices() != {device}:
                self.misplaced.append(f"shard {idx} saw its request on "
                                      f"{s.devices()}")
            out = self.shard_body(s)
            seq = seq_of(request)
            if (self.ctx.inject == "corrupt_response" and idx == 1
                    and seq >= self.first_seq and seq % 7 == 3):
                out = self._corrupt(out)
            cntl.response_device_arrays = [out]
            return bytes(request)
        return shard

    # ------------------------------------------------------------ client
    def _controller(self, seq: int):
        from brpc_tpu.rpc import Controller

        cntl = Controller()
        cntl.request_device_arrays = [self.requests[seq % self.pool]]
        cntl.__dict__["bench_merge"] = {
            "tag": tag_of(seq), "lock": threading.Lock(),
            "parts": [None] * self.n, "ready": threading.Event(),
            "sum": None, "error": None}
        return cntl

    def call(self, seq: int, done) -> None:
        self.fabric.combo.call(SERVICE, METHOD, tag_of(seq),
                               cntl=self._controller(seq), done=done)

    def call_sync(self, seq: int):
        cntl = self.fabric.combo.call(SERVICE, METHOD, tag_of(seq),
                                      cntl=self._controller(seq))
        if not cntl.join(self.ctx.cell.config["layout"]["channel_options"]
                         ["timeout_ms"] / 1000.0 + 5.0):
            raise RuntimeError("fan-out call did not complete")
        return cntl

    def ready_now(self, cntl) -> bool:
        st = cntl.__dict__["bench_merge"]
        return st["ready"].is_set() and (st["sum"] is None
                                         or st["sum"].is_ready())

    def response_arrays(self, seq: int, cntl) -> list:
        if cntl.failed():
            raise RuntimeError(f"call failed: {cntl.error_code} "
                               f"{cntl.error_text} {cntl.sub_errors}")
        st = cntl.__dict__["bench_merge"]
        if not st["ready"].is_set():
            # ParallelChannel completes the call when the last sub call
            # is COUNTED; another thread may still be inside merge()
            self.late_merges += 1
            if not st["ready"].wait(MERGE_WAIT_S):
                raise AssertionError("the merged sum never arrived")
        if st["error"]:
            raise AssertionError(st["error"])
        if st["sum"].devices() != {self.devices[0]}:
            raise AssertionError(f"sum on {st['sum'].devices()}")
        return [st["sum"]]

    def verify(self, seq: int, cntl, arrs) -> None:
        self.verifier.add("allreduce", arrs[0],
                          self.expected[seq % self.pool])

    def warm(self) -> int:
        for seq in range(self.pool):
            cntl = self.call_sync(seq)
            arrs = self.response_arrays(seq, cntl)
            self.verifier.warm("allreduce", arrs[0],
                               self.expected[seq % self.pool])
        return self.pool

    def finish(self) -> int:
        if self.misplaced:
            raise AssertionError(f"requests off their chip: "
                                 f"{self.misplaced[:4]}")
        return self.verifier.finish()

    def describe(self) -> dict:
        combo = self.fabric.combo
        return {"lanes": self.fabric.assert_lanes(), "shards": self.n,
                "collective_fused": combo.collective_fused,
                "collective_fallbacks": combo.collective_fallbacks,
                "late_merges": self.late_merges}

    def close(self) -> None:
        if self.fabric is not None:
            self.fabric.close()
