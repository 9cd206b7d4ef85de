"""The ``collective_allreduce`` deployment: ``parallel_allreduce``'s four
servers and channel with the lowering armed, so that a call runs as ONE
XLA collective program and sends no message.

The same four ``Server``s on ``ici://`` (server i on chip i, the shard's
handler answers its block times 2) and one ``ParallelChannel`` over four
sub channels that reply to chip 0, built with the program's stock
``RowScatterMapper`` and ``SumMerger``. Set-up sends every pooled
request once through that channel's un-lowered fan-out, then attaches a
``CollectiveChannel`` over the four chips for ``(Mesh, Shard)`` with the
shard body ``s * 2`` and sends every pooled request once more, lowered;
both must equal the reference, which ties the client's shard function
to the servers' handler. In the window every call must be lowered.

Traffic keys: ``pool`` (distinct seeded requests, used in rotation)."""

from __future__ import annotations

from benchmark.lib import collective_calls
from benchmark.lib.fabric import Fabric
from benchmark.lib.stamps import tag_of
from benchmark.reference import collective_allreduce as reference
from benchmark.services.allreduce import (METHOD, SERVICE,
                                          AllreduceDeployment)
# the stock pair the lowering is defined for: a program without them
# cannot run this deployment, and says so here, at once
from brpc_tpu.rpc.combo_channels import RowScatterMapper, SumMerger


def build(ctx):
    return CollectiveAllreduceDeployment(ctx)


class CollectiveAllreduceDeployment(AllreduceDeployment):
    def __init__(self, ctx):
        super().__init__(ctx)
        # warm-up: every pooled request fanned out, then lowered
        self.first_seq = 2 * self.pool
        self.problems: list = []
        self.attempted = 0

    def prepare(self) -> None:
        import jax

        super().prepare()
        # committed to chip 0, as a caller's tensor is
        self.requests = [jax.device_put(r, self.devices[0])
                         for r in self.requests]
        ref = jax.jit(reference.allreduce_reference, static_argnums=1)
        with jax.default_device(self.devices[0]):
            self.expected = [ref(r, self.n) for r in self.requests]

    def start(self) -> None:
        from brpc_tpu.rpc import Service

        services = []
        for i in range(self.n):
            svc = Service(SERVICE)
            svc.register_method(METHOD, self.stamps.wrap_handler(
                self._make_shard(i), shard=i))
            services.append(svc)
        self.fabric = Fabric(self.ctx.cell.config["layout"], services,
                             call_mapper=RowScatterMapper(),
                             response_merger=SumMerger())

    def _arm(self) -> None:
        from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh

        mesh = make_rpc_mesh(1, self.n, devices=self.devices)
        self.fabric.combo.attach_collective(
            CollectiveChannel(mesh), {(SERVICE, METHOD): lambda s: s * 2})

    # ------------------------------------------------------------ client
    def _controller(self, seq: int):
        from brpc_tpu.rpc import Controller

        cntl = Controller()
        cntl.request_device_arrays = [self.requests[seq % self.pool]]
        return cntl

    def call(self, seq: int, done=None):
        if seq == self.first_seq:
            # the window's first call (the driver's short warm run moved
            # first_seq past itself)
            collective_calls.mark_window_start()
            self.attempted = 0
        self.attempted += 1
        return self.fabric.combo.call(SERVICE, METHOD, tag_of(seq),
                                      cntl=self._controller(seq), done=done)

    def call_sync(self, seq: int):
        cntl = self.call(seq)
        if not cntl.join(25.0):     # a lowered call is complete already
            raise RuntimeError("the call did not complete")
        return cntl

    def ready_now(self, cntl) -> bool:
        arrs = cntl.response_device_arrays
        return bool(arrs) and arrs[0].is_ready()

    def response_arrays(self, seq: int, cntl, lowered: bool = True) -> list:
        if cntl.failed():
            raise RuntimeError(f"call failed: {cntl.error_code} "
                               f"{cntl.error_text} {cntl.sub_errors}")
        if bool(getattr(cntl, "collective_lowered", False)) != lowered:
            raise AssertionError(
                f"the call was {'not ' if lowered else ''}lowered")
        if not lowered and cntl.sub_responses != [tag_of(seq)] * self.n:
            raise AssertionError("a shard answered another request's tag")
        (out,) = cntl.response_device_arrays
        if out.devices() != {self.devices[0]}:
            raise AssertionError(f"sum on {out.devices()}")
        if (self.ctx.inject == "corrupt_response"
                and seq >= self.first_seq and seq % 7 == 3):
            out = self._corrupt(out)    # no handler runs in a lowered call
        return [out]

    def warm(self) -> int:
        for lowered in (False, True):
            if lowered:
                self._arm()
            for i in range(self.pool):
                seq = i + (self.pool if lowered else 0)
                cntl = self.fabric.combo.call(
                    SERVICE, METHOD, tag_of(seq), cntl=self._controller(seq))
                if not cntl.join(25.0):
                    raise RuntimeError("warm-up call did not complete")
                arrs = self.response_arrays(seq, cntl, lowered=lowered)
                self.verifier.warm("allreduce", arrs[0],
                                   self.expected[seq % self.pool])
        combo = self.fabric.combo
        if combo.collective_fused != self.pool or combo.collective_fallbacks:
            raise AssertionError(
                f"set-up lowered {combo.collective_fused} of {self.pool} "
                f"calls, {combo.collective_fallbacks} fell back")
        return 2 * self.pool

    def finish(self) -> int:
        collective_calls.mark_window_end()
        if self.misplaced:
            raise AssertionError(f"requests off their chip: "
                                 f"{self.misplaced[:4]}")
        counts = collective_calls.window_counters()
        if counts is None or counts["fused"] != self.attempted \
                or counts["fallbacks"]:
            self.problems.append(
                f"{self.attempted} calls attempted since the window's "
                f"mark, the program counted {counts}")
        # problems are in describe(); each counts as a bad response
        return self.verifier.finish() + len(self.problems)

    def describe(self) -> dict:
        out = super().describe()
        out.pop("late_merges")
        out.update(calls_since_mark=self.attempted,
                   window_counters=collective_calls.window_counters(),
                   problems=self.problems)
        return out
