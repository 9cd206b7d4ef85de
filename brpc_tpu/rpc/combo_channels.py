"""Combo channels (SURVEY.md §2.6):

  ParallelChannel  — one call fans out to N sub-channels, responses merge
                     (parallel_channel.h: CallMapper :94, ResponseMerger
                     :127, fail_limit :168).
  SelectiveChannel — LB over heterogeneous sub-channels with
                     retry-elsewhere (selective_channel.h:52).
  PartitionChannel — shard fan-out by partition index; each partition is
                     its own server group (partition_channel.h:46-136).

These are host-side fan-outs over arbitrary transports. When every
sub-target is a device on one mesh, prefer parallel/collective.py which
lowers the same shape onto XLA collectives instead of N point-to-point
calls.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.channel import Channel
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.load_balancer import LoadBalancer, new_load_balancer
from brpc_tpu.butil.endpoint import EndPoint

logger = logging.getLogger("brpc_tpu.rpc")


class SubCall:
    """What a CallMapper returns for one sub-channel."""

    __slots__ = ("service", "method", "request", "device_arrays", "skip")

    def __init__(self, service: str, method: str, request: Any,
                 device_arrays: Optional[List] = None, skip: bool = False):
        self.service = service
        self.method = method
        self.request = request
        self.device_arrays = device_arrays
        self.skip = skip

    @classmethod
    def skipped(cls) -> "SubCall":
        return cls("", "", b"", skip=True)


class CallMapper:
    """Maps the logical call onto sub-channel i (parallel_channel.h:94)."""

    def map(self, sub_index: int, nsub: int, service: str, method: str,
            request: Any, cntl: Controller) -> SubCall:
        return SubCall(service, method, request,
                       device_arrays=cntl.request_device_arrays or None)


class ResponseMerger:
    """Folds one finished sub-call into the final controller
    (parallel_channel.h:127). Default: collect payload bytes in order."""

    def merge(self, final_cntl: Controller, sub_index: int,
              sub_cntl: Controller) -> None:
        final_cntl.sub_responses[sub_index] = (
            sub_cntl.response_payload.to_bytes()
            if sub_cntl.response_payload is not None else None)
        if sub_cntl.response_device_arrays:
            final_cntl.sub_device_arrays[sub_index] = \
                sub_cntl.response_device_arrays


class ParallelChannel:
    def __init__(self, fail_limit: Optional[int] = None,
                 call_mapper: Optional[CallMapper] = None,
                 response_merger: Optional[ResponseMerger] = None):
        self._subs: List[Channel] = []
        self.fail_limit = fail_limit
        self.call_mapper = call_mapper or CallMapper()
        self.response_merger = response_merger or ResponseMerger()
        # collective lowering (parallel/collective.py): when every
        # sub-channel rides the device lane of one mesh, the N-sub-call
        # fan-out is the wrong program — attach_collective swaps it for
        # ONE jit'd shard_map op per (service, method)
        self._collective = None
        self._collective_fns: Dict[Any, Callable] = {}
        self._lane_verdict: Optional[bool] = None
        self.collective_fused = 0
        self.collective_fallbacks = 0

    def attach_collective(self, collective,
                          service_fns: Dict[Any, Callable]) -> None:
        """Arm collective lowering: ``collective`` is a
        parallel.collective.CollectiveChannel over the mesh whose
        devices back the sub-channels; ``service_fns`` maps
        ``(service, method)`` to the jax-traceable per-shard function
        equivalent to what that RPC method computes. A device-array
        call to a mapped method then lowers to ONE XLA collective
        (scatter over the shard axis + on-device merge) instead of N
        point-to-point lane RPCs — the fan-out and merge become ICI
        traffic inside one compiled program. Calls that don't qualify
        (host payloads, unmapped methods, a non-device-lane sub) fan
        out exactly as before."""
        self._collective = collective
        self._collective_fns = dict(service_fns)
        self._lane_verdict = None

    def _all_device_lane(self) -> bool:
        """One probe per sub-channel generation: every sub must expose
        a device lane for the fused program to be equivalent (a plain
        TCP sub would silently drop out of a collective)."""
        if self._lane_verdict is None:
            try:
                self._lane_verdict = bool(self._subs) and all(
                    sub.device_lane_kind() is not None
                    for sub in self._subs)
            except Exception:
                self._lane_verdict = False
        return self._lane_verdict

    def _maybe_collective(self, service: str, method: str,
                          cntl: Controller) -> bool:
        """Try the fused path; True means the call completed there.
        Any lowering failure falls back to the per-sub fan-out — the
        optimization must never change call semantics."""
        coll = self._collective
        if coll is None:
            return False
        fn = self._collective_fns.get((service, method))
        if fn is None:
            return False
        arrs = cntl.request_device_arrays
        if not arrs or len(arrs) != 1:
            return False
        if type(self.call_mapper) is not CallMapper:
            # a custom mapper rewrites per-sub requests; the collective
            # can only express the stock scatter shape
            return False
        if len(self._subs) != coll.n_shards or not self._all_device_lane():
            return False
        try:
            out = coll.call(fn, arrs[0])
        except Exception:
            if not self.collective_fallbacks:
                # once per channel: the fan-out below keeps the call's
                # semantics, but a lowering that never works must not
                # look like one that does
                logger.exception(
                    "collective lowering of %s.%s failed; falling back "
                    "to the per-sub fan-out (collective_fallbacks "
                    "counts the rest)", service, method)
            self.collective_fallbacks += 1
            return False
        self.collective_fused += 1
        cntl.collective_lowered = True
        cntl.response_device_arrays = [out]
        cntl._complete()
        return True

    def add_sub_channel(self, ch: Channel) -> None:
        self._subs.append(ch)
        self._lane_verdict = None

    @property
    def sub_channel_count(self) -> int:
        return len(self._subs)

    def call(self, service: str, method: str, request: Any = b"",
             cntl: Optional[Controller] = None,
             done: Optional[Callable] = None, **kw) -> Controller:
        cntl = cntl or Controller()
        cntl._done_cb = done
        # snapshot: DynamicPartitionChannel swaps self._subs atomically on
        # re-partition; one call must see one consistent generation
        subs = self._subs
        nsub = len(subs)
        cntl.sub_responses = [None] * nsub
        cntl.sub_device_arrays = [None] * nsub
        cntl.sub_errors = [None] * nsub
        if nsub == 0:
            cntl.set_failed(berr.EINTERNAL, "no sub channels")
            cntl._complete()
            return cntl
        if self._maybe_collective(service, method, cntl):
            return cntl
        fail_limit = (self.fail_limit if self.fail_limit is not None else nsub)
        state = {"pending": 0, "failed": 0, "done": False}
        lock = threading.Lock()
        sub_calls = []
        for i, sub in enumerate(subs):
            sc = self.call_mapper.map(i, nsub, service, method, request, cntl)
            if sc is None or sc.skip:
                continue
            sub_calls.append((i, sub, sc))
        if not sub_calls:
            cntl.set_failed(berr.EREQUEST, "call mapper skipped every sub call")
            cntl._complete()
            return cntl
        state["pending"] = len(sub_calls)

        def on_sub_done(i):
            def _cb(sub_cntl):
                finish = False
                with lock:
                    if state["done"]:
                        return
                    if sub_cntl.failed():
                        state["failed"] += 1
                        cntl.sub_errors[i] = (sub_cntl.error_code,
                                              sub_cntl.error_text)
                    state["pending"] -= 1
                    if state["failed"] >= fail_limit or state["pending"] == 0:
                        state["done"] = True
                        finish = True
                if not sub_cntl.failed():
                    try:
                        self.response_merger.merge(cntl, i, sub_cntl)
                    except Exception as e:
                        with lock:
                            state["failed"] += 1
                        cntl.sub_errors[i] = (berr.ERESPONSE,
                                              f"merger failed: {e}")
                if finish:
                    if state["failed"] >= fail_limit:
                        cntl.set_failed(
                            berr.ETOOMANYFAILS,
                            f"{state['failed']}/{len(sub_calls)} sub calls failed")
                    cntl._complete()
            return _cb

        for i, sub, sc in sub_calls:
            sub.call(sc.service, sc.method, sc.request,
                     done=on_sub_done(i),
                     request_device_arrays=sc.device_arrays, **kw)
        return cntl

    def call_sync(self, service, method, request=b"", timeout_s: float = 30.0,
                  **kw) -> Controller:
        cntl = self.call(service, method, request, **kw)
        cntl.join(timeout_s)
        return cntl


class SelectiveChannel:
    """Pick ONE healthy sub-channel per call; retries go to a different
    one (selective_channel.h:52)."""

    def __init__(self, load_balancer: str | LoadBalancer = "rr",
                 max_retry: int = 2):
        self._subs: List[Channel] = []
        self._lb = (load_balancer if isinstance(load_balancer, LoadBalancer)
                    else new_load_balancer(load_balancer))
        self.max_retry = max_retry

    def add_sub_channel(self, ch: Channel) -> None:
        self._subs.append(ch)
        # the LB keys sub-channels by synthetic endpoints (index as host)
        self._lb.reset_servers(
            tuple(EndPoint("sub", str(i), 0) for i in range(len(self._subs))))

    def call(self, service: str, method: str, request: Any = b"",
             cntl: Optional[Controller] = None,
             done: Optional[Callable] = None, **kw) -> Controller:
        cntl = cntl or Controller()
        cntl._done_cb = done
        tried: set = set()
        outer = self

        def attempt(tries_left: int):
            ep = outer._lb.select_server(tried or None)
            if ep is None:
                cntl.set_failed(berr.ETOOMANYFAILS, "no sub channel left")
                cntl._complete()
                return
            tried.add(ep)
            sub = outer._subs[int(ep.host)]

            def _cb(sub_cntl):
                outer._lb.feedback(ep, sub_cntl.latency_us(), sub_cntl.failed())
                if sub_cntl.failed() and tries_left > 0:
                    attempt(tries_left - 1)
                    return
                cntl.error_code = sub_cntl.error_code
                cntl.error_text = sub_cntl.error_text
                cntl.response_payload = sub_cntl.response_payload
                cntl.response_device_arrays = sub_cntl.response_device_arrays
                cntl.response_attachment = sub_cntl.response_attachment
                cntl._complete()

            sub.call(service, method, request, done=_cb, **kw)

        attempt(self.max_retry)
        return cntl

    def call_sync(self, service, method, request=b"", timeout_s: float = 30.0,
                  **kw) -> Controller:
        cntl = self.call(service, method, request, **kw)
        cntl.join(timeout_s)
        return cntl


class PartitionParser:
    """Splits a logical request into per-partition requests
    (partition_channel.h:46)."""

    def parse(self, partition_index: int, num_partitions: int, service: str,
              method: str, request: Any, cntl: Controller) -> SubCall:
        return SubCall(service, method, request)


class PartitionChannel(ParallelChannel):
    """Fan out one call to all partitions of a sharded service; partition
    i's servers come from sub-channel i (partition_channel.h:75)."""

    def __init__(self, partition_parser: Optional[PartitionParser] = None,
                 fail_limit: Optional[int] = 1,
                 response_merger: Optional[ResponseMerger] = None):
        parser = partition_parser or PartitionParser()
        outer_self = self

        class _Mapper(CallMapper):
            def map(self, i, nsub, service, method, request, cntl):
                return parser.parse(i, nsub, service, method, request, cntl)

        super().__init__(fail_limit=fail_limit, call_mapper=_Mapper(),
                         response_merger=response_merger)
        self.partition_parser = parser

    def add_partition(self, ch: Channel) -> None:
        self.add_sub_channel(ch)

    @property
    def partition_count(self) -> int:
        return self.sub_channel_count


class DynamicPartitionChannel(PartitionChannel):
    """PartitionChannel that re-shards as the naming service changes the
    partition map (partition_channel.h:136 DynamicPartitionChannel +
    the _dynpart LB). Server entries carry ``#partition=K/N``; on every
    update the sub-channel list is rebuilt to N partitions, each backed
    by a list:// cluster of that partition's replicas, and swapped in
    atomically (in-flight calls keep the generation they started with)."""

    def __init__(self, naming_url: str,
                 partition_parser: Optional[PartitionParser] = None,
                 fail_limit: Optional[int] = 1,
                 response_merger: Optional[ResponseMerger] = None,
                 options=None, control=None):
        super().__init__(partition_parser, fail_limit, response_merger)
        from brpc_tpu.rpc.naming import NamingServiceThread
        self._options = options
        self._control = control
        self._generation = 0
        self._ready = threading.Event()
        self._retired: List[list] = []
        self._retire_lock = threading.Lock()
        self._ns = NamingServiceThread(naming_url, control=control)
        self._ns.watch(self._rebuild)

    def wait_ready(self, timeout_s: float = 5.0) -> bool:
        return self._ready.wait(timeout_s)

    def _rebuild(self, servers) -> None:
        from brpc_tpu.rpc.cluster_channel import ClusterChannel
        by_partition: Dict[int, list] = {}
        nparts = 0
        for ep in servers:
            spec = ep.extra("partition")
            if not spec or "/" not in spec:
                continue
            k_s, n_s = spec.split("/", 1)
            try:
                k, n = int(k_s), int(n_s)
            except ValueError:
                continue
            if n <= 0 or not 0 <= k < n:
                continue
            nparts = max(nparts, n)
            by_partition.setdefault(k, []).append(
                EndPoint(ep.scheme, ep.host, ep.port))
        new_subs = []
        for k in range(nparts):
            eps = by_partition.get(k)
            if not eps:
                # a hole in the partition map: serve what we can; calls
                # hitting the missing shard fail via the empty cluster
                eps = []
            url = "list://" + ",".join(str(e) for e in eps)
            new_subs.append(ClusterChannel(url, "rr", self._options,
                                           control=self._control))
        old, self._subs = self._subs, new_subs   # atomic ref swap
        self._generation += 1
        self._ready.set()
        if old:
            # in-flight calls still hold the old generation: closing now
            # would fail their sub-calls mid-flight. Retire after a grace
            # period instead.
            from brpc_tpu.fiber.timer import global_timer
            with self._retire_lock:
                self._retired.append(old)
            global_timer().schedule_after(10.0, self._close_retired)

    def _close_retired(self) -> None:
        with self._retire_lock:
            gens, self._retired = self._retired[:1], self._retired[1:]
        for gen in gens:
            for ch in gen:
                try:
                    ch.close()
                except Exception:
                    pass

    def close(self) -> None:
        self._ns.stop()
        with self._retire_lock:
            gens, self._retired = self._retired, []
        for gen in gens + [self._subs]:
            for ch in gen:
                try:
                    ch.close()
                except Exception:
                    pass
