"""Combo channels (SURVEY.md §2.6):

  ParallelChannel  — one call fans out to N sub-channels, responses merge
                     (parallel_channel.h: CallMapper :94, ResponseMerger
                     :127, fail_limit :168).
  SelectiveChannel — LB over heterogeneous sub-channels with
                     retry-elsewhere (selective_channel.h:52).
  PartitionChannel — shard fan-out by partition index; each partition is
                     its own server group (partition_channel.h:46-136).

These are host-side fan-outs over arbitrary transports. When every
sub-target is a device on one mesh, ``ParallelChannel.attach_collective``
lowers a call whose mapper and merger say what they do
(``RowScatterMapper`` with ``SumMerger`` or with the collecting default)
onto ONE XLA collective program (parallel/collective.py) instead of N
point-to-point calls.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc import span as _span
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


@functools.lru_cache(maxsize=None)
def _row_block(sub_index: int, nsub: int) -> Callable:
    """The jitted slice of block ``sub_index`` of ``nsub`` of a leading
    dimension: one program a shape, as a caller's own slicing would be."""
    import jax

    def row_block(big):
        rows = big.shape[0] // nsub
        return jax.lax.slice_in_dim(big, sub_index * rows,
                                    (sub_index + 1) * rows, axis=0)
    return jax.jit(row_block)


@functools.lru_cache(maxsize=None)
def _sum_parts() -> Callable:
    import jax

    def sum_parts(*parts):
        return functools.reduce(lambda a, b: a + b, parts)
    return jax.jit(sum_parts)


class RowScatterMapper(CallMapper):
    """Block i of the leading dimension of the call's ONE request array
    to sub i (the rows must divide by the sub count); the host bytes go
    to every sub as they are. With ``SumMerger`` the fan-out is an
    allreduce, and the pair is what ``attach_collective`` can lower."""

    def map(self, sub_index: int, nsub: int, service: str, method: str,
            request: Any, cntl: Controller) -> SubCall:
        arrs = cntl.request_device_arrays
        if not arrs or len(arrs) != 1:
            raise ValueError("RowScatterMapper maps a call with one "
                             f"request device array, not {len(arrs or ())}")
        big = arrs[0]
        if not big.shape or big.shape[0] % nsub:
            raise ValueError(f"a request of shape {big.shape} does not "
                             f"scatter over {nsub} sub channels: its "
                             "leading dimension must divide")
        return SubCall(service, method, request,
                       device_arrays=[_row_block(sub_index, nsub)(big)])


class SumMerger(ResponseMerger):
    """Adds the sub replies' arrays (one a reply) where the first sub's
    reply landed, the caller's reply device, in the order of the subs,
    once the last one is in: the sum is ``cntl.response_device_arrays``.
    The replies' bytes stay in ``sub_responses``, as the default keeps
    them; the replies' arrays are given up for the sum
    (``sub_device_arrays`` stays unfilled). A call that lost a sub has
    no sum."""

    def __init__(self):
        self._lock = threading.Lock()

    def merge(self, final_cntl: Controller, sub_index: int,
              sub_cntl: Controller) -> None:
        parts = sub_cntl.response_device_arrays
        if not parts or len(parts) != 1:
            raise ValueError(f"sub {sub_index} answered {len(parts or ())} "
                             "device arrays; SumMerger adds one a sub")
        final_cntl.sub_responses[sub_index] = (
            sub_cntl.response_payload.to_bytes()
            if sub_cntl.response_payload is not None else None)
        with self._lock:
            # kept and looked at in one step: only the merge that brings
            # the last reply sees them all
            held = final_cntl.__dict__.setdefault(
                "_sum_parts", [None] * len(final_cntl.sub_responses))
            held[sub_index] = parts[0]
            if any(p is None for p in held):
                return
            del final_cntl.__dict__["_sum_parts"]
        import jax
        (device,) = held[0].devices()
        held = [p if p.devices() == {device} else jax.device_put(p, device)
                for p in held]
        final_cntl.response_device_arrays = [_sum_parts()(*held)]


# the lane of the /device cell a lowered call is a transfer of
COLLECTIVE_LANE = "collective"

# process-wide, on /vars (docs/observability.md): calls lowered to one
# collective, lowerings that were tried and raised, request bytes lowered
_fused_var = Adder(0)
_fallbacks_var = Adder(0)
_bytes_var = Adder(0)


def expose_collective_vars() -> None:
    """(Re-)expose the three: from ``attach_collective``, so a process
    that lowers nothing lists none of them."""
    _fused_var.expose("parallel_collective_fused")
    _fallbacks_var.expose("parallel_collective_fallbacks")
    _bytes_var.expose("parallel_collective_bytes")


def collective_counters() -> dict:
    """The three process-wide sums since start."""
    return {"fused": _fused_var.get_value(),
            "fallbacks": _fallbacks_var.get_value(),
            "bytes": _bytes_var.get_value()}


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
        self._reply_devices: List[Any] = []
        self._collective_peer = ""
        self.collective_fused = 0
        self.collective_fallbacks = 0

    def attach_collective(self, collective,
                          service_fns: Dict[Any, Callable]) -> None:
        """Arm collective lowering: ``collective`` is a
        parallel.collective.CollectiveChannel over the mesh whose
        devices back the sub-channels (shard i of the mesh is sub i's
        server); ``service_fns`` maps ``(service, method)`` to the
        jax-traceable per-shard function equivalent to what that RPC
        method computes on its request array. A call to a mapped method
        with ONE request device array then runs as ONE XLA program
        (``jit_collective_<service>_<method>``) instead of N lane RPCs,
        where the channel's mapper and merger are a pair the program
        can express, and the merge is taken from the merger:

          RowScatterMapper + SumMerger       -> scatter, fn, ``psum``;
              the sum is one array on the channel's reply device (sub
              0's ``#reply_device``) in ``cntl.response_device_arrays``
          RowScatterMapper + ResponseMerger  -> scatter, fn, the blocks
              unmerged (``concat``): block i is one array on sub i's
              reply device in ``cntl.sub_device_arrays[i]``

        which is where, and on which device, the fan-out of the same
        pair leaves them, and ``response_device_arrays`` is filled by
        the sum alone. The one field a lowered call cannot fill is
        ``sub_responses``: the request's host bytes reach no handler,
        so no reply bytes exist and every entry stays None.

        Exactly these classes: a subclass, the broadcasting
        ``CallMapper`` and any other pair fan out. So do a call with
        host bytes only or several arrays, rows that do not divide by
        the sub count, an unmapped method, a sub off the device lane, a
        sub count other than the mesh's shards: none of them counts as
        anything. A lowering that was tried and raised is logged once,
        counted (``collective_fallbacks`` here, ``parallel_collective_
        fallbacks`` on /vars, one failed transfer in the ``collective``
        cell of /device) and the call fans out. The call completes
        when the program is dispatched; its arrays are ready later, as
        any reply's are. While spans record, a lowered call leaves one
        client span (docs/observability.md)."""
        self._collective = collective
        self._collective_fns = dict(service_fns)
        self._lane_verdict = None
        expose_collective_vars()

    def _all_device_lane(self) -> bool:
        """One probe per sub-channel generation: every sub must expose
        a device lane for the fused program to be equivalent (a plain
        TCP sub would silently drop out of a collective). The subs'
        reply devices are learned with it."""
        if self._lane_verdict is None:
            try:
                self._lane_verdict = bool(self._subs) and all(
                    sub.device_lane_kind() is not None
                    for sub in self._subs)
                if self._lane_verdict:
                    self._reply_devices = [sub.reply_device()
                                           for sub in self._subs]
                    mesh = self._collective.mesh
                    self._collective_peer = "mesh://" + "x".join(
                        f"{name}{size}" for name, size in mesh.shape.items())
            except Exception:
                self._lane_verdict = False
        return self._lane_verdict

    def _lowered_merge(self) -> Optional[str]:
        """The collective that this channel's mapper and merger add up
        to, or None. By class and not by isinstance: a subclass may do
        anything."""
        if type(self.call_mapper) is not RowScatterMapper:
            return None
        merger = type(self.response_merger)
        if merger is SumMerger:
            return "sum"
        if merger is ResponseMerger:
            return "concat"
        return None

    def _maybe_collective(self, service: str, method: str,
                          cntl: Controller) -> bool:
        """Try the fused path; True means the call completed there. A
        call that does not qualify, or whose lowering raises, takes the
        per-sub fan-out, which gives the same answer."""
        coll = self._collective
        if coll is None:
            return False
        fn = self._collective_fns.get((service, method))
        if fn is None:
            return False
        arrs = cntl.request_device_arrays
        if not arrs or len(arrs) != 1:
            return False
        merge = self._lowered_merge()
        if merge is None:
            return False
        nsub = len(self._subs)
        if nsub != coll.n_shards or not self._all_device_lane():
            return False
        request = arrs[0]
        if not request.shape or request.shape[0] % nsub:
            return False        # the mapper refuses it, with the reason
        from brpc_tpu.parallel.collective import ready_waiter
        from brpc_tpu.transport import device_stats

        span = None
        if _span.recording():
            span = _span.start_client_span(cntl, service, method)
            span.remote_side = self._collective_peer
            span.request_size = request.nbytes
        tracker = device_stats.open_transfer(
            self._collective_peer, COLLECTIVE_LANE, request.nbytes)

        def on_ready(err) -> None:
            """The waiter's thread: the result is on the reply device
            (or the wait for it raised ``err``)."""
            if tracker is not None:
                if err is None:
                    tracker.lane_acked()
                else:
                    tracker.lane_failed(f"result not ready: {err}")
            if span is not None:
                # the lowered call's first byte, and the span's end
                span.first_byte_us = time.monotonic_ns() // 1000
                if err is not None:
                    span.annotate(f"result not ready: {err}"[:200])
                _span.finish_span(span, cntl)

        try:
            placed, src = coll.scatter(request)
            if tracker is not None:
                tracker.lane_encoded()
            if span is not None:
                span.write_done_us = time.monotonic_ns() // 1000
                # which program the call runs: the scatter's form
                span.annotate(f"collective lowered: {coll.scatter_form(src)}"
                              f" + {merge} over {nsub} shards, no sub call")
            out = coll.run(fn, placed, src, merge,
                           name=f"collective_{service}_{method}")
            if tracker is not None:
                tracker.lane_flushed()
            if span is not None:
                span.dispatch_us = time.monotonic_ns() // 1000
            # the arrays where the merger's fan-out leaves them: the sum
            # as the one response array on sub 0's reply device, the
            # collected blocks one a sub on that sub's
            if merge == "sum":
                out = [coll.replica_on(out, self._reply_devices[0])]
                cntl.response_device_arrays = out
            else:
                out = coll.blocks_on(out, self._reply_devices)
                cntl.sub_device_arrays = [[block] for block in out]
        except Exception as e:
            if not self.collective_fallbacks:
                # once per channel: the fan-out below keeps the call's
                # semantics, but a lowering that never works must not
                # look like one that does
                logger.exception(
                    "collective lowering of %s.%s failed; falling back "
                    "to the per-sub fan-out (collective_fallbacks "
                    "counts the rest)", service, method)
            self.collective_fallbacks += 1
            _fallbacks_var.add(1)
            if tracker is not None:
                tracker.lane_failed(f"lowering raised: {e}")
            if span is not None:
                # the call has not failed: it fans out from here
                span.annotate(f"lowering raised, fanned out: {e}"[:200])
                _span.finish_span(span, cntl)
            return False
        self.collective_fused += 1
        _fused_var.add(1)
        _bytes_var.add(request.nbytes)
        if tracker is not None or span is not None:
            ready_waiter().watch(out, on_ready)
        cntl.collective_lowered = True
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
        try:
            for i, sub in enumerate(subs):
                sc = self.call_mapper.map(i, nsub, service, method, request,
                                          cntl)
                if sc is None or sc.skip:
                    continue
                sub_calls.append((i, sub, sc))
        except Exception as e:
            # upstream's SubCall::Bad(): a request the mapper cannot map
            # fails the call, before any sub call is issued
            cntl.set_failed(berr.EREQUEST, f"call mapper failed: {e}")
            cntl._complete()
            return cntl
        if not sub_calls:
            cntl.set_failed(berr.EREQUEST, "call mapper skipped every sub call")
            cntl._complete()
            return cntl
        state["pending"] = len(sub_calls)

        def on_sub_done(i):
            def _cb(sub_cntl):
                # merge() first, THEN count the sub call as done: the
                # thread that counts the last one finds every merge
                # finished, so a call never completes ahead of a merge
                merge_error = None
                if not sub_cntl.failed():
                    with lock:
                        late = state["done"]
                    if late:
                        return
                    try:
                        self.response_merger.merge(cntl, i, sub_cntl)
                    except Exception as e:
                        merge_error = (berr.ERESPONSE, f"merger failed: {e}")
                with lock:
                    if state["done"]:
                        return
                    if sub_cntl.failed():
                        state["failed"] += 1
                        cntl.sub_errors[i] = (sub_cntl.error_code,
                                              sub_cntl.error_text)
                    elif merge_error is not None:
                        state["failed"] += 1
                        cntl.sub_errors[i] = merge_error
                    state["pending"] -= 1
                    finish = (state["failed"] >= fail_limit
                              or state["pending"] == 0)
                    if finish:
                        state["done"] = True
                        failed = state["failed"]
                if finish:
                    if failed >= fail_limit:
                        cntl.set_failed(
                            berr.ETOOMANYFAILS,
                            f"{failed}/{len(sub_calls)} sub calls failed")
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
