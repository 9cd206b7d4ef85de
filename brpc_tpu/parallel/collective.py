"""Collective lowering: the combo-channel shapes compiled onto the mesh.

SURVEY.md §2.8's table, realized. When a ParallelChannel's sub-targets are
the devices of one mesh, N point-to-point RPCs + a host merge is the wrong
program for a TPU pod — the same dataflow is ONE SPMD computation whose
fan-out/merge are XLA collectives riding ICI:

  ParallelChannel fan-out + merge  -> scatter_gather(): shard_map of the
      service fn over the 'shard' axis, merge lowered to psum/all_gather
  Sharded addressing (Partition)   -> the in_spec partitioning itself
  Replica selection (Selective)    -> 'replica' axis; replicated in_spec
  Fan-in reduce (allreduce bench)  -> all_reduce()

Everything here is jit-compiled once per shape and reused — the RPC-side
analogue of the reference registering protocols once at GlobalInitialize.

A request that lives whole on ONE device of the mesh (what a caller has
after ``jax.device_put(x, dev)``, and what every array off the device
lane is) is scattered INSIDE the lowered program: the caller's buffer
is the program's argument on its own device, the other devices get a
resident stand-in of the same shape, and ONE ``all-to-all`` carries
block j of every buffer to shard j, ahead of the service function and
the merge: the source's blocks are one operation's transfers, the
stand-ins' zeros travel beside them and are never read. It is a move
and nothing else (no slice copied out first, no select after), so every
bit pattern arrives as it was written. Nothing is copied outside the
program and the whole call is one launch. A request anywhere else (off
the mesh, spread over several devices, or a mesh with more than one
replica) is handed to ``NamedSharding(mesh, P('shard'))`` by
``jax.device_put`` first.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from brpc_tpu.butil import postfork, thread_cpu
from brpc_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS, shard_map


_MERGES = ("sum", "mean", "max", "min", "concat", "stack", "none")


class CollectiveChannel:
    """The ParallelChannel of a device mesh.

    call(service_fn, request): request is sharded over the 'shard' axis,
    service_fn runs per shard, responses merge on-device. service_fn must
    be a jax-traceable function shard -> shard_response.
    """

    def __init__(self, mesh: Mesh, merge: str = "concat"):
        if merge not in _MERGES:
            raise ValueError(f"merge must be one of {_MERGES}")
        self.mesh = mesh
        self.merge = merge
        self._compiled: Dict[Any, Callable] = {}
        # the stand-ins of an in-program scatter, by (shape, dtype, src)
        self._stand_ins: Dict[Any, list] = {}
        # a device's place in the mesh, which is where a result's piece
        # on it sits among the result's pieces
        self._device_index = {d: i for i, d in enumerate(mesh.devices.flat)}

    # ------------------------------------------------------------ lowering
    def _lower(self, service_fn: Callable, merge: str, name: str,
               src: Optional[int]) -> Callable:
        mesh = self.mesh
        n = self.n_shards

        def take_block(x):
            """This shard's block of a request that lives on shard
            ``src``: ONE all-to-all in which block j of every shard's
            buffer goes to shard j; what arrives from ``src`` is this
            shard's, and the stand-ins' zeros beside it are never
            read."""
            rows = x.shape[0] // n
            got = jax.lax.all_to_all(x, SHARD_AXIS, split_axis=0,
                                     concat_axis=0, tiled=True)
            return jax.lax.slice_in_dim(got, src * rows, (src + 1) * rows)

        def per_shard(x):
            y = service_fn(x if src is None else take_block(x))
            if merge == "sum":
                return jax.lax.psum(y, SHARD_AXIS)
            if merge == "mean":
                return jax.lax.pmean(y, SHARD_AXIS)
            if merge == "max":
                return jax.lax.pmax(y, SHARD_AXIS)
            if merge == "min":
                return jax.lax.pmin(y, SHARD_AXIS)
            return y  # concat/stack/none: stitching via out_specs

        if merge in ("sum", "mean", "max", "min"):
            out_spec = P()              # merged result replicated
        elif merge == "none":
            out_spec = P(SHARD_AXIS)    # leave sharded (response stays put)
        else:                           # concat / stack
            out_spec = P(SHARD_AXIS)
        fn = shard_map(per_shard, mesh=mesh, in_specs=P(SHARD_AXIS),
                       out_specs=out_spec)
        # the compiled module is named after it (``jit_<name>``): a
        # trace tells one lowered method from another
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)

    def _source_index(self, request) -> Optional[int]:
        """Where on the shard axis the whole request lives, or None: not
        on one device of this mesh, or a mesh the in-program scatter
        does not cover (more than one replica)."""
        if self.mesh.shape[REPLICA_AXIS] != 1:
            return None
        devices = getattr(request, "devices", None)
        held = devices() if devices is not None else ()
        if len(held) != 1:
            return None
        # one replica: a device's place in the mesh is its shard
        return self._device_index.get(next(iter(held)))

    def scatter(self, request) -> Tuple[Any, Optional[int]]:
        """Hand ``request`` to the mesh: ``(placed, src)`` for ``run``.
        ``src`` is the shard that holds the request whole (``placed``
        then is the request with its stand-ins, no copy made: the blocks
        travel inside the program), or None where ``placed`` is the
        request put to ``NamedSharding(mesh, P('shard'))``. The leading
        dimension must divide by the shard count."""
        n = self.n_shards
        if not request.shape or request.shape[0] % n:
            raise ValueError(
                f"a request of shape {request.shape} does not scatter "
                f"over {n} shards: its leading dimension must divide")
        src = self._source_index(request)
        if src is None:
            return jax.device_put(
                request, NamedSharding(self.mesh, P(SHARD_AXIS))), None
        key = (request.shape, request.dtype, src)
        rest = self._stand_ins.get(key)
        if rest is None:
            rest = self._stand_ins[key] = [
                jax.device_put(jnp.zeros(request.shape, request.dtype), d)
                for i, d in enumerate(self.mesh.devices[0]) if i != src]
        parts = rest[:src] + [request] + rest[src:]
        placed = jax.make_array_from_single_device_arrays(
            (n * request.shape[0],) + request.shape[1:],
            NamedSharding(self.mesh, P(SHARD_AXIS)), parts)
        return placed, src

    @staticmethod
    def scatter_form(src: Optional[int]) -> str:
        """What ``scatter`` and the program do with a request, in a few
        words for a span's annotation: ``src`` as ``scatter`` gave it."""
        return ("scatter by device_put" if src is None
                else "all-to-all scatter in the program")

    def run(self, service_fn: Callable, placed, src: Optional[int] = None,
            merge: Optional[str] = None, name: str = "per_shard"):
        """The lowered program on what ``scatter`` returned; compiled
        once a (function, merge, name, source) and shape."""
        merge = merge or self.merge
        key = (id(service_fn), merge, name, src)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._lower(service_fn, merge, name, src)
            self._compiled[key] = fn
        return fn(placed)

    def call(self, service_fn: Callable, request, merge: Optional[str] = None,
             name: str = "per_shard"):
        """One fan-out/merge over the shard axis. ``request``'s leading dim
        is scattered across shards (it must divide by shard count)."""
        placed, src = self.scatter(request)
        return self.run(service_fn, placed, src, merge, name)

    def replica_on(self, out, device):
        """A merged (replicated) result as an array on ``device`` alone:
        the replica the collective left there, or a copy of one where
        ``device`` is not of the mesh."""
        for shard in out.addressable_shards:
            if shard.device == device:
                return shard.data
        return jax.device_put(out.addressable_shards[0].data, device)

    def blocks_on(self, out, devices) -> list:
        """The blocks of an unmerged (``concat``) result in the order of
        the shards, block i as an array on ``devices[i]`` alone: the
        block the program left there as it is, a copy of any other."""
        shards = sorted(out.addressable_shards,
                        key=lambda shard: shard.index[0].start or 0)
        return [shard.data if shard.device == device
                else jax.device_put(shard.data, device)
                for shard, device in zip(shards, devices)]

    # ------------------------------------------------- common collectives
    def all_reduce(self, x, op: str = "sum"):
        return self.call(_identity, x, merge=op)

    def all_gather(self, x):
        """Every shard sees the full request (fan-out broadcast side)."""
        fn = jax.jit(shard_map(
            lambda s: jax.lax.all_gather(s, SHARD_AXIS, tiled=True),
            mesh=self.mesh, in_specs=P(SHARD_AXIS), out_specs=P(),
            check_vma=False))  # replication holds post-all_gather; not inferable
        return fn(x)

    def reduce_scatter(self, x):
        fn = jax.jit(shard_map(
            lambda s: jax.lax.psum_scatter(s, SHARD_AXIS, tiled=True),
            mesh=self.mesh, in_specs=P(None), out_specs=P(SHARD_AXIS)))
        return fn(x)

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[SHARD_AXIS]


def _identity(s):
    return s


class ReadyWaiter:
    """Learns when lowered results are ready without a thread a call:
    one long-lived thread blocks on the outputs in the order they were
    handed over (a mesh finishes its programs in the order it was given
    them, so the one in front is the next to finish) and calls each
    one's callback with the error, or None."""

    def __init__(self):
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def watch(self, out, on_ready: Callable[[Optional[BaseException]],
                                            None]) -> None:
        if self._thread is None:
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="collective_wait",
                        daemon=True)
                    self._thread.start()
        self._queue.put((out, on_ready))

    def _run(self) -> None:
        from brpc_tpu.transport.device_stats import stamp_device_thread

        thread_cpu.set_role("device_wait")
        stamp_device_thread("device:collective")
        while True:
            out, on_ready = self._queue.get()
            err = None
            try:
                jax.block_until_ready(out)  # parks in PjRt, GIL released
            except Exception as e:  # noqa: BLE001 - the callback's to tell
                err = e
            try:
                on_ready(err)
            except Exception:  # noqa: BLE001 - one call's, not the thread's
                import logging
                logging.getLogger("brpc_tpu.parallel").exception(
                    "collective ready callback failed")


_waiter = ReadyWaiter()


def ready_waiter() -> ReadyWaiter:
    return _waiter


def _postfork_reset() -> None:
    """The waiter's thread is the parent's; what it held (device arrays)
    is abandoned, so the child runs no runtime destructor."""
    global _waiter
    postfork.abandon(_waiter)
    _waiter = ReadyWaiter()


postfork.register("parallel.collective_waiter", _postfork_reset)


def all_to_all_reshard(mesh: Mesh, x, concat_axis: int, split_axis: int):
    """Ulysses-style resharding: move the sharded dimension of ``x`` from
    ``split_axis`` to ``concat_axis`` with one all-to-all over 'shard' —
    e.g. [seq/N, heads] -> [seq, heads/N] for long-sequence attention.
    The all-to-all is the sequence-parallel workhorse (SURVEY.md §5
    long-context analog)."""

    def per_shard(s):
        return jax.lax.all_to_all(s, SHARD_AXIS, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    in_spec = [None] * x.ndim
    in_spec[concat_axis] = SHARD_AXIS
    out_spec = [None] * x.ndim
    out_spec[split_axis] = SHARD_AXIS
    fn = shard_map(per_shard, mesh=mesh, in_specs=P(*in_spec),
                       out_specs=P(*out_spec))
    return jax.jit(fn)(x)


def replicated_call(mesh: Mesh, service_fn: Callable, request):
    """SelectiveChannel's degenerate mesh form: every replica holds the
    full request; the caller reads any replica's response (they're
    identical — replica choice becomes a scheduling detail, not a data
    movement)."""
    fn = shard_map(service_fn, mesh=mesh, in_specs=P(), out_specs=P())
    return jax.jit(fn)(request)
