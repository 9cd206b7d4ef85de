"""InputMessenger: cuts complete messages out of a socket's byte stream by
trying registered protocols' Parse functions (brpc/input_messenger.{h,cpp}).

Keeps the reference's two hot-path tricks: the per-socket preferred
protocol index (first successful parser is remembered,
input_messenger.cpp:219), and in-place processing of the *last* message
while earlier ones get fresh fibers (QueueMessage, :183 — so a pipelined
burst parallelizes but the common single-message case pays no extra
handoff).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Callable, List, Optional

from brpc_tpu.butil.flags import define_flag, flag
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.bvar.reducer import Adder, Maxer, PassiveStatus
from brpc_tpu.fiber import TaskControl, global_control
from brpc_tpu.protocol.registry import PARSE_OK, PARSE_NOT_ENOUGH_DATA, PARSE_TRY_OTHERS, get_protocols
from brpc_tpu.transport import event_dispatcher as _event_dispatcher
from brpc_tpu.transport import syscall_stats as _syscall_stats
from brpc_tpu.transport.socket import Socket

# Run-to-completion budget for a pipelined burst: up to this many
# messages of one dispatcher wakeup process IN the dispatch context
# (each still escalates to a fiber the moment it suspends — only the
# sync leg runs inline), anything past it spills to fibers with ONE
# amortized parking-lot signal (TaskControl.spawn_many). The budget
# bounds how long a burst of sync handlers can hold the event thread.
define_flag("dispatch_inline_budget", 16,
            "messages of one input burst processed in the dispatch "
            "context before the rest spill to fibers (single batch "
            "wake); suspending handlers escalate immediately")

# dispatch batch size: messages the Python dispatch loop settled per
# dispatcher wakeup cycle (native echo-serve batches are accounted
# separately via rpc server native batch counters). Windowed avg/peak
# on /vars + prometheus + the /status saturation pane.
_batch_msgs = Adder().expose("dispatch_batch_msgs")
_batch_cycles = Adder().expose("dispatch_batches")
_batch_peak = Maxer()
_batch_windows = None


def _batch_window_views():
    """(msgs_per_s, cycles_per_s, peak_window), created on first scrape
    (a Window registers with the background sampler thread)."""
    global _batch_windows
    if _batch_windows is None:
        from brpc_tpu.bvar.window import PerSecond, Window
        _batch_windows = (PerSecond(_batch_msgs, 10),
                          PerSecond(_batch_cycles, 10),
                          Window(_batch_peak, 10))
    return _batch_windows


def dispatch_batch_avg_10s() -> float:
    """Windowed mean messages per dispatch cycle (1.0 = no batching)."""
    msgs, cycles, _ = _batch_window_views()
    c = cycles.get_value() or 0
    if not c:
        return 0.0
    return round((msgs.get_value() or 0) / c, 2)


def dispatch_batch_peak_10s() -> int:
    _, _, peak = _batch_window_views()
    return peak.get_value() or 0


def _postfork_reset() -> None:
    """Fork hygiene: the window views are registered with the parent's
    sampler series; recreate them against the child's sampler."""
    global _batch_windows
    _batch_windows = None


from brpc_tpu.butil import postfork as _postfork  # noqa: E402
#   (registration ships with the singleton it resets)

_postfork.register("transport.input_messenger", _postfork_reset)


PassiveStatus(dispatch_batch_avg_10s).expose("dispatch_batch_size_avg_10s")
PassiveStatus(dispatch_batch_peak_10s).expose("dispatch_batch_size_peak_10s")


def record_dispatch_batch(n: int) -> None:
    _batch_msgs.add(n)
    _batch_cycles.add(1)
    _batch_peak.update(n)
    # syscalls_per_rpc denominator (transport/syscall_stats.py): every
    # message this authority dispatches — requests AND responses, so a
    # loopback process counts both sides of each call
    _syscall_stats.note_rpc_messages(n)


async def _counted_dispatch(socket, work):
    """Run a queued message's processing with the socket's
    pending_responses claimed for its WHOLE lifetime — a spawned
    request that hasn't started yet must already be visible to the
    cut-through gate, or its response could interleave mid-stream."""
    try:
        r = work() if callable(work) else work
        if hasattr(r, "__await__"):
            await r
    finally:
        with socket.pending_lock:
            if socket.pending_responses > 0:
                socket.pending_responses -= 1


def counted_spawn(control, socket, work, name: str) -> None:
    """Spawn queued-message processing under a pending_responses claim
    (claimed HERE, at queue time, not at coroutine start). ``work`` is
    a zero-arg callable or an awaitable. Sockets that can never enter
    cut-through (no native-echo server) skip the claim entirely."""
    from brpc_tpu.rpc.server_dispatch import _track_pending
    if not _track_pending(socket):
        control.spawn(work, name=name)   # spawn runs callables/awaitables
        return
    with socket.pending_lock:
        socket.pending_responses += 1
    control.spawn(_counted_dispatch(socket, work), name=name)


def counted_spawn_many(control, socket, works, name: str) -> None:
    """Batch twin of counted_spawn: every work's claim lands before any
    fiber can start, and the whole spill pays ONE parking-lot signal
    (TaskControl.spawn_many)."""
    from brpc_tpu.rpc.server_dispatch import _track_pending
    if not _track_pending(socket):
        control.spawn_many(works, name=name)
        return
    with socket.pending_lock:
        socket.pending_responses += len(works)
    control.spawn_many([_counted_dispatch(socket, w) for w in works],
                       name=name)


def counted_run_inline(control, socket, work, name: str) -> None:
    """Process one queued message IN the dispatch context under its
    pending claim (run-to-completion: the sync leg runs right here
    with zero wakes; the first real suspension parks the remainder as
    a normal fiber). The budgeted middle of a pipelined burst."""
    from brpc_tpu.rpc.server_dispatch import _track_pending
    if not _track_pending(socket):
        control.run_inline(_drive(work), name=name)
        return
    with socket.pending_lock:
        socket.pending_responses += 1
    control.run_inline(_counted_dispatch(socket, work), name=name)


async def _drive(work):
    r = work() if callable(work) else work
    if hasattr(r, "__await__"):
        await r


class InputMessenger:
    def __init__(self, protocols: Optional[List] = None,
                 control: Optional[TaskControl] = None):
        self._protocols = protocols  # None = global registry snapshot per call
        self._control = control or global_control()

    def protocols(self) -> List:
        return self._protocols if self._protocols is not None else get_protocols()

    async def on_new_messages(self, socket: Socket):
        """The socket's input callback: parse-loop the portal, dispatch."""
        self.on_new_messages_sync(socket)

    def on_new_messages_sync(self, socket: Socket) -> None:
        """Sync twin of on_new_messages: parses and dispatches entirely
        on the calling context. The LAST message is processed in place
        (``_process_last``): its processing runs right here until it
        first suspends, and from there it is a fiber of its own, so the
        connection's input goes on meanwhile, as on the turbo lane (a
        request whose sync handler holds a worker must not keep the
        requests behind it on this connection unread:
        input_messenger.cpp runs its last message after it has given
        the socket's read events up). A fully-sync cycle (the client
        response path, pure stream frames) touches no coroutine or
        fiber machinery at all.

        On the event loop's thread while spans record (``loop`` below)
        the pass, which the socket entered cutting, marks where it
        turns to processing (and back, where it cuts on) for the loop's
        sums of its awake time; a lap into the phase that runs reads no
        clock. A stream frame is processed inside ``process_inline``,
        which marks it itself."""
        loop = _event_dispatcher.stamping
        protocols = self.protocols()
        # mid-frame short-circuit: the previous cycle's parse told us
        # how many bytes the frame needs — until they're here, nothing
        # below can make progress (input_messenger.cpp keeps the same
        # cut-size memo between reads)
        need = socket.input_need
        if need:
            if socket.input_portal.size < need:
                return None
            socket.input_need = 0
        idx = socket.preferred_protocol
        if 0 <= idx < len(protocols):
            proto = protocols[idx]
            # turbo lane: one native call cuts + meta-decodes the whole
            # pending burst of small tpu_std frames, and the records
            # dispatch through the slim fast paths (the native per-call
            # loop; scan_frames in fastcore.cc)
            ts = getattr(proto, "turbo_scan", None)
            if ts is not None:
                portal = socket.input_portal
                # a large-frame echo in flight: forward the newly
                # arrived body bytes first (cut-through serving)
                cut = socket.user_data.get("_cut_forward")
                if cut is not None:
                    if not proto.cut_forward(portal, socket, cut):
                        return None          # mid-frame: await more bytes
                # scan the WHOLE portal before dispatching (the classic
                # loop's discipline — dispatch decisions like "earlier
                # messages get fresh fibers" need the full burst view);
                # the loop matters on chunk-handoff transports (mem://)
                # where each frame sits in its own block and one scan
                # only sees the head block
                all_recs = None
                nserve = getattr(proto, "native_serve", None)
                ncut = getattr(proto, "try_cut_through", None)
                mid_frame = False
                while True:
                    # echo-class front runs serve entirely in C (one
                    # scan+pack call, one write)
                    if nserve is not None and nserve(portal, socket):
                        if not portal:
                            break
                        continue
                    # large echo frames stream through without assembly
                    # — only when no undispatched requests sit ahead
                    # (their responses must leave first)
                    if ncut is not None and all_recs is None and \
                            ncut(portal, socket):
                        if socket.user_data.get("_cut_forward") is not None:
                            mid_frame = True
                            break
                        continue
                    recs = ts(portal, socket)
                    if not recs:
                        break
                    if all_recs is None:
                        all_recs = recs
                    else:
                        all_recs.extend(recs)
                    if not portal:
                        break    # fully consumed: skip the empty rescan
                if mid_frame:
                    return None
                if all_recs:
                    record_dispatch_batch(len(all_recs))
                    if loop is not None:
                        loop.lap(_event_dispatcher.PROCESS)
                    tail = proto.turbo_dispatch(all_recs, socket)
                    if not socket.input_portal:
                        if tail is not None:
                            self._process_last(socket, proto, tail)
                        return None
                    if tail is not None:
                        # leftover (slow) bytes still need the classic
                        # loop below; the fallback tail becomes a fiber
                        counted_spawn(self._control, socket, tail,
                                      "process_tpu_std")
                    if loop is not None:
                        loop.lap(_event_dispatcher.CUT)
        # single-message fast path: a connection already claimed by a
        # protocol, one complete frame waiting (the overwhelmingly common
        # non-pipelined case) — parse and process directly, skipping the
        # candidate-ordering machinery below (the reference's
        # preferred_index + process-in-place discipline,
        # input_messenger.cpp:219,183)
        if 0 <= idx < len(protocols):
            proto = protocols[idx]
            status, msg = proto.parse(socket.input_portal, socket)
            if status == PARSE_OK and not socket.input_portal:
                record_dispatch_batch(1)
                if not proto.process_inline(msg, socket):
                    if loop is not None:
                        loop.lap(_event_dispatcher.PROCESS)
                    self._process_last(socket, proto,
                                       proto.process(msg, socket))
                return None
            if status == PARSE_NOT_ENOUGH_DATA:
                return None
            if status == PARSE_OK:
                # more bytes follow: hand the parsed message to the
                # general loop's dispatch rules (pipelined burst)
                msgs = [] if proto.process_inline(msg, socket) \
                    else [(proto, msg)]
            else:
                msgs = []
        else:
            msgs = []
        while socket.input_portal:
            if loop is not None:
                loop.lap(_event_dispatcher.CUT)     # after a stream frame
            idx = socket.preferred_protocol
            if 0 <= idx < len(protocols):
                # burst fast path: a protocol already claimed this
                # connection and can batch-cut a pipelined window in one
                # native scan (tpu_std.batch_parse)
                bp = getattr(protocols[idx], "batch_parse", None)
                if bp is not None:
                    batch = bp(socket.input_portal, socket)
                    if batch:
                        proto = protocols[idx]
                        for msg in batch:
                            if not proto.process_inline(msg, socket):
                                msgs.append((proto, msg))
                        continue
            order = range(len(protocols)) if idx < 0 else (
                [idx] + [i for i in range(len(protocols)) if i != idx])
            claimed = None
            waiting_for_bytes = False
            ambiguous = False
            for i in order:
                proto = protocols[i]
                # parse contract: peek-only unless returning PARSE_OK
                status, msg = proto.parse(socket.input_portal, socket)
                if status == PARSE_OK:
                    socket.preferred_protocol = i
                    claimed = (proto, msg)
                    break
                if status == PARSE_NOT_ENOUGH_DATA:
                    # these bytes are this protocol's, just incomplete:
                    # stop and wait for more input
                    waiting_for_bytes = True
                    break
                # PARSE_TRY_OTHERS: not this protocol's bytes — but a
                # disclaim on a prefix shorter than the protocol's
                # discriminator is only tentative (segmented frame)
                if socket.input_portal.size < proto.min_probe_bytes:
                    ambiguous = True
            if claimed is not None:
                proto, msg = claimed
                # order-critical messages (stream frames) dispatch inline
                # in parse order; everything else may fan out to fibers
                if not proto.process_inline(msg, socket):
                    msgs.append(claimed)
                continue
            if not waiting_for_bytes and not ambiguous and \
                    socket.input_portal:
                # every protocol definitively disclaimed: drop the
                # connection (ambiguous short prefixes wait for more bytes)
                socket.set_failed(ValueError("unparsable input"))
            break
        if not msgs:
            return None
        record_dispatch_batch(len(msgs))
        if loop is not None:
            loop.lap(_event_dispatcher.PROCESS)
        if len(msgs) > 1:
            # bounded run-to-completion for the burst: RESPONSE
            # messages (no user handler — pure completion work) process
            # right here in parse order up to the inline budget, paying
            # zero wakes; requests and past-budget messages keep the
            # classic fresh-fiber fan-out (a blocking sync handler must
            # not serialize the burst), now spilled through ONE
            # amortized parking-lot signal (spawn_many) instead of a
            # signal per message.
            budget = flag("dispatch_inline_budget")
            inline_run = []
            spill = []
            for proto, msg in msgs[:-1]:
                meta = getattr(msg, "meta", None)
                if (len(inline_run) < budget and meta is not None
                        and hasattr(meta, "HasField")
                        and not meta.HasField("request")):
                    inline_run.append((proto, msg))
                else:
                    spill.append((proto, msg))
            if spill:
                counted_spawn_many(
                    self._control, socket,
                    [(lambda p=p_, m=m_: p.process(m, socket))
                     for p_, m_ in spill], name="process_burst")
            for proto, msg in inline_run:
                counted_run_inline(
                    self._control, socket,
                    (lambda p=proto, m=msg: p.process(m, socket)),
                    name=f"process_{proto.name}")
        proto, msg = msgs[-1]
        self._process_last(socket, proto, proto.process(msg, socket))
        return None

    def _process_last(self, socket: Socket, proto, r) -> None:
        """``r`` is what ``process`` returned for a cycle's last message:
        nothing where the processing was sync and is done, else its
        coroutine, stepped here until it finishes or first suspends,
        under its pending claim like any other queued message."""
        if r is not None and hasattr(r, "__await__"):
            counted_run_inline(self._control, socket, r,
                               name=f"process_{proto.name}")


def process_in_parse_order(socket: Socket, key: str, item,
                           handler: Callable) -> None:
    """Serialize order-critical message handling per connection: append to
    a per-socket queue and let exactly one drain fiber run ``handler(item,
    socket)`` for each item in parse order. Fibers run on multiple OS
    threads, so the pending/draining handoff takes a real lock. Used by
    HTTP/1.1 pipelining and the RESP FIFO (any protocol whose responses
    must leave in request order)."""
    lock = socket.user_data.setdefault(key + "_lock", threading.Lock())
    with lock:
        pending = socket.user_data.setdefault(key + "_pending", deque())
        pending.append(item)
        if socket.user_data.get(key + "_draining"):
            return
        socket.user_data[key + "_draining"] = True

    async def _drain():
        while True:
            # popleft outside the flag check would race a new enqueue;
            # keep both under one lock acquisition
            with lock:
                if not pending:
                    socket.user_data[key + "_draining"] = False
                    return
                it = pending.popleft()
            try:
                await handler(it, socket)
            except BaseException as e:
                # a dead drain fiber with _draining still True would wedge
                # the connection forever: fail it so the peer sees a close
                # instead of a silent hang
                with lock:
                    socket.user_data[key + "_draining"] = False
                socket.set_failed(e if isinstance(e, Exception)
                                  else ConnectionError(f"drain died: {e!r}"))
                raise

    socket._control.spawn(_drain, name=key + "_serial")
