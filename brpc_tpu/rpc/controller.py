"""Controller: per-call context & state machine for both sides
(brpc/controller.{h,cpp}, SURVEY.md §2.6).

Client side owns: correlation id (a versioned slot in a global pool — the
bthread_id of the reference), deadline timer, retries, backup request,
response data. Completion is a one-shot event that both fibers (await) and
plain threads (block) can wait on, matching Join(cid)'s dual waiters.

Server side owns: error state, attachments, device payloads, the response
path handle.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, List, Optional

from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.butil.resource_pool import ResourcePool
from brpc_tpu.fiber.sync import FiberEvent
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.transport.syscall_stats import (join_plucked as _njoin_plucked,
                                              join_waited as _njoin_waited)

# global correlation-id pool: id -> client Controller (the reference's
# bthread_id space, id.h:46). Native when available: fastcore's Pool is
# respool.cc (versioned slots, odd-version-live) holding the Controller
# objects — ids are never 0 by construction there. Resolved on FIRST
# USE, not import: fastcore.get() may compile the extension, and module
# import must stay cheap.
_call_pool = None
_call_pool_lock = threading.Lock()
_prf = None   # lazily bound client_dispatch.process_response_fast


def _pool():
    p = _call_pool
    if p is None:
        p = _make_pool()
    return p


def _make_pool():
    # locked: two first-RPC threads must agree on ONE pool — a call
    # registered in a discarded duplicate would never hear its response
    global _call_pool
    with _call_pool_lock:
        if _call_pool is None:
            from brpc_tpu.native import fastcore as _fastcore
            fc = _fastcore.get()
            if fc is not None:
                _call_pool = fc.Pool(1 << 17)
            else:
                p = ResourcePool()
                # reserve slot 0 forever: correlation id 0 must stay
                # invalid, because proto3 serializes 0 as an absent
                # field (a frame with no/zero correlation_id must never
                # address a live call)
                p.insert(None)
                _call_pool = p
        return _call_pool


def _postfork_reset() -> None:
    """Fork hygiene: every slot in the pool is a parent-side in-flight
    call whose socket/fiber the child does not own; a fresh child has
    zero calls in flight by definition."""
    global _call_pool, _call_pool_lock
    _call_pool = None
    _call_pool_lock = threading.Lock()


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("rpc.controller", _postfork_reset)


def address_call(correlation_id: int):
    return _pool().address(correlation_id)


def take_call(correlation_id: int):
    """Remove-and-return: the first finisher wins; stale responses and
    fired timers lose the race here (OnVersionedRPCReturned's version
    check, controller.cpp:575)."""
    return _pool().remove(correlation_id)


_lazy_create_lock = threading.Lock()
_MISSING = object()


class Controller:
    """Scalar fields live as CLASS defaults (an instance attribute
    appears only when written) and mutable members are created lazily on
    first touch — a Controller is built per call on BOTH sides of every
    RPC, and the reference keeps the equivalent cheap by pooling
    (resource_pool.h:14-47); in Python the analogous lever is not
    allocating the ~15 sub-objects a call never uses."""

    # ---- shared scalars
    error_code: int = berr.OK
    error_text: str = ""
    log_id: int = 0
    remote_side: Optional[EndPoint] = None
    local_side: Optional[EndPoint] = None
    auth_token: str = ""
    auth_context = None        # server side: verified peer identity
    compress_type: int = 0
    trace_id: int = 0
    span_id: int = 0
    # request priority / cost-class tag (RpcRequestMeta.priority):
    # client side set it BEFORE the call, server handlers read the
    # wire value here. 0 = unset — the tag is absent on the wire and
    # existing traffic is unchanged. Higher = more important is the
    # convention the traffic engine's per-class reports assume; the
    # DAGOR admission work will shed on it.
    request_priority: int = 0
    # ---- client side scalars
    timeout_ms: Optional[float] = None
    max_retry: Optional[int] = None   # None = inherit channel option
    backup_request_ms: Optional[float] = None
    correlation_id: int = 0
    response_payload: Optional[IOBuf] = None
    response_msg: Any = None
    _done_cb: Optional[Callable] = None
    current_try: int = 0
    start_us: int = 0
    end_us: int = 0
    used_backup: bool = False
    stream = None              # Stream piggybacked on this call
    # which server's response actually completed the call (set by
    # process_response; None on timeout/failure) — with backup
    # requests, tried_servers[-1] is NOT necessarily the winner
    responded_server = None
    _lb_swept_n: Optional[int] = None
    _owner_channel = None
    # ---- client call internals (set by Channel.call)
    _service_name: str = ""
    _method_name: str = ""
    _request_bytes: bytes = b""
    # ---- server side scalars
    _server_socket = None
    _response_sender: Optional[Callable] = None
    _progressive = None        # ProgressiveAttachment (http chunked)
    _session_local = None      # borrowed from the server's data pool
    _session_kv: Optional[dict] = None    # kvmap.h SessionKV
    # ---- deadline propagation (both sides): absolute monotonic-ns
    # deadline. Server side: stamped from the request's timeout_ms at
    # arrival (server_dispatch); client side: stamped by Channel.call —
    # retry/backup scheduling clamps to it (a retry that cannot possibly
    # complete is not issued).
    _deadline_ns: Optional[int] = None
    _completed = False         # set under _arb_lock by _complete
    _finalized = False         # _complete ran end-to-end (joiners gate)
    _issue_socket = None       # socket of the current attempt (pluck lane)

    # mutable members, created on first touch. _lb_lock guards the
    # tried/selection handshake between a late backup attempt and the
    # completion sweep; _arb_lock serializes take-and-complete /
    # take-and-retry (the reference gets this from the bthread_id lock,
    # id.h:46) — a response-error retry swaps the correlation id under
    # it so the deadline timer can never interleave with the swap.
    _LAZY = {
        "request_attachment": IOBuf,
        "response_attachment": IOBuf,
        "request_device_arrays": list,
        "response_device_arrays": list,
        "_done_event": FiberEvent,
        "_timer_ids": list,
        "tried_servers": list,      # endpoints tried (retry-elsewhere)
        "_complete_hooks": list,    # LB feedback / breaker / client spans
        "_lb_fed": list,
        "_cancel_subs": list,       # (socket, cb) notify_on_cancel subs
        "_lb_lock": threading.Lock,
        "_arb_lock": threading.RLock,
    }

    def __init__(self):
        pass

    def __getattr__(self, name):
        factory = Controller._LAZY.get(name)
        if factory is None:
            raise AttributeError(
                f"{type(self).__name__!r} object has no attribute {name!r}")
        # one global creation lock: two threads lazily materializing the
        # SAME lock field must agree on one object or arbitration breaks
        with _lazy_create_lock:
            d = self.__dict__
            v = d.get(name, _MISSING)
            if v is _MISSING:
                v = factory()
                d[name] = v
        return v

    def session_kv(self) -> dict:
        """Lazily-created per-call key/value annotations (kvmap.h +
        Controller::SessionKV): whatever the app records here is flushed
        to the log in one line when the call completes, so everything
        about one session lands greppable together. Flushing CLEARS the
        map, so on a reused controller any annotation added after the
        previous completion belongs to the NEXT call."""
        if self._session_kv is None:
            self._session_kv = {}
        return self._session_kv

    def flush_session_kv(self) -> None:
        """Log-and-clear (FlushSessionKV, controller.cpp:160: flushed at
        controller destruction; ours flushes at call completion). Never
        raises: a kv value whose __str__ explodes must not abort the
        completion path it runs on (join() would hang)."""
        kv = self._session_kv
        if not kv:
            return
        self._session_kv = None
        try:
            pairs = " ".join(f"{k}={v}" for k, v in kv.items())
            logging.getLogger("brpc_tpu.session").info(
                "Session ends. %s @%s.%s log_id=%d", pairs,
                self._service_name or "?", self._method_name or "?",
                self.log_id)
        except Exception:
            logging.getLogger("brpc_tpu.session").exception(
                "session_kv flush failed")

    def create_progressive_attachment(
            self, content_type: str = "application/octet-stream"):
        """HTTP chunked-response body fed after the handler returns
        (progressive_attachment.h); native streams use Stream instead."""
        from brpc_tpu.rpc.progressive import ProgressiveAttachment
        self._progressive = ProgressiveAttachment(content_type)
        return self._progressive

    def session_local_data(self):
        """Reusable per-request object from ServerOptions.
        session_local_data_factory (server.h session_local_data)."""
        return self._session_local

    # ---------------------------------------------------------------- names
    @property
    def service_name(self) -> str:
        return self._service_name

    @property
    def method_name(self) -> str:
        return self._method_name

    # --------------------------------------------------------------- error
    def failed(self) -> bool:
        return self.error_code != berr.OK

    def set_failed(self, code: int, text: str = "") -> None:
        self.error_code = code
        self.error_text = text or berr.errno_name(code)

    def reset_error(self) -> None:
        self.error_code = berr.OK
        self.error_text = ""

    def latency_us(self) -> int:
        return max(0, self.end_us - self.start_us)

    # ---------------------------------------------------- deadline budget
    def remaining_ms(self) -> Optional[float]:
        """Milliseconds left in this call's deadline budget, clamped at
        0.0; None when no deadline applies. Server side this is the
        CLIENT's remaining budget (arrival stamp + request timeout_ms):
        a handler past it is computing a response nobody will read —
        check it inside long loops, and nested calls the handler makes
        inherit it automatically (Channel.call shrinks their timeout to
        min(own timeout, this))."""
        dl = self._deadline_ns
        if dl is None:
            return None
        return max(0.0, (dl - time.monotonic_ns()) / 1e6)

    def deadline_expired(self) -> bool:
        """True once the deadline budget is exhausted (False when no
        deadline applies)."""
        dl = self._deadline_ns
        return dl is not None and time.monotonic_ns() >= dl

    # ---------------------------------------------------- client completion
    def _reset_for_call(self) -> None:
        """Per-CALL client state must reset on controller reuse (called
        at the top of Channel.call): a stale one-shot done event would
        make join() return before the new response arrives (with the
        previous call's payload), stale tried/attempt bookkeeping would
        exclude healthy servers or trip the cluster channel's
        late-attempt guard, a stale retry counter would shrink the new
        retry budget, and stale completion hooks (pooled-connection
        returns) would re-run and double-insert sockets into the pool.
        LB bookkeeping resets under _lb_lock — a still-in-flight backup
        attempt from the PREVIOUS call must not interleave with the
        reset and leak its selection."""
        self.reset_error()
        self.current_try = 0
        with self._arb_lock:
            self._completed = False
            self.__dict__.pop("_finalized", None)
            self._set_issue_socket(None)
            # fresh lazy event next call: a stale one-shot event would
            # make join() return with the previous call's payload
            self.__dict__.pop("_done_event", None)
        # __dict__ peeks: a FRESH controller (the common case) has no
        # instance state to reset — clearing class-default fields would
        # only materialize them
        d = self.__dict__
        d.pop("end_us", None)
        d.pop("_deadline_ns", None)        # new call, new budget
        d.pop("_pending_deadline", None)   # stale lazy deadline would
        #                                    clamp the new call's pluck
        d.pop("_pluck_fast", None)         # per-issue native-pluck hint
        d.pop("_fail_handled", None)       # per-attempt failure latch
        d.pop("_sync_fast", None)          # per-call pre-claim hint
        d.pop("_client_span", None)        # previous call's rpcz span
        d.pop("_attempt_spans", None)      # previous call's attempt spans
        d.pop("_bs_attempts", None)        # previous call's open backend
        #                                    stat-cell records (swept at
        #                                    completion; belt & braces)
        d.pop("_bs_resp_bytes", None)      # previous response's wire size
        # trace context is per-CALL: a stale trace_id would defeat the
        # serving-trace inheritance in Channel.call (the nested call
        # would chain onto the PREVIOUS request's tree) and pin every
        # reused controller to its first call's trace forever
        d.pop("trace_id", None)
        d.pop("span_id", None)
        pre = d.pop("_pluck_preclaimed", None)
        if pre is not None:                # unconsumed pre-send claim
            pre.pluck_release()
        d.pop("response_payload", None)
        d.pop("response_attachment", None)
        d.pop("response_device_arrays", None)
        d.pop("responded_server", None)
        d.pop("used_backup", None)
        d.pop("_hedge_decision", None)     # previous call's hedge arming
        d.pop("request_priority", None)    # per-call tag: a reused
        #                                    controller must not carry
        #                                    the previous call's class
        d.pop("_adm_local_sheds", None)    # per-call doomed-send count
        d.pop("stream", None)     # a previous call's stream must not
        #                           resurface on the new call's response
        hooks = d.get("_complete_hooks")
        if hooks:
            hooks.clear()
        if d.get("tried_servers") or d.get("_lb_fed") \
                or d.get("_lb_swept_n") is not None:
            with self._lb_lock:
                self.tried_servers.clear()
                self._lb_swept_n = None
                self._lb_fed = []

    def _set_issue_socket(self, sock) -> None:
        """Balanced per-socket in-flight accounting around every
        _issue_socket assignment (issue, retry/backup re-issue, reset,
        completion): socket.client_inflight counts calls issued and not
        yet completed on the socket, which gates the lazy-deadline
        pluck (join). The old->new swap runs under _arb_lock — a backup
        re-issue on the timer thread racing completion on the IO thread
        must not both read the same 'old' (double-decrement + leaked
        increment would skew the gate permanently); each thread then
        applies its own counter deltas, which commute."""
        d = self.__dict__
        with self._arb_lock:
            old = d.get("_issue_socket")
            if old is sock:
                return
            if sock is None:
                d.pop("_issue_socket", None)
            else:
                d["_issue_socket"] = sock
        lazy_to_arm = None
        if old is not None:
            with old.pending_lock:
                old.client_inflight -= 1
                old.inflight_calls.discard(self)
        if sock is not None:
            with sock.pending_lock:
                sock.client_inflight += 1
                sock.inflight_calls.add(self)
                if sock.client_inflight > 1:
                    # a lazy-deadline plucker owns this socket's input:
                    # OUR (possibly huge) response will run through its
                    # processing pass, during which its deadline cannot
                    # preempt — give it the real timer it skipped. The
                    # pending_lock orders this against the plucker's own
                    # register-or-arm decision in join(), so one side
                    # always arms.
                    lazy_to_arm = sock._lazy_plucker
            if sock.failed:
                # registration raced set_failed's drain: the drain may
                # have snapshotted before our add — re-trigger it (the
                # drain is idempotent) so this call can't sit out the
                # full deadline on a dead socket
                sock._drain_inflight_calls()
        if lazy_to_arm is not None and lazy_to_arm is not self:
            lazy_to_arm._arm_lazy_deadline()

    def _register_call(self) -> int:
        try:
            self.correlation_id = _pool().insert(self)
        except RuntimeError:
            # native pool exhausted (131072 live in-flight calls): fail
            # THIS call with a limit error instead of crashing the call
            # path — bounded-id backpressure, not unbounded growth
            raise OverflowError("correlation-id pool exhausted "
                                "(too many in-flight calls)") from None
        return self.correlation_id

    def _add_complete_hook(self, hook) -> None:
        """Completion-aware registration: a hook added AFTER the call
        completed (start_cancel can finish the call while _issue_rpc is
        still registering pooled-socket return hooks) runs immediately
        instead of silently never running — which would leak the pooled
        connection."""
        with self._arb_lock:
            if not self._completed:
                self._complete_hooks.append(hook)
                return
        try:
            hook(self)
        except Exception:
            pass

    def _complete(self) -> None:
        d = self.__dict__
        with self._arb_lock:
            self._completed = True
        self.end_us = time.monotonic_ns() // 1000
        # retry-budget accounting — here because _complete is the ONE
        # point every client completion flavor passes through: every
        # successful call slowly re-earns tokens, and a CLIENT-LOCAL
        # timeout (no responder: the deadline timer or the sync-pluck
        # joiner fired) drains one — a stalled cluster whose sockets
        # stay alive produces exactly these, and without the drain the
        # bucket would stay pinned at capacity while hedges pile load
        # onto the stall. Other failures drained in the channel's
        # failure paths already; a server-RESPONDED deadline shed is a
        # reject (responded_server set) and costs nothing.
        ch = d.get("_owner_channel")
        if ch is not None:
            rb = ch._retry_budget
            if rb is not None:
                if self.error_code == 0:
                    rb.refill()
                elif self.error_code == berr.ERPCTIMEDOUT \
                        and d.get("responded_server") is None:
                    rb.drain()
        # __dict__ peeks: lazily-created members that were never touched
        # need no completion work — don't materialize them just to find
        # them empty (this runs once per call)
        tids = d.get("_timer_ids")
        if tids:
            from brpc_tpu.fiber.timer import global_timer
            for tid in tids:
                global_timer().unschedule(tid)
            tids.clear()
        if self.failed():
            # a stream piggybacked on a failed call must not leak in the
            # global stream pool (timeout/socket-failure completion paths
            # never reach client_dispatch)
            stream = getattr(self, "stream", None)
            if stream is not None:
                stream.close()
        for hook in d.get("_complete_hooks", ()):
            try:
                hook(self)
            except Exception:
                pass
        # a completed call must not pin its socket (conn + portal read
        # blocks) for the controller's lifetime
        self._set_issue_socket(None)
        cb = self._done_cb
        # joiners may only observe completion AFTER end_us, timer
        # cancellation and the completion hooks above — _finalized (not
        # _completed, which arbitration publishes first) gates the
        # lazy-event fast path, and the done event is read under the
        # same lock join() creates it under, so a joiner either sees
        # _finalized or its fresh event is seen here
        with self._arb_lock:
            d["_finalized"] = True
            ev = d.get("_done_event")
        if ev is not None:
            ev.set()
        if cb is not None:
            cb(self)
        # after the done callback, so annotations recorded there land in
        # THIS call's line (the reference flushes at destruction, which
        # is also after done runs)
        self.flush_session_kv()

    def start_cancel(self) -> None:
        """Cancel an in-flight client call (Controller::StartCancel):
        completes NOW with ECANCELED; the late response finds no call
        and is dropped by the versioned-id arbitration. No-op if the
        call already finished or was never issued. Like the reference,
        cancellation is client-local — the server may still execute
        the handler."""
        if self.correlation_id == 0:
            # never registered (fresh/server-side/combo-parent
            # controller): taking id 0 would consume the reserved
            # slot-0 sentinel (see _call_pool setup)
            return
        with self._arb_lock:
            taken = take_call(self.correlation_id) is self
        if taken:
            self.set_failed(berr.ECANCELED, "canceled by caller")
            self._complete()

    # ------------------------------------------------- server-side cancel
    def is_canceled(self) -> bool:
        """Server side (Controller::IsCanceled): True once the client's
        connection is gone — a long handler should stop wasting work on
        a response nobody will read.

        Detection requires the connection's input fiber to be free to
        observe the EOF: run long handlers with
        ``ServerOptions(usercode_in_pthread=True)`` (the reference gets
        the same decoupling from its dedicated event-dispatcher
        bthreads). An in-place handler monopolizes the input fiber, so
        the EOF is only drained after it returns."""
        s = self._server_socket
        return bool(s is not None and s.failed)

    def notify_on_cancel(self, callback: Callable[[], None]) -> None:
        """Server side (Controller::NotifyOnCancel): run ``callback``
        when the client's connection dies; immediately if it already
        has. At most once per request — the subscription is dropped
        when the request completes, so keep-alive connections serving
        many requests don't accumulate stale notifications."""
        s = self._server_socket
        if s is None:
            return
        wrapped = lambda _sock: callback()   # noqa: E731
        self._cancel_subs.append((s, wrapped))
        s.on_failed(wrapped)

    def _drop_cancel_subs(self) -> None:
        """Called when the server request completes: a finished
        request must not hear about later connection deaths."""
        subs = self.__dict__.get("_cancel_subs")
        if not subs:
            return
        self._cancel_subs = []
        for s, cb in subs:
            try:
                s.off_failed(cb)
            except AttributeError:
                pass

    def _join_event(self):
        """Finalized -> None (nothing to wait for); else the lazily
        created done event, under the lock _complete reads it under.
        Gates on _finalized, not _completed: between the two, _complete
        is still cancelling timers and running completion hooks, and a
        joiner returning that early would read a stale end_us / race
        the LB feedback."""
        d = self.__dict__
        if d.get("_finalized"):
            return None
        with self._arb_lock:
            if d.get("_finalized"):
                return None
            return self._done_event   # lazy-created via _LAZY

    def join(self, timeout_s: Optional[float] = None) -> bool:
        """Block the calling thread until the call finishes.

        Non-worker threads first try the sync-pluck lane: the joiner
        adopts the issuing socket's input and processes its own
        response in place (Socket.pluck_until) — zero cross-thread
        wakes. Fiber workers and pluck-incapable transports fall to
        the event wait. A pre-send claim taken by the issue path
        (pluck_preclaim) is consumed here, or released on every path
        that cannot pluck — an unconsumed claim would wedge the
        socket (reads paused forever)."""
        pre = self.__dict__.pop("_pluck_preclaimed", None)
        try:
            if self._finalized:
                return True
            sock = self._issue_socket
            if pre is not None and pre is not sock:
                # a retry moved the call off the preclaimed socket:
                # release NOW — holding its lane (reads paused) while
                # we pluck the new socket would starve every other
                # call multiplexed on it for up to the deadline
                pre.pluck_release()
                pre = None
            pend = self.__dict__.get("_pending_deadline")
            if sock is not None and not sock.failed:
                from brpc_tpu.fiber.scheduler import current_group
                if current_group() is None:
                    deadline = time.monotonic() + (
                        timeout_s if timeout_s is not None else 86400.0)
                    if pend is not None:
                        # multiplex gate, bilateral with
                        # _set_issue_socket: under the same lock, either
                        # we see other calls in flight (keep the real
                        # timer), or we register as the socket's lazy
                        # plucker so a later issuer arms our timer for
                        # us — no window where a big foreign response
                        # can stall the deadline with no timer
                        with sock.pending_lock:
                            if sock.client_inflight > 1:
                                pend = None
                            else:
                                sock._lazy_plucker = self
                        if pend is None:
                            self._arm_lazy_deadline()
                    # lazy deadline (call_sync): the plucker IS the
                    # timer — clamp the pluck to the RPC deadline and
                    # fire the final timeout path ourselves if it passes
                    # (same thread-safe take the timer thread would do)
                    pluck_deadline = deadline if pend is None \
                        else min(deadline, pend[1])
                    # native receive loop (fastcore pluck_scan): armed
                    # by the small-frame issue path; completes through
                    # the same process_response_fast the turbo
                    # dispatcher uses
                    fast = None
                    pf = self.__dict__.get("_pluck_fast")
                    if pf is not None:
                        global _prf
                        if _prf is None:
                            from brpc_tpu.rpc.client_dispatch import \
                                process_response_fast as _prf_mod
                            _prf = _prf_mod
                        fast = (pf[0], self.correlation_id, pf[1], _prf)
                    try:
                        claimed = pre is sock
                        if claimed:
                            pre = None   # pluck_until settles the claim
                        if sock.pluck_until(lambda: self._finalized,
                                            pluck_deadline, fast=fast,
                                            preclaimed=claimed):
                            _njoin_plucked.add(1)
                            return True
                    except Exception:
                        pass   # pluck is an optimization, never a failure
                    finally:
                        if pend is not None:
                            with sock.pending_lock:
                                if sock._lazy_plucker is self:
                                    sock._lazy_plucker = None
                    if pend is not None and not self._finalized and \
                            time.monotonic() >= pend[1]:
                        try:
                            pend[0]._on_timeout(self)
                        except Exception:
                            pass
                        if self._finalized:
                            return True
                    if timeout_s is not None:
                        timeout_s = max(0.0, deadline - time.monotonic())
        finally:
            if pre is not None:
                try:
                    pre.pluck_release()
                except Exception:
                    pass
        # leaving the pluck lane (escalation, failed socket, fiber
        # caller, claim contention): the deadline needs a real timer
        self._arm_lazy_deadline()
        ev = self._join_event()
        if ev is None:
            return True
        _njoin_waited.add(1)
        return ev.wait_pthread(timeout_s)

    def _arm_lazy_deadline(self) -> None:
        """Convert a pending (lazily-enforced) deadline into a real
        timer — called whenever the call leaves the sync-pluck lane, so
        deadline semantics are identical to the eager path from here."""
        pend = self.__dict__.pop("_pending_deadline", None)
        if pend is None or self._finalized:
            return
        ch, dl = pend
        from brpc_tpu.fiber.timer import global_timer
        tid = global_timer().schedule_at(dl, lambda: ch._on_timeout(self))
        self._timer_ids.append(tid)
        if self._completed:      # completion interleaved with the arm
            global_timer().unschedule(tid)

    async def join_async(self, timeout_s: Optional[float] = None) -> bool:
        self._arm_lazy_deadline()   # fiber joiner cannot pluck-enforce
        ev = self._join_event()
        return True if ev is None else await ev.wait(timeout_s)
