"""Channel: the client stub (brpc/channel.{h,cpp}).

Owns protocol choice, timeout/retry/backup-request defaults, and the
connection to a single server (naming-service + load-balanced cluster
channels compose on top — see rpc/cluster_channel.py). The call path
mirrors Channel::CallMethod -> Controller::IssueRPC -> Socket::Write
(SURVEY.md §3.1): serialize, register correlation id, pack, enqueue,
arm deadline/backup timers, wait.
"""

from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

from brpc_tpu.butil.endpoint import EndPoint, str2endpoint
from brpc_tpu.butil.flags import flag as _flag
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.fiber import TaskControl, global_control
from brpc_tpu.fiber.timer import global_timer
from brpc_tpu.protocol.proto import tpu_rpc_meta_pb2 as pb
from brpc_tpu.protocol.tpu_std import (_HDR as _TPU_HDR, MAGIC as _TPU_MAGIC,
                                       SMALL_FRAME_MAX,
                                       _TAG_ATTACHMENT_SIZE,
                                       _TAG_CORRELATION_ID, _varint,
                                       pack_frame_head, pack_message,
                                       pack_small_frame, serialize_payload)

_TAG_CORRELATION_ID_B = _TAG_CORRELATION_ID.to_bytes(1, "big")
_TAG_ATTACHMENT_SIZE_B = _TAG_ATTACHMENT_SIZE.to_bytes(1, "big")
from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.rpc import backend_stats as _bs
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.controller import Controller, address_call, take_call
from brpc_tpu.rpc.span import recording as _span_recording
from brpc_tpu.transport import socket as _socket_mod
from brpc_tpu.transport.input_messenger import InputMessenger
from brpc_tpu.transport.socket import Socket, create_client_socket


def _fail_inflight_calls(sock, calls) -> None:
    """Socket-failure fan-out: every client call still issued on the
    dead socket fails (or retries elsewhere) NOW instead of sitting out
    its full deadline — the reference's SetFailed -> bthread_id_error
    behavior (socket.cpp; OnVersionedRPCReturned sees EFAILEDSOCKET
    immediately). Runs on a fiber (retries may reconnect, which blocks);
    take_call arbitration on the SNAPSHOT correlation id makes racing
    completions — and a controller recycled onto a brand-new call
    before this fiber ran — a no-op."""
    reason = str(sock.fail_reason or "socket failed")
    for cntl, cid, seq in calls:
        ch = getattr(cntl, "_owner_channel", None)
        try:
            if ch is not None:
                ch._maybe_retry(cntl, berr.EFAILEDSOCKET,
                                f"socket failed: {reason}",
                                failed_ep=sock.remote_endpoint,
                                expect_cid=cid, expect_seq=seq)
                continue
            with cntl._arb_lock:
                if cntl.__dict__.get("_issue_seq") != seq:
                    continue   # re-issued since the snapshot: stale
                taken = take_call(cid) is cntl
            if taken:
                cntl.set_failed(berr.EFAILEDSOCKET,
                                f"socket failed: {reason}")
                cntl._complete()
        except Exception:
            pass   # one broken call must not strand the rest


_socket_mod.inflight_failer = _fail_inflight_calls


_client_fdr = None   # lazily built; False = extension unavailable

# retries/backups not issued because the call's deadline budget could
# not possibly cover them (/vars) — the client half of deadline
# propagation: an attempt that cannot complete is never launched
nretry_suppressed = Adder().expose("retry_suppressed_budget")

# retries/hedges suppressed because the channel's retry token bucket
# ran dry (RetryBudget — overload must not be amplified) — /vars
nretry_throttled = Adder().expose("retry_throttled")

# hedges not armed because the remaining deadline budget sat under the
# fastest backend's recent p50 (a hedge that cannot win is pure load;
# Dean & Barroso, The Tail at Scale) — /vars
nhedge_suppressed = Adder().expose("hedge_suppressed_budget")

# sends failed fast CLIENT-side against a piggybacked admission
# threshold (DAGOR: doomed traffic stops at the source instead of
# burning a socket round trip to be shed at the server's door) — /vars
nclient_priority_shed = Adder().expose("client_priority_shed")

# admission-threshold cache discipline (Channel._adm_cache): entries
# expire after TTL, a broken CONNECTION drops its backend's entries at
# once (a restarted backend must not inherit a stale threshold — see
# _on_attempt_failed), and every PROBE interval one doomed send per
# (backend, service) goes through anyway so a relaxing threshold is
# observed
ADM_THRESHOLD_TTL_S = 5.0
ADM_PROBE_INTERVAL_S = 0.25

# failure codes that never drain the retry token bucket: overload
# REJECTS cost the server microseconds at the door (see _maybe_retry),
# and a naming-empty fail-fast burns nothing anywhere — draining on it
# would leave the channel throttled long after the naming url is fixed
# (NamingEmptyError's stated contract)
_NO_DRAIN_CODES = frozenset(_bs.REJECT_CODES) | {berr.ENAMINGEMPTY}

_csc = None   # lazily bound server_dispatch.current_serving_controller


def client_fast_drain_hook(options):
    """The client-side chunk fast lane for a channel's sockets (None
    when inapplicable): only default-protocol (tpu_std) channels — the
    lane scans MAGIC-framed responses."""
    if options.protocol not in ("", "tpu_std"):
        return None
    global _client_fdr
    if _client_fdr is None:
        from brpc_tpu.rpc.client_dispatch import make_client_fast_drain
        _client_fdr = make_client_fast_drain() or False
    return _client_fdr or None


@dataclass
class ChannelOptions:
    protocol: str = "tpu_std"
    connection_type: str = "single"      # single | pooled | short
    # stable channel name for per-backend client telemetry (/backends,
    # /lb_trace, the backend_stats prometheus labels); empty = an
    # auto-generated "channel-N" (cluster channels default to their
    # naming url). Reuse ONE name for channels that mean the same
    # dependency — cells are keyed by it.
    name: str = ""
    timeout_ms: Optional[float] = 1000.0
    max_retry: int = 3
    backup_request_ms: Optional[float] = None
    auth_token: str = ""
    # pluggable Authenticator (rpc/auth.py): generate_credential() result
    # rides the request meta; wins over auth_token
    auth: Optional[Any] = None
    # app-level health check (details/health_check.cpp:59-144): a
    # callable(EndPoint)->bool that must succeed before a dead server is
    # revived — use rpc_health_check(...) for the RPC-probe flavor.
    # Cluster channels only; None keeps the bare-connect gate.
    app_health_check: Optional[Any] = None
    # process-global connection sharing for connection_type="single"
    # (socket_map.h:147): channels to the same (endpoint, protocol) reuse
    # one Socket
    share_connections: bool = True
    # pluggable retry decision (retry_policy.h): RetryPolicy instance or
    # callable(Controller)->bool; None = default (transport/availability
    # errors retry, semantic errors don't). Consulted for every failed
    # attempt while tries remain — including server-returned errors.
    retry_policy: Optional[Any] = None
    # per-channel retry token bucket (retry_policy.RetryBudget — the
    # gRPC retryThrottling shape): failed attempts drain, successes
    # slowly refill, and an empty bucket suppresses retries AND hedges
    # (`retry_throttled` bvar) so a cluster brown-out cannot be
    # amplified into an outage by the retry storm. True = defaults
    # (100 tokens, 0.1 refill), an instance = custom sizing, None = off.
    retry_budget: Optional[Any] = None
    # how long ClusterChannel's constructor waits for the naming
    # service's first server-list update before giving up (calls then
    # fail fast with ENAMINGEMPTY + the `naming_empty` bvar while the
    # list stays empty)
    naming_wait_s: float = 5.0
    # naming-service filter (naming_service_filter.h): callable
    # (EndPoint)->bool; servers it rejects never reach the load
    # balancer. Cluster channels only.
    ns_filter: Optional[Any] = None
    # channel-group retry budget (ISSUE 14): every channel in a process
    # naming the same group shares ONE RetryBudget — a process holding
    # N channels to one cluster otherwise gives a brown-out N buckets
    # of retry fuel (the PR 10 amplification hole). The group's sizing
    # comes from the FIRST member's retry_budget spec; later members
    # join the existing bucket. Empty = per-channel budget semantics
    # unchanged.
    budget_group: str = ""




def connect_dedup(lock, read_fn, write_fn, make_fn):
    """Connect outside the lock, publish under it; exactly one winner per
    slot, losers are discarded (shared by Channel and ClusterChannel)."""
    cur = read_fn()
    if cur is not None and not cur.failed:
        return cur
    new = make_fn()
    with lock:
        cur = read_fn()
        if cur is not None and not cur.failed:
            loser = new
        else:
            write_fn(new)
            loser = None
    if loser is not None:
        loser.set_failed(ConnectionError("duplicate connect discarded"))
        with lock:
            cur = read_fn()
        if cur is None or cur.failed:
            raise ConnectionError("connection closed concurrently")
        return cur
    return new


_chan_seq = itertools.count(1)


class Channel:
    def __init__(self, address: Optional[str | EndPoint] = None,
                 options: Optional[ChannelOptions] = None,
                 control: Optional[TaskControl] = None):
        self.options = options or ChannelOptions()
        # per-backend telemetry identity (backend_stats cells + the LB
        # decision ring are keyed by it); subclasses override
        # _default_stats_name (ClusterChannel: its naming url) so the
        # registration happens exactly once
        self._stats_name = self.options.name or self._default_stats_name()
        _bs.global_stats().register_channel(self._stats_name, self)
        if self.options.budget_group:
            # cluster-scoped token bucket: all channels in the group
            # drain/refill ONE budget (retry_policy.shared_retry_budget)
            from brpc_tpu.rpc.retry_policy import shared_retry_budget
            self._retry_budget = shared_retry_budget(
                self.options.budget_group, self.options.retry_budget)
        elif self.options.retry_budget is not None:
            from brpc_tpu.rpc.retry_policy import RetryBudget
            self._retry_budget = RetryBudget.resolve(
                self.options.retry_budget)
        else:
            self._retry_budget = None
        # piggybacked admission thresholds, keyed (backend, service):
        # plain dict, atomic get/set/pop only — fed by the response
        # paths, consulted by the issue path's doomed-send fail-fast.
        # Empty (the overwhelming common case) costs one truthiness
        # check per issue/response.
        self._adm_cache: dict = {}
        self._adm_sweep = 0.0          # last stale-entry sweep stamp
        self._control = control or global_control()
        self._messenger = InputMessenger(control=self._control)
        self._socket: Optional[Socket] = None
        self._socket_lock = threading.Lock()
        self._map_key = None                 # global SocketMap lease key
        self._endpoint: Optional[EndPoint] = None
        self._framer_cache = None
        # (service, method, timeout_ms, auth_token) -> serialized RpcMeta
        # prefix (everything but correlation_id/attachment_size); the
        # small-call fast path appends those as hand-encoded varint
        # fields per call instead of building a pb object
        self._meta_prefix_cache: dict = {}
        # pooled-connection_type freelist (socket.h connection pooling)
        self._conn_pool: List[Socket] = []
        self._pool_lock = threading.Lock()
        self._pool_closed = False
        if address is not None:
            self.init(address)

    def init(self, address: str | EndPoint) -> None:
        self._endpoint = (address if isinstance(address, EndPoint)
                          else str2endpoint(address))

    def _default_stats_name(self) -> str:
        return f"channel-{next(_chan_seq)}"

    @property
    def stats_name(self) -> str:
        """This channel's row key on /backends and /lb_trace."""
        return self._stats_name

    lb_name = None    # /backends channel header; ClusterChannel overrides

    def _label_socket(self, s, ep) -> None:
        """Tag a channel-owned socket with its owner identity so the
        /connections client rows are attributable at a glance. First
        owner wins on a socket_map-shared connection — the label names
        who DIALED it, not every multiplexed tenant."""
        ud = s.user_data
        ud.setdefault("channel", self._stats_name)
        ud.setdefault("backend", _bs.ep_key(ep))

    # ---------------------------------------------------------- connection
    def _get_socket(self) -> Socket:
        def _make():
            s = create_client_socket(
                self._endpoint, on_input=self._messenger.on_new_messages,
                control=self._control)
            s.fast_drain = client_fast_drain_hook(self.options)
            self._label_socket(s, self._endpoint)
            return s

        if (self.options.connection_type == "single"
                and self.options.share_connections):
            # process-global sharing (socket_map.h:147): one multiplexed
            # connection per (endpoint, protocol) across ALL channels;
            # this channel holds one refcounted lease on it
            from brpc_tpu.transport.socket_map import (SocketMap,
                                                       global_socket_map)
            with self._socket_lock:
                s = self._socket
            # probe OUTSIDE _socket_lock: a dead peer turns the probe
            # into set_failed, whose on_failed callbacks run inline and
            # may re-enter the channel (callback-under-lock)
            if s is not None and not s.failed \
                    and not s.probe_unobserved():
                return s
            # the key carries the credential flavor (socket_map.h keys
            # include ssl/auth settings): channels with different
            # credentials must not multiplex one verified connection
            auth_part = (self.options.auth_token
                         or (f"auth#{id(self.options.auth)}"
                             if self.options.auth is not None else ""))
            key = SocketMap.key(self._endpoint,
                                f"{self.options.protocol}|{auth_part}")
            s = global_socket_map().acquire(key, _make)
            self._label_socket(s, self._endpoint)
            with self._socket_lock:
                old, self._socket = self._socket, s
                self._map_key = key
            if old is not None:
                # this channel holds exactly ONE lease: drop the stale
                # socket's lease — or, when a concurrent first call
                # already stored this very socket, the duplicate lease
                # the second acquire() just took
                global_socket_map().release(key, old)
            return s

        def _write(s):
            self._socket = s

        return connect_dedup(self._socket_lock, lambda: self._socket,
                             _write, _make)

    def device_lane_kind(self,
                         timeout_s: float = 2.0) -> Optional[str]:
        """The device-lane flavor of this channel's connection
        ('local-d2d' / 'pjrt-pull' / 'staged'), or None when the
        transport has no device lane at all. Dials lazily and waits
        (bounded) for the lane hello, since the flavor is negotiated —
        combo channels probe this once per generation before lowering
        a device fan-out to one XLA collective."""
        try:
            sock = self._get_socket()
        except Exception:
            return None
        conn = sock.conn
        if not conn.supports_device_lane:
            return None
        kind = conn.lane_kind
        if kind is None:
            return None
        if conn.peer_info is None:
            # hello still in flight: the kind would read as the staged
            # floor; wait for the negotiated answer
            deadline = time.monotonic() + timeout_s
            while conn.peer_info is None:
                if sock.failed or time.monotonic() >= deadline:
                    break
                time.sleep(0.001)
            kind = conn.lane_kind
        return kind

    def reply_device(self):
        """The local device this channel's replies' device arrays land
        on: the endpoint's ``#reply_device=K``, device 0 where it names
        none (what the ``ici://`` and ``tpu://`` dials read)."""
        from brpc_tpu.butil.jax_runtime import local_device
        return local_device(self._endpoint.reply_device,
                            f"{self._endpoint} #reply_device")

    def close(self) -> None:
        """Release the connection(s); the channel may be re-used (it will
        reconnect lazily)."""
        with self._socket_lock:
            s, self._socket = self._socket, None
            key, self._map_key = self._map_key, None
        if s is not None:
            if key is not None:
                # shared socket: return the lease; it closes only when
                # the last channel lets go
                from brpc_tpu.transport.socket_map import global_socket_map
                global_socket_map().release(key, s)
            elif not s.failed:
                s.set_failed(ConnectionError("channel closed"))
        with self._pool_lock:
            pool, self._conn_pool = self._conn_pool, []
            self._pool_closed = True
        for sock in pool:
            if not sock.failed:
                sock.set_failed(ConnectionError("channel closed"))

    # ---------------------------------------------------------------- call
    def call(self, service_name: str, method_name: str, request: Any = b"",
             cntl: Optional[Controller] = None,
             done: Optional[Callable[[Controller], None]] = None,
             request_device_arrays: Optional[List] = None,
             response_class=None, stream_options=None,
             _lazy_deadline: bool = False) -> Controller:
        """Begin an RPC; returns the Controller immediately. Wait with
        cntl.join() (thread) / await cntl.join_async() (fiber), or pass
        ``done`` for callback style — the async CallMethod triple."""
        cntl = cntl or Controller()
        if "_completed" in cntl.__dict__:
            cntl._reset_for_call()   # reused controller: full reset
        else:
            # fresh controller: nothing to reset — just arm completion
            # (the done event itself is lazy: created by the first
            # joiner that arrives before completion)
            cntl.__dict__["_completed"] = False
        cntl.start_us = time.monotonic_ns() // 1000
        if cntl.timeout_ms is None:
            cntl.timeout_ms = self.options.timeout_ms
        # deadline inheritance: a call made INSIDE a serving handler may
        # not outlive the request being served — shrink to the parent's
        # remaining budget (min rule; docs/robustness.md). A parent with
        # no deadline inherits nothing.
        global _csc
        if _csc is None:
            from brpc_tpu.rpc.server_dispatch import \
                current_serving_controller as _csc_mod
            _csc = _csc_mod
        parent = _csc()
        if parent is not None and parent is not cntl:
            # trace propagation: a nested call joins the serving
            # request's trace (the server span's id becomes this call's
            # parent), so a client->A->B chain assembles into ONE tree
            # across processes (tools/trace.py). Only when the parent
            # actually carries a trace — otherwise the fast framing
            # path stays trace-free.
            if parent.trace_id and not cntl.trace_id:
                cntl.trace_id = parent.trace_id
                cntl.span_id = parent.span_id
            # priority inheritance (ISSUE 14): a nested call carries
            # the serving request's business priority unless the
            # caller explicitly set one — a chain's class survives
            # hops exactly like its deadline budget does below (same
            # fiber-local path; 0 = unset inherits, a reused
            # controller was reset by _reset_for_call)
            if parent.request_priority and not cntl.request_priority:
                cntl.request_priority = parent.request_priority
            rem = parent.remaining_ms()
            if rem is not None:
                if rem <= 0.0:
                    # the parent's budget is already gone: issuing would
                    # waste a downstream server's time on a reply nobody
                    # can use — fail fast, before any socket work
                    cntl._done_cb = done
                    cntl.set_failed(berr.ERPCTIMEDOUT,
                                    "parent request's deadline budget "
                                    "exhausted before nested call")
                    cntl._complete()
                    return cntl
                if cntl.timeout_ms is None or cntl.timeout_ms > rem:
                    cntl.timeout_ms = rem
        if cntl.timeout_ms is not None:
            # the client-side absolute deadline: retry/backup scheduling
            # clamps to it (cheap: one subtraction per retry decision)
            cntl.__dict__["_deadline_ns"] = time.monotonic_ns() \
                + int(cntl.timeout_ms * 1e6)
        if cntl.max_retry is None:
            cntl.max_retry = self.options.max_retry
        if cntl.backup_request_ms is None:
            cntl.backup_request_ms = self.options.backup_request_ms
        cntl._done_cb = done
        if not cntl.auth_token:
            if self.options.auth is not None:
                cntl.auth_token = self.options.auth.generate_credential()
            else:
                cntl.auth_token = self.options.auth_token
        if request_device_arrays:
            cntl.request_device_arrays = list(request_device_arrays)
        if response_class is not None:
            cntl.response_msg = response_class()
        elif cntl.response_msg is not None:
            cntl.response_msg = None
        cntl._service_name = service_name
        cntl._method_name = method_name
        cntl._request_bytes = serialize_payload(request)
        if cntl.compress_type:
            # compress once here, not per (re)issue attempt
            from brpc_tpu.rpc.compress import compress
            cntl._request_bytes = compress(cntl._request_bytes,
                                           cntl.compress_type)
        if stream_options is not None:
            # stream setup piggybacks on this RPC (StreamCreate)
            from brpc_tpu.rpc.stream import Stream
            cntl.stream = Stream(stream_options)
        if _span_recording():
            from brpc_tpu.rpc.span import finish_span, start_client_span
            span = start_client_span(cntl, service_name, method_name)
            span.request_size = len(cntl._request_bytes)
            # the issue path stamps write_done_us on it (request write
            # completion) and the response path stamps first_byte /
            # parse_done — per-call, popped by _reset_for_call on reuse
            cntl.__dict__["_client_span"] = span
            # a reused Controller must not accumulate span hooks across
            # calls (stale spans would be re-finished with this call's
            # data and resubmitted)
            cntl._complete_hooks = [
                h for h in cntl._complete_hooks
                if not getattr(h, "_span_hook", False)]
            hook = lambda c, s=span: (finish_span(s, c),  # noqa: E731
                                      _settle_attempt_spans(c))
            hook._span_hook = True
            cntl._complete_hooks.append(hook)
        cntl._owner_channel = self  # response-path retry needs the channel
        try:
            cntl._register_call()
        except OverflowError as e:
            # bounded correlation-id space (native respool): complete
            # the call with ELIMIT instead of crashing the caller —
            # in-flight backpressure, matching concurrency-limiter
            # semantics
            cntl.set_failed(berr.ELIMIT, str(e))
            cntl._complete()
            return cntl
        if _lazy_deadline:
            # sync caller on a plain thread: the issue path may claim
            # the pluck lane BEFORE the send (pluck_preclaim), so the
            # response can only complete on the joining thread — on a
            # 1-core box the dispatcher otherwise wins the race to the
            # response about half the time (cross-thread completion +
            # event-wait join, the expensive shape). Set HERE, after
            # every path that could return without issuing — a leaked
            # flag would make a later done-callback call preclaim a
            # lane no joiner ever consumes (a wedged socket).
            from brpc_tpu.fiber.scheduler import current_group
            if current_group() is None:
                cntl.__dict__["_sync_fast"] = True
        self._issue_rpc(cntl)
        # deadline timer: final — no retry after it fires (HandleTimeout).
        # With inline input processing the response may have completed
        # DURING _issue_rpc: arming then would pin the controller in the
        # timer heap for the full timeout (the leak unschedule exists to
        # prevent), so check first — and re-check after arming, because a
        # completion on another thread can interleave with the arm.
        if cntl.timeout_ms is not None and not cntl._completed:
            if _lazy_deadline:
                # sync-pluck fast path (call_sync): the joiner that is
                # about to pluck enforces the deadline itself, so the
                # common completed-in-time call never touches the timer
                # heap (arm + cancel measured ~15-25us/call). join()
                # arms the real timer the moment the call leaves the
                # pluck lane (escalation, socket failure, fiber caller).
                cntl.__dict__["_pending_deadline"] = (
                    self, time.monotonic() + cntl.timeout_ms / 1e3)
            else:
                tid = global_timer().schedule_after(
                    cntl.timeout_ms / 1e3, lambda: self._on_timeout(cntl))
                cntl._timer_ids.append(tid)
                if cntl._completed:
                    global_timer().unschedule(tid)
        if cntl.backup_request_ms is not None and cntl.backup_request_ms > 0 \
                and not cntl._completed:
            tid = global_timer().schedule_after(
                cntl.backup_request_ms / 1e3, lambda: self._on_backup_timer(cntl))
            cntl._timer_ids.append(tid)
            if cntl._completed:
                global_timer().unschedule(tid)
        return cntl

    def call_sync(self, service_name: str, method_name: str, request: Any = b"",
                  cntl: Optional[Controller] = None, **kw) -> Controller:
        cntl = self.call(service_name, method_name, request, cntl=cntl,
                         _lazy_deadline=True, **kw)
        budget = None if cntl.timeout_ms is None else cntl.timeout_ms / 1e3 + 5.0
        cntl.join(budget)
        return cntl

    async def call_async(self, service_name: str, method_name: str,
                         request: Any = b"", cntl: Optional[Controller] = None,
                         **kw) -> Controller:
        cntl = self.call(service_name, method_name, request, cntl=cntl, **kw)
        budget = None if cntl.timeout_ms is None else cntl.timeout_ms / 1e3 + 5.0
        await cntl.join_async(budget)
        return cntl

    # ------------------------------------------------------------ internals
    def _framer(self):
        """Wire framing per ChannelOptions.protocol: tpu_std (default) or
        a frame-capable variant (hulu_pbrpc/sofa_pbrpc). Resolved once —
        the protocol is fixed for the channel's lifetime and this sits on
        the per-issue hot path."""
        framer = self._framer_cache
        if framer is not None:
            return framer
        if self.options.protocol in ("", "tpu_std"):
            framer = pack_message
        else:
            from brpc_tpu.protocol.registry import find_protocol
            proto = find_protocol(self.options.protocol)
            framer = getattr(proto, "frame", None)
            if framer is None:
                raise ValueError(
                    f"protocol {self.options.protocol!r} cannot frame "
                    f"Channel requests (use RedisClient/GrpcChannel/... "
                    f"for it)")
        self._framer_cache = framer
        return framer

    def _pick_socket(self, cntl: Controller) -> Socket:
        """Server/connection selection for one (re)issue; cluster channels
        override this with LB selection (controller.cpp:1048-1135).
        connection_type (socket.h GetPooledSocket/GetShortSocket):
          single — one multiplexed connection (default)
          pooled — exclusive connection per in-flight call, returned to
                   the pool on completion (protocols that can't
                   interleave, or parallelism past one conn's pipeline)
          short  — fresh connection per call, closed on completion"""
        ctype = self.options.connection_type
        if ctype in ("", "single"):
            return self._get_socket()
        if ctype == "pooled":
            sock = None
            while sock is None:
                with self._pool_lock:
                    self._pool_closed = False   # channel in use again
                    cand = self._conn_pool.pop() if self._conn_pool \
                        else None
                if cand is None:
                    break
                # probe OUTSIDE _pool_lock: a dead peer turns the probe
                # into set_failed, whose on_failed callbacks run inline
                # and may re-enter the channel (callback-under-lock)
                if not cand.failed and not cand.probe_unobserved():
                    sock = cand
            if sock is None:
                sock = create_client_socket(
                    self._endpoint, on_input=self._messenger.on_new_messages,
                    control=self._control)
                sock.fast_drain = client_fast_drain_hook(self.options)
                self._label_socket(sock, self._endpoint)

            def _return(c, s=sock):
                if s.failed:
                    return
                with self._pool_lock:
                    if not self._pool_closed:
                        self._conn_pool.append(s)
                        return
                # a call completing after close() must not re-populate the
                # emptied pool — nothing would ever close that socket again
                s.set_failed(ConnectionError("channel closed"))

            cntl._add_complete_hook(_return)
            return sock
        if ctype == "short":
            sock = create_client_socket(
                self._endpoint, on_input=self._messenger.on_new_messages,
                control=self._control)
            sock.fast_drain = client_fast_drain_hook(self.options)
            self._label_socket(sock, self._endpoint)
            cntl._add_complete_hook(
                lambda c, s=sock: s.failed or s.set_failed(
                    ConnectionError("short connection done")))
            return sock
        raise ValueError(f"unknown connection_type {ctype!r}")

    def _issue_rpc(self, cntl: Controller) -> None:
        """Pick socket, pack, enqueue (Controller::IssueRPC,
        controller.cpp:1010)."""
        # a retry may take a different framing branch than the first
        # attempt: the native-pluck hint is per-issue state, and the
        # new attempt gets a fresh failure-verdict latch. _issue_seq
        # names THIS attempt — failure paths capture it so a verdict
        # arriving after a re-issue (stale write callback, inflight
        # failer fiber that lost the race) is recognizably stale and
        # no-ops instead of judging the live attempt (the correlation
        # id alone cannot tell attempts apart: transport retries keep
        # it).
        d = cntl.__dict__
        d["_issue_seq"] = d.get("_issue_seq", 0) + 1
        d.pop("_pluck_fast", None)
        d.pop("_fail_handled", None)
        # a previous attempt's unconsumed pre-claim must not wedge its
        # socket (reads paused, claim never handed to a plucker); the
        # sync-fast hint is first-issue-only — a retry's joiner may
        # already be plucking another socket
        pre = d.pop("_pluck_preclaimed", None)
        if pre is not None:
            pre.pluck_release()
        sync_fast = d.pop("_sync_fast", False)
        try:
            sock = self._pick_socket(cntl)
        except (ConnectionError, OSError, ValueError) as e:
            # a selection failure with its own errno (naming-empty)
            # fails fast under that code; plain connect/pick failures
            # stay EFAILEDSOCKET (retry-elsewhere)
            self._maybe_retry(cntl, getattr(e, "berrno",
                                            berr.EFAILEDSOCKET), str(e))
            return
        if self._adm_cache and self._doomed_by_threshold(cntl, sock):
            # the chosen backend's piggybacked admission threshold
            # sits above this call's level: the send is DOOMED at THIS
            # backend — fail the attempt here, before the attempt
            # record, the span and the socket write (DAGOR: overload
            # stops burning sockets at the source), and hand it to the
            # retry machinery like the server's own shed would arrive:
            # a cluster pick already sits on tried_servers, so the
            # retry goes ELSEWHERE (one stalled node must not doom a
            # call the healthy survivor would serve), while a cluster
            # whose every backend is doomed fails in microseconds once
            # the pick exclusions exhaust. EPRIORITYSHED is a reject —
            # no token drain, no LALB penalty, no breaker darkening —
            # and probe-through keeps one send per interval flowing so
            # a relaxing threshold is observed.
            nclient_priority_shed.add(1)
            # a local shed never left the building: it is a re-pick,
            # not load on the cluster — wire-attempt accounting
            # (outage amplification) subtracts these
            d["_adm_local_sheds"] = d.get("_adm_local_sheds", 0) + 1
            self._maybe_retry(cntl, berr.EPRIORITYSHED,
                              "below piggybacked admission threshold "
                              f"at {sock.remote_endpoint} (shed "
                              "client-side)",
                              failed_ep=sock.remote_endpoint)
            return
        cntl.remote_side = sock.remote_endpoint
        cntl.local_side = sock.local_endpoint
        cntl._set_issue_socket(sock)  # sync-pluck lane (Controller.join)
        # first-issue sync call: claim the lane pre-send so the
        # dispatcher can never win the race to the response, whatever
        # the frame (small, large, with a device batch). A socket left
        # sticky-paused by the previous call's settle is claimed for
        # free, where a write without the claim would first re-arm
        # reads (Socket._submit) for join() to pause them again
        if sync_fast and sock.pluck_preclaim():
            d["_pluck_preclaimed"] = sock
        att = cntl.__dict__.get("request_attachment")
        # per-backend telemetry: this attempt is now issued AT a
        # concrete backend — open its stat-cell record (closed by
        # _on_attempt_failed or the completion sweep) and, under rpcz,
        # a per-attempt child span so retry/backup fan-out is visible
        # in the trace tree (submitted only for multi-attempt calls)
        if _bs.enabled():
            self._bs_attempt_begin(cntl, sock, att)
        span = d.get("_client_span")
        if span is not None:
            self._add_attempt_span(cntl, span, sock, d["_issue_seq"])
        # small-call fast path: the default protocol with none of the
        # optional sections (compress/trace/stream/device arrays) frames
        # from a cached meta prefix into ONE bytes object and sends it
        # straight from this context — no pb object, no IOBuf
        if (self._framer_cache is pack_message or
                (self._framer_cache is None
                 and self.options.protocol in ("", "tpu_std"))) \
                and not cntl.compress_type and not cntl.trace_id \
                and cntl.stream is None \
                and not cntl.__dict__.get("request_device_arrays") \
                and cntl.log_id == 0:
            key = (cntl._service_name, cntl._method_name, cntl.timeout_ms,
                   cntl.auth_token, cntl.request_priority)
            prefix = self._meta_prefix_cache.get(key)
            if prefix is None:
                m = pb.RpcMeta()
                m.request.service_name = cntl._service_name
                m.request.method_name = cntl._method_name
                if cntl.timeout_ms is not None:
                    m.request.timeout_ms = int(cntl.timeout_ms)
                if cntl.auth_token:
                    m.request.auth_token = cntl.auth_token
                if cntl.request_priority:
                    # part of the CONSTANT request submessage, so it
                    # rides the cached prefix (key carries it above)
                    m.request.priority = cntl.request_priority
                prefix = m.SerializeToString()
                if len(self._meta_prefix_cache) < 4096:
                    self._meta_prefix_cache[key] = prefix
            att_size = att.size if att else 0
            if len(cntl._request_bytes) + att_size <= SMALL_FRAME_MAX:
                # one-allocation C pack, single bytes frame
                wire = pack_small_frame(prefix, cntl.correlation_id,
                                        cntl._request_bytes,
                                        att.to_bytes() if att else b"")
                # a sync joiner may run the native pluck loop for this
                # call (Socket.pluck_until fast lane): the expected
                # response is a small tpu_std frame
                cntl.__dict__["_pluck_fast"] = (_TPU_MAGIC, SMALL_FRAME_MAX)
            else:
                # large attachment: same cached-prefix meta (no pb build
                # per call), header+meta in one native allocation
                # (pack_frame_head — no Python varint joins), attachment
                # rides as zero-copy refs behind it
                head = pack_frame_head(prefix, cntl.correlation_id,
                                       att_size, len(cntl._request_bytes))
                wire = IOBuf()
                if cntl._request_bytes:
                    wire.append(head + cntl._request_bytes)
                else:
                    wire.append(head)
                if att_size:
                    wire.append_buf(att)
            try:
                sock.write(wire, on_done=lambda err, s=sock,
                           q=d["_issue_seq"], sp=d.get("_client_span"):
                           self._on_write_done(cntl, err, s, q, sp))
            except (BlockingIOError, ConnectionError, OSError) as e:
                self._maybe_retry(cntl, berr.EFAILEDSOCKET, str(e),
                                  failed_ep=sock.remote_endpoint)
            return
        meta = pb.RpcMeta()
        meta.request.service_name = cntl._service_name
        meta.request.method_name = cntl._method_name
        meta.request.log_id = cntl.log_id
        if cntl.timeout_ms is not None:
            meta.request.timeout_ms = int(cntl.timeout_ms)
        if cntl.auth_token:
            meta.request.auth_token = cntl.auth_token
        if cntl.request_priority:
            meta.request.priority = cntl.request_priority
        meta.correlation_id = cntl.correlation_id
        meta.compress_type = cntl.compress_type
        request_bytes = cntl._request_bytes  # already compressed in call()
        if cntl.trace_id:
            meta.trace_id = cntl.trace_id
            meta.span_id = cntl.span_id
        stream = getattr(cntl, "stream", None)
        if stream is not None:
            meta.stream_settings.stream_id = stream.id
            # plain assignment, NOT bind_socket: the stream is not
            # established yet — subscribing to this attempt's failure
            # would let a failed first attempt permanently close a
            # stream whose retried setup succeeds (failure semantics
            # attach in client_dispatch once the response arrives)
            stream.socket = sock
        use_lane = (bool(cntl.request_device_arrays)
                    and sock.conn.supports_device_lane)
        wire, lane = self._framer()(
            meta, request_bytes, attachment=_copy_buf(cntl.request_attachment),
            device_arrays=cntl.request_device_arrays, device_lane=use_lane)
        try:
            # a lane batch and its envelope enter the socket's write
            # queue as one item (the receiver matches batches to
            # envelopes FIFO); the socket's single writer sends both and
            # fires on_done after the flush, in its own context with no
            # lock held — a failed write may re-issue from there. The
            # batch's stage tracker hangs its child span off this call's
            # client span (trace inherit).
            sock.write(wire, on_done=lambda err, s=sock,
                       q=d["_issue_seq"], sp=d.get("_client_span"):
                       self._on_write_done(cntl, err, s, q, sp),
                       device_arrays=lane, span=d.get("_client_span"))
        except (BlockingIOError, ConnectionError, OSError) as e:
            # a dead conn must fail the controller (or retry), never
            # escape to the caller with the call leaked
            self._maybe_retry(cntl, berr.EFAILEDSOCKET, str(e),
                              failed_ep=sock.remote_endpoint)

    def _on_write_done(self, cntl: Controller, err: Optional[BaseException],
                       sock=None, seq: Optional[int] = None, span=None):
        if err is None:
            # stage stamp: request write completed. ``span`` was
            # captured at issue time — a parked write completing after
            # the controller was recycled onto a NEW call must stamp
            # the OLD call's span, not the new one's. First attempt
            # wins (a retry's re-send must not overwrite the issue
            # timeline); a write parked behind a blocked conn (chaos
            # delay, full kernel buffer) lands here late and shows as
            # queue_us.
            if span is not None:
                # this callback runs on the writer's thread and can be
                # delivered after the reader saw the response (even
                # after the call completed); span.stamp_first_byte has
                # then set write_done_us and this late stamp stays out.
                # Clock read first: no call, so no thread switch,
                # between the check and the store
                now_us = time.monotonic_ns() // 1000
                if not span.write_done_us and not span.first_byte_us:
                    span.write_done_us = now_us
            return
        self._maybe_retry(cntl, berr.EFAILEDSOCKET, str(err),
                          failed_ep=sock.remote_endpoint
                          if sock is not None else None,
                          expect_seq=seq)

    def _retry_policy(self):
        # resolved once: the policy is fixed at channel construction and
        # this sits on the per-failure hot path
        cached = getattr(self, "_retry_policy_cached", None)
        if cached is None:
            from brpc_tpu.rpc.retry_policy import resolve
            cached = self._retry_policy_cached = resolve(
                self.options.retry_policy)
        return cached

    def _maybe_retry(self, cntl: Controller, code: int, text: str,
                     failed_ep=None, expect_cid: Optional[int] = None,
                     expect_seq: Optional[int] = None) -> None:
        """Retry on transport failures while the call is still live
        (OnVersionedRPCReturned's error branch, controller.cpp:634);
        the retry policy decides whether this error class retries.

        One verdict per attempt: a failing socket can surface through
        TWO paths for the same call (the write's on_done error callback
        and set_failed's inflight fan-out) — the _fail_handled latch,
        check-and-set under the arbitration lock, lets exactly one of
        them act (a double verdict would re-issue the same correlation
        id twice or burn the retry budget and spuriously fail a live
        retry). ``expect_cid`` pins the CALL being judged (a recycled
        controller's new call must not be judged by a stale snapshot);
        ``expect_seq`` pins the ATTEMPT — transport retries keep the
        correlation id, so only the issue sequence can tell a verdict
        for a dead attempt from one against its live successor."""
        cid = cntl.correlation_id if expect_cid is None else expect_cid
        if self._adm_cache and code in (berr.EFAILEDSOCKET, berr.ECLOSE):
            # the CONNECTION to this backend died: whatever admission
            # threshold it piggybacked describes a process that may no
            # longer exist — a respawned backend must be approached
            # fresh, not doomed-shed against its predecessor's number
            # for up to a TTL (the fabric storm's recover tail pins
            # this). Before the latch on purpose: even a stale verdict
            # for an already re-issued attempt reports a real
            # connection death, and the drop is idempotent.
            ep = failed_ep or self._endpoint
            if ep is not None:
                epk = _bs.ep_key(ep)
                for key in [k for k in list(self._adm_cache)
                            if k[0] == epk]:
                    self._adm_cache.pop(key, None)
        if address_call(cid) is not cntl:
            return  # already completed (response/timeout won) or recycled
        # policy consult BEFORE the lock: user policy code must not run
        # while the timer thread can block on cntl._arb_lock
        allow = (cntl.current_try < cntl.max_retry
                 and self._policy_allows(cntl, code, text))
        if allow and self._budget_exhausted(cntl):
            # deadline clamp: a retry that cannot possibly complete
            # inside the remaining budget is not issued — the deadline
            # timer delivers the final verdict; this attempt's error
            # stands if it wins the take below
            allow = False
            nretry_suppressed.add(1)
        rb = self._retry_budget
        if allow and rb is not None and rb.throttled():
            # empty token bucket: the cluster is browning out and this
            # channel's retries would amplify it — the attempt's error
            # stands (gRPC retryThrottling / Tail-at-Scale discipline)
            allow = False
            nretry_throttled.add(1)
        with cntl._arb_lock:
            if address_call(cid) is not cntl:
                return
            if expect_seq is not None and \
                    cntl.__dict__.get("_issue_seq") != expect_seq:
                return  # stale verdict: the call was already re-issued
            if cntl.__dict__.get("_fail_handled"):
                return  # another failure path already judged this attempt
            cntl.__dict__["_fail_handled"] = True
            taken = False
            if allow:
                cntl.current_try += 1
            else:
                taken = take_call(cid) is cntl
        if rb is not None and code not in _NO_DRAIN_CODES:
            # drain AFTER the latch: the same dead socket surfaces
            # through two failure paths, and only the one that won the
            # latch may spend a token (a double drain per failure would
            # halve the budget's real capacity). Overload REJECTS never
            # drain: a shed costs the server microseconds at the door
            # (DAGOR: shed early, shed cheaply) and the shedding node
            # is already protecting itself — spending retry tokens on
            # them would throttle the retries-elsewhere that keep
            # goodput flat while one node sheds. The bucket guards
            # against EXPENSIVE failures: dead conns, timeouts.
            rb.drain()
        if allow:
            # report the failed attempt before moving on (the final
            # attempt is reported by the completion hook instead)
            self._on_attempt_failed(cntl, code, text, failed_ep)
            self._launch_retry(cntl, code, text)
            return
        if taken:
            cntl.set_failed(code, text)
            cntl._complete()

    def _budget_exhausted(self, cntl: Controller) -> bool:
        dl = cntl.__dict__.get("_deadline_ns")
        return dl is not None and time.monotonic_ns() >= dl

    def _launch_retry(self, cntl: Controller, code: int, text: str) -> None:
        """Issue the next attempt — immediately (the default,
        backoff-free policy) or after the policy's exponential backoff,
        clamped so the wait cannot outlive the deadline budget. The
        delayed re-issue re-checks call liveness: a deadline completion
        during the backoff wins and the retry evaporates."""
        backoff_s = 0.0
        try:
            # current_try was already incremented for the NEW attempt:
            # the policy contract wants the 0-based index of the attempt
            # that just FAILED
            view = _PolicyView(cntl, code, text,
                               current_try=max(0, cntl.current_try - 1))
            backoff_s = float(
                self._retry_policy().retry_backoff_s(view) or 0.0)
        except Exception:
            backoff_s = 0.0   # a broken policy must not kill the retry
        if backoff_s > 0.0:
            dl = cntl.__dict__.get("_deadline_ns")
            if dl is not None:
                backoff_s = min(backoff_s, max(
                    0.0, (dl - time.monotonic_ns()) / 1e9 - 1e-3))
        if backoff_s <= 0.0 and not cntl.__dict__.get("_retry_reentry"):
            self._reissue_guarded(cntl)
            return
        # deferred re-issue — two reasons share it: a backoff wait, or
        # a synchronously-failing endpoint (dead connect) that would
        # otherwise recurse issue->fail->retry->issue on this stack
        # until it overflows. The timer callback only SPAWNS: _issue_rpc
        # can block in connect() for seconds, and the process-wide timer
        # thread must keep firing deadlines/backups for every other call
        # (the chaos lane's no-hangs invariant depends on it).
        cid = cntl.correlation_id

        def _fire():
            if address_call(cid) is cntl:
                self._control.spawn(
                    (lambda: address_call(cid) is cntl
                     and self._reissue_guarded(cntl)),
                    name="retry_reissue")

        global_timer().schedule_after(max(0.0, backoff_s), _fire)

    def _reissue_guarded(self, cntl: Controller) -> None:
        """_issue_rpc with the reentry latch held: a failure inside it
        that retries again is recognized by _launch_retry and deferred
        to the timer instead of growing the stack."""
        d = cntl.__dict__
        d["_retry_reentry"] = True
        try:
            self._issue_rpc(cntl)
        finally:
            d.pop("_retry_reentry", None)

    def _policy_allows(self, cntl: Controller, code: int, text: str) -> bool:
        """Consult the retry policy with the failure visible through a
        READ-ONLY view (retry_policy.h's DoRetry contract takes a const
        Controller*): the real controller is never mutated, so this can
        run without cntl._arb_lock — mutating error_code in place here
        raced a concurrent timeout completion and could restore a
        completed call's error state to OK (a silent false success)."""
        view = _PolicyView(cntl, code, text)
        try:
            return bool(self._retry_policy().do_retry(view))
        except Exception:
            return False  # a broken policy must not loop retries

    def _retry_taken_call(self, cntl: Controller, code: int, text: str,
                          failed_ep=None, allow: Optional[bool] = None) -> bool:
        """Server-returned error on a call the caller has already WON
        via take_call: if policy + budget allow, re-register the
        controller under a FRESH correlation id (the analog of the
        reference's versioned-id bump — stale responses to the old id
        simply find no call) and re-issue. Returns True when the retry
        was launched; False means the caller completes the controller.

        Must be called with cntl._arb_lock held by the caller along
        with its take_call, so the deadline timer can't interleave: a
        timer firing during the id swap blocks on the lock, then finds
        the NEW id and completes the call with ERPCTIMEDOUT. Pass the
        policy verdict via ``allow`` (computed BEFORE the lock) so user
        policy code never runs on the timer thread's critical path."""
        rb = self._retry_budget
        if rb is not None and not _bs.is_reject(code, True):
            # a server-returned error IS a failed attempt — except the
            # reject class, which is a µs-cheap shed (see _maybe_retry).
            # This path only runs for RESPONDED errors, so ERPCTIMEDOUT
            # here is the server's own deadline shed: a reject too.
            rb.drain()
        if allow is None:
            allow = self._policy_allows(cntl, code, text)
        if cntl.current_try >= cntl.max_retry or not allow:
            return False
        if self._budget_exhausted(cntl):
            # same clamp as _maybe_retry: no budget, no new attempt
            nretry_suppressed.add(1)
            return False
        if rb is not None and rb.throttled():
            nretry_throttled.add(1)
            return False
        cntl.current_try += 1
        self._on_attempt_failed(cntl, code, text, failed_ep)
        cntl._register_call()
        return True

    # --------------------------------------- admission-threshold cache
    def _track_admission_threshold(self, ep, service: str,
                                   threshold: int) -> None:
        """Response-path hook: a server piggybacked its current DAGOR
        admission threshold (or, threshold 0, stopped — absent field /
        fast-lane response), cache it per (backend, service). Called
        only while a threshold rides the wire or the cache is non-empty
        — the calm hot path never lands here."""
        key = (_bs.ep_key(ep), service)
        now = time.monotonic()
        if threshold:
            ent = self._adm_cache.get(key)
            if ent is None:
                self._adm_cache[key] = [threshold, now, now]
            else:
                ent[0] = threshold
                ent[1] = now
        else:
            self._adm_cache.pop(key, None)
        if now - self._adm_sweep > ADM_THRESHOLD_TTL_S:
            # lazy sweep (at most once per TTL): an entry for a
            # (backend, service) the app stopped calling would
            # otherwise keep the cache truthy forever — every
            # issue/response of the whole channel paying the admission
            # lookups for a pair nobody uses
            self._adm_sweep = now
            for k in [k for k, e in list(self._adm_cache.items())
                      if now - e[1] > ADM_THRESHOLD_TTL_S]:
                self._adm_cache.pop(k, None)

    def _client_user_slot(self, cntl: Controller, sock) -> int:
        """This call's user sub-priority as the SERVER will compute it:
        the auth cookie when one rides the request, else the hash of
        the connection's client address — our socket's local endpoint
        IS the server's remote_endpoint, and the shared
        admission.cached_socket_slot keeps both sides' hash in
        lockstep."""
        from brpc_tpu.rpc.admission import cached_socket_slot, user_slot
        if cntl.auth_token:
            return user_slot(cntl.auth_token)
        return cached_socket_slot(sock, sock.local_endpoint)

    def _doomed_by_threshold(self, cntl: Controller, sock) -> bool:
        """True = this send's admission level sits below the backend's
        cached threshold and the probe window hasn't come around: fail
        it locally. Stale entries (TTL) expire here so a restarted or
        recovered backend is re-probed by the first send."""
        key = (_bs.ep_key(sock.remote_endpoint), cntl._service_name)
        ent = self._adm_cache.get(key)
        if ent is None:
            return False
        now = time.monotonic()
        if now - ent[1] > ADM_THRESHOLD_TTL_S:
            self._adm_cache.pop(key, None)
            return False
        from brpc_tpu.rpc.admission import compose_level
        level = compose_level(cntl.request_priority,
                              self._client_user_slot(cntl, sock))
        if level >= ent[0]:
            return False
        if now - ent[2] >= ADM_PROBE_INTERVAL_S:
            # probe-through: one doomed send per interval goes to the
            # wire anyway, so a relaxing threshold reaches this cache
            # (its response either carries a lower threshold or, calm,
            # clears the entry)
            ent[2] = now
            return False
        return True

    # ------------------------------------------- per-backend telemetry
    def _bs_cell(self, ep) -> tuple:
        """(backend_key, cell) for an endpoint, cached per channel —
        the hot path must not pay a registry lookup per attempt."""
        cells = self.__dict__.get("_bs_cells")
        if cells is None:
            cells = {}
            self.__dict__["_bs_cells"] = cells
        entry = cells.get(ep)
        if entry is None:
            key = _bs.ep_key(ep)
            entry = (key, _bs.global_stats().cell(self._stats_name, key))
            cells[ep] = entry
        return entry

    def _bs_attempt_begin(self, cntl: Controller, sock, att) -> None:
        key, cell = self._bs_cell(sock.remote_endpoint)
        cell.on_start(len(cntl._request_bytes) + (att.size if att else 0))
        _bs.attempt_start(cntl, [key, time.monotonic_ns(), cell],
                          self._bs_on_complete)

    def _bs_on_complete(self, cntl: Controller) -> None:
        _bs.call_complete(cntl)

    def _add_attempt_span(self, cntl: Controller, parent, sock,
                          seq: int) -> None:
        from brpc_tpu.rpc.span import start_attempt_span
        sp = start_attempt_span(parent, cntl._service_name,
                                cntl._method_name, seq,
                                self._bs_cell(sock.remote_endpoint)[0],
                                backup=cntl.used_backup)
        if cntl.used_backup:
            dec = cntl.__dict__.get("_hedge_decision")
            if dec is not None:
                # greppable arming evidence: remaining deadline budget
                # vs the p50 bar at decision time (the fabric storm's
                # "no hedge past budget" assert reads these)
                r, p = dec
                sp.annotate(
                    "hedge_armed remaining_ms=%s p50_ms=%s"
                    % ("inf" if r is None else round(r, 2),
                       "na" if p is None else round(p, 2)))
        with cntl._arb_lock:
            cntl.__dict__.setdefault("_attempt_spans", []).append(sp)

    def _close_attempt_span(self, cntl: Controller, code: int,
                            key: Optional[str] = None) -> None:
        """Stamp the failing attempt's span with its verdict — matched
        by backend key when the failure path knows it (with a
        concurrent backup, the newest open span belongs to a DIFFERENT,
        healthy backend and must not inherit this error); newest-open
        is the fallback when the endpoint is unknown."""
        spans = cntl.__dict__.get("_attempt_spans")
        if not spans:
            return
        now = time.monotonic_ns() // 1000
        with cntl._arb_lock:
            victim = None
            for sp in reversed(spans):
                if sp.end_us:
                    continue
                if victim is None:
                    victim = sp
                if key is not None and sp.remote_side == key:
                    victim = sp
                    break
            if victim is not None:
                victim.end_us = now
                victim.error_code = code

    def _on_attempt_failed(self, cntl: Controller, code: int, text: str,
                           failed_ep=None) -> None:
        """Per-attempt failure hook (LB feedback + circuit breaker ride
        the ClusterChannel override; per-backend stat cells and attempt
        spans settle here for every channel flavor). ``failed_ep``
        names the attempt's endpoint when the failure path knows it —
        with a concurrent backup selection, tried_servers[-1] may
        already be a DIFFERENT server."""
        ep = failed_ep or self._endpoint
        if _bs.enabled():
            _bs.attempt_error(self._stats_name, cntl, code, ep)
        self._close_attempt_span(cntl, code,
                                 _bs.ep_key(ep) if ep is not None else None)

    def _on_timeout(self, cntl: Controller) -> None:
        # under the arbitration lock: a response-error retry swapping
        # the correlation id must not interleave with this take — the
        # timer blocked here resumes against the NEW id and still ends
        # the call (the deadline is final across retries)
        with cntl._arb_lock:
            taken = take_call(cntl.correlation_id) is cntl
        if taken:
            cntl.set_failed(berr.ERPCTIMEDOUT,
                            f"deadline {cntl.timeout_ms}ms exceeded")
            cntl._complete()

    def _hedge_p50_ms(self) -> Optional[float]:
        """The fastest backend's recent p50 (ms) among this channel's
        stat cells — the hedge arming bar: when even the quickest
        backend's median cannot fit inside the remaining budget, the
        hedge is pure load on a cluster that is already slow. None =
        no telemetry yet (stats disabled / no completed calls);
        hedging then falls back to deadline-only gating."""
        cells = self.__dict__.get("_bs_cells")
        if not cells:
            return None
        best = None
        for _key, cell in cells.values():
            p = cell.recent_p50_us()
            if p > 0.0 and (best is None or p < best):
                best = p
        return None if best is None else best / 1e3

    def _on_backup_timer(self, cntl: Controller) -> None:
        """Send a duplicate request; first response wins
        (backup_request_ms, controller.cpp:331). Budget-aware arming
        (The Tail at Scale: hedged requests must never amplify
        overload): the hedge is suppressed when the retry token bucket
        is dry, and never armed when the remaining deadline sits under
        the fastest backend's recent p50 — a hedge that cannot finish
        in time is a guaranteed-wasted request. On first win the loser
        is cancelled client-side: its pending timers unschedule at
        completion, its LB selection and stat-cell record are swept as
        abandoned, and its attempt span closes with the verdict."""
        if address_call(cntl.correlation_id) is not cntl:
            return
        if self._budget_exhausted(cntl):
            # a backup issued at/after the deadline cannot win: the
            # timeout completion is already due (or racing this timer)
            nretry_suppressed.add(1)
            return
        rb = self._retry_budget
        if rb is not None and rb.throttled():
            # hedges amplify load exactly like retries: same bucket
            nretry_throttled.add(1)
            return
        dl = cntl.__dict__.get("_deadline_ns")
        remaining_ms = None if dl is None \
            else (dl - time.monotonic_ns()) / 1e6
        p50_ms = self._hedge_p50_ms()
        if remaining_ms is not None and p50_ms is not None \
                and remaining_ms < p50_ms:
            nhedge_suppressed.add(1)
            return
        cntl.used_backup = True
        # the arming evidence rides the attempt span (fabric storm
        # asserts no hedge was ever armed past budget from /rpcz)
        cntl.__dict__["_hedge_decision"] = (remaining_ms, p50_ms)
        self._issue_rpc(cntl)


def _settle_attempt_spans(cntl) -> None:
    """Settle the per-attempt child spans after the main client span
    finished: stragglers (the final attempt; a backup that lost the
    race) close with the call's verdict, and the set is submitted ONLY
    when the call used more than one attempt — a single-attempt call
    keeps exactly one client span, a retried/hedged call shows its
    fan-out in /rpcz and tools/trace.py critical paths."""
    from brpc_tpu.rpc.span import submit_span
    spans = cntl.__dict__.pop("_attempt_spans", None)
    if not spans:
        return
    now = time.monotonic_ns() // 1000
    for sp in spans:
        if not sp.end_us:
            sp.end_us = now
            sp.error_code = cntl.error_code
    if len(spans) > 1:
        for sp in spans:
            submit_span(sp)


class _PolicyView:
    """Read-only controller facade handed to RetryPolicy.do_retry /
    retry_backoff_s: the attempt's error is visible, every other
    attribute proxies to the real controller, and writes are rejected —
    so policies cannot race the completion paths. ``current_try`` may
    be pinned by the caller (the backoff path runs after the increment
    for the new attempt, but the contract exposes the index of the
    attempt that just failed)."""

    __slots__ = ("_cntl", "error_code", "error_text", "current_try")

    def __init__(self, cntl, code: int, text: str,
                 current_try: Optional[int] = None):
        object.__setattr__(self, "_cntl", cntl)
        object.__setattr__(self, "error_code", code)
        object.__setattr__(self, "error_text", text)
        object.__setattr__(self, "current_try",
                           cntl.current_try if current_try is None
                           else current_try)

    def failed(self) -> bool:
        return self.error_code != 0

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_cntl"), name)

    def __setattr__(self, name, value):
        raise AttributeError("retry policies see a read-only controller")


def _copy_buf(buf: IOBuf) -> IOBuf:
    out = IOBuf()
    out.append_buf(buf)
    return out
