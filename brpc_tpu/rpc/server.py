"""Server: service registry + acceptor + graceful stop
(brpc/server.{h,cpp}: StartInternal :750, Stop/Join :691).

start() listens on any registered transport scheme; accepted conns become
Sockets whose input callback is the shared InputMessenger. The server
rides along in socket.user_data so protocol dispatch finds it
(the reference reaches the server through the Socket's acceptor back-ref).
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional

from brpc_tpu.butil.endpoint import EndPoint, str2endpoint
from brpc_tpu.butil.flags import define_flag, flag
from brpc_tpu.bvar.latency_recorder import LatencyRecorder
from brpc_tpu.fiber import TaskControl, global_control
from brpc_tpu.rpc.service import Method, Service
from brpc_tpu.transport import syscall_stats as _syscall_stats
from brpc_tpu.transport.base import get_transport
from brpc_tpu.transport.input_messenger import InputMessenger
from brpc_tpu.transport.socket import Socket

define_flag("server_queue_shed_ms", 200.0,
            "queue-delay shed budget: a request whose arrival-to-"
            "dispatch time exceeds this is rejected with ELIMIT before "
            "the handler runs (default gate for adaptive-limiter "
            "servers; ServerOptions.queue_delay_shed_ms overrides "
            "per server)", validator=lambda v: v > 0)

_nlimit_shed = None   # lazily bound server_dispatch.nlimit_shed (the
#                       Adder lives with the other dispatch counters;
#                       importing it at module top would be a cycle for
#                       nothing — the reject path is cold)


def _count_limit_shed() -> None:
    global _nlimit_shed
    v = _nlimit_shed
    if v is None:
        from brpc_tpu.rpc.server_dispatch import nlimit_shed
        v = _nlimit_shed = nlimit_shed
    v.add(1)


# the last-started server, weakly held: the process-wide
# server_concurrency_limit/_inflight gauges read through it (multiple
# servers in one process: the newest wins, like the other server vars)
_limiter_var_server = None


def _expose_limiter_vars(server) -> None:
    global _limiter_var_server
    _limiter_var_server = weakref.ref(server)
    from brpc_tpu.bvar.reducer import PassiveStatus

    def _read(attr_fn, default=0):
        ref = _limiter_var_server
        s = ref() if ref is not None else None
        if s is None:
            return default
        return attr_fn(s)

    PassiveStatus(lambda: _read(lambda s: s.concurrency_limit() or 0)) \
        .expose("server_concurrency_limit")
    PassiveStatus(lambda: _read(lambda s: s.concurrency)) \
        .expose("server_concurrency_inflight")
    # the DAGOR admission threshold: 0 while calm; merged shard views
    # take the max (the shard_group "threshold" scalar rule)
    PassiveStatus(lambda: _read(
        lambda s: s._admission.wire_threshold()
        if s._admission is not None else 0)) \
        .expose("server_admission_threshold")


# process-wide graceful-SIGTERM state: weak so stopped/forgotten servers
# don't linger, installed once so restart cycles don't chain handlers
_sigterm_registry: "weakref.WeakSet" = weakref.WeakSet()
_sigterm_lock = threading.Lock()
_sigterm_installed = False


def _install_sigterm_handler_once() -> None:
    global _sigterm_installed
    with _sigterm_lock:
        if _sigterm_installed:
            return
        import signal
        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            for srv in list(_sigterm_registry):
                try:
                    srv.stop()
                except Exception:
                    pass
            if callable(prev):
                prev(signum, frame)
            elif prev == signal.SIG_DFL:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                signal.raise_signal(signal.SIGTERM)

        try:
            signal.signal(signal.SIGTERM, _on_term)
            _sigterm_installed = True
        except ValueError:
            pass  # not the main thread: best-effort


class ServerOptions:
    def __init__(self, num_workers: Optional[int] = None,
                 max_concurrency=None,
                 method_max_concurrency: Optional[Dict[str, object]] = None,
                 queue_delay_shed_ms: Optional[float] = None,
                 request_costs=None,
                 priority_admission: Optional[bool] = None,
                 auth_token: Optional[str] = None,
                 auth=None, interceptor=None,
                 enable_builtin_services: bool = True,
                 redis_service=None, thrift_service=None,
                 nshead_service=None, esp_service=None,
                 mongo_service_adaptor=None, rtmp_service=None,
                 session_local_data_factory=None,
                 session_local_data_reset=None,
                 usercode_in_pthread: bool = False,
                 health_reporter=None):
        self.num_workers = num_workers
        # server-wide in-flight cap: an int, or an adaptive spec string
        # ('auto[:initial[:min[:max]]]' | 'constant:N' | 'timeout:MS' —
        # the reference's -max_concurrency vocabulary); backed by a
        # ConcurrencyLimiter driven from both dispatch paths
        self.max_concurrency = max_concurrency
        # per-method caps: {"Service.Method": spec} — consulted after
        # the server-wide limiter (rpc/concurrency_limiter.py)
        self.method_max_concurrency = method_max_concurrency
        # queue-delay shed gate (DAGOR-style overload control): requests
        # whose arrival-to-dispatch time exceeds this budget are shed
        # with ELIMIT before the handler runs. None = default ON (from
        # the server_queue_shed_ms flag) when max_concurrency is an
        # adaptive spec, OFF otherwise; a number forces it on.
        self.queue_delay_shed_ms = queue_delay_shed_ms
        # cost-weighted limiter slots (rpc/admission.CostModel): True
        # charges each request a weight from its size + its method's
        # expected-latency bucket, so a 4MB streaming call draws more
        # of the concurrency limit than a 4B echo. None/False = every
        # request costs exactly one slot (the PR 10 behavior).
        self.request_costs = request_costs
        # DAGOR two-level priority admission (rpc/admission.py): when
        # the limiter or queue-delay gate reports overload, requests
        # below the adaptive (business, user) threshold are shed with
        # EPRIORITYSHED before parse/handler, and the threshold rides
        # every response back to senders. None = default ON whenever
        # any overload organ is configured (a limiter or the queue
        # gate) — inert until overload AND inert on uniform-priority
        # traffic (the top-class clamp); False forces it off.
        self.priority_admission = priority_admission
        self.auth_token = auth_token
        # pluggable Authenticator (rpc/auth.py; brpc/authenticator.h) —
        # wins over auth_token, which is sugar for TokenAuthenticator
        self.auth = auth
        # Interceptor (brpc/interceptor.h): callable(cntl) -> None accepts,
        # (error_code, reason) or raise InterceptorError rejects
        self.interceptor = interceptor
        self.enable_builtin_services = enable_builtin_services
        # server-side redis command table (ServerOptions::redis_service in
        # the reference, brpc/redis.h:240)
        self.redis_service = redis_service
        # native thrift method table (brpc/thrift_service.h)
        self.thrift_service = thrift_service
        # legacy family adaptors (nshead_service.h, esp_message.h,
        # mongo_service_adaptor.h)
        self.nshead_service = nshead_service
        self.esp_service = esp_service
        self.mongo_service_adaptor = mongo_service_adaptor
        # live publish/play relay registry (rtmp.h RtmpService)
        self.rtmp_service = rtmp_service
        # per-request reusable objects (ServerOptions.
        # session_local_data_factory, simple_data_pool.h)
        self.session_local_data_factory = session_local_data_factory
        self.session_local_data_reset = session_local_data_reset
        # run blocking sync handlers on a reserve pthread pool
        # (usercode_in_pthread + usercode_backup_pool in the reference)
        self.usercode_in_pthread = usercode_in_pthread
        # custom /health responder (brpc/health_reporter.h): callable
        # (server) -> bytes|str|(status:int, content_type:str, body) —
        # lets apps gate readiness on their own state
        self.health_reporter = health_reporter


class Server:
    def __init__(self, options: Optional[ServerOptions] = None,
                 control: Optional[TaskControl] = None):
        self.options = options or ServerOptions()
        self._control = control or global_control()
        self._messenger = InputMessenger(control=self._control)
        if self.options.session_local_data_factory is not None:
            from brpc_tpu.rpc.data_pool import SimpleDataPool
            self.session_local_pool = SimpleDataPool(
                self.options.session_local_data_factory,
                reset=self.options.session_local_data_reset)
        else:
            self.session_local_pool = None
        self._services: Dict[str, Service] = {}
        self._build_limiters()
        self._listener = None
        self._endpoint: Optional[EndPoint] = None
        self._conns: List[Socket] = []
        self._conns_lock = threading.Lock()
        self._running = False
        self._stopped_event = threading.Event()
        self.method_status: Dict[str, LatencyRecorder] = {}
        self._native_echo = None        # (svc_bytes, mth_bytes, key)
        self._fast_drain_hook = None    # lazy; False = unavailable
        self.concurrency = 0            # in-flight requests
        self._concurrency_lock = threading.Lock()
        self.nprocessed = 0
        self.nerror = 0
        self._shard_group = None        # supervisor handle (num_shards>1)
        self.shard_index = None         # set in shard workers
        self._serving = None            # GenerateService handle (serving/)

    def _build_limiters(self) -> None:
        """Resolve the concurrency-limiter specs (construction and
        postfork re-arm share this: a forked shard must not inherit the
        parent limiter's inflight count or lock)."""
        from brpc_tpu.rpc.concurrency_limiter import new_limiter
        o = self.options
        self._limiter = new_limiter(o.max_concurrency)
        self._method_limiters = {
            k: new_limiter(v)
            for k, v in (o.method_max_concurrency or {}).items()}
        qd = o.queue_delay_shed_ms
        if qd is None and isinstance(o.max_concurrency, str):
            # adaptive servers get the queue-delay gate by default: a
            # saturated node must reject in microseconds, not let queued
            # work time out in seconds (The Tail at Scale / DAGOR)
            qd = flag("server_queue_shed_ms")
        self._queue_shed_ns = int(qd * 1e6) if qd else 0
        # DAGOR priority admission + weighted request costs (ISSUE 14).
        # Rebuilt here so a forked shard gets fresh window/threshold
        # state, like the limiters above. Admission defaults ON where
        # an overload organ exists to signal it (any limiter, or the
        # queue gate) — it stays inert until overload AND never sheds
        # uniform-priority traffic (the top-class clamp), so servers
        # without priority-tagged callers keep exact PR 10 behavior.
        from brpc_tpu.rpc.admission import (AdmissionController,
                                            CostModel, admission_enabled)
        want_adm = o.priority_admission
        if want_adm is None:
            want_adm = (self._limiter is not None
                        or bool(self._method_limiters)
                        or self._queue_shed_ns > 0)
        self._admission = AdmissionController() \
            if (want_adm and admission_enabled()) else None
        self._cost_model = CostModel(self) if o.request_costs else None

    def concurrency_limit(self) -> Optional[int]:
        """The server-wide limiter's current limit (None = unlimited) —
        the /status saturation pane's ``concurrency_limit``."""
        lim = self._limiter
        return lim.max_concurrency if lim is not None else None

    # ------------------------------------------------------------ services
    def add_service(self, service: Service) -> None:
        if self._running:
            raise RuntimeError("add_service after start")
        if service.name in self._services:
            raise ValueError(f"service {service.name!r} already added")
        self._services[service.name] = service
        for m in service.methods.values():
            # precomputed /status key: an f-string per request adds up
            m.full_name = f"{service.name}.{m.name}"
            if m.native_kind == "echo" and self._native_echo is None:
                # ONE native echo target per server (the C serving loop
                # matches a single (service, method) pair); additional
                # echo-marked methods serve through the normal paths
                self._native_echo = (service.name.encode(),
                                     m.name.encode(), m.full_name)

    def find_method(self, service_name: str, method_name: str) -> Optional[Method]:
        svc = self._services.get(service_name)
        if svc is None:
            return None
        return svc.methods.get(method_name)

    def services(self) -> Dict[str, Service]:
        return dict(self._services)

    # ----------------------------------------------------------- lifecycle
    def start(self, address: str | EndPoint,
              num_shards: Optional[int] = None,
              shard_options=None) -> EndPoint:
        """Listen and serve; returns the bound endpoint (with the real
        port for tcp://host:0).

        ``num_shards=N`` (N>1, tcp only) turns this call into
        shard-group serving: N worker processes each bind the same
        port with SO_REUSEPORT and run a fully private stack — the
        GIL-parallel escape hatch mapping the reference's -reuse_port
        (see rpc/shard_group.py). This process becomes the SUPERVISOR:
        it serves no traffic itself; stop()/join() drain the group."""
        if self._running:
            raise RuntimeError("server already started")
        if num_shards is not None and num_shards > 1:
            import copy
            from brpc_tpu.rpc.shard_group import (ShardGroup,
                                                  ShardGroupOptions)
            # copy before overriding num_shards: the caller may reuse
            # their options object for another group
            opts = copy.copy(shard_options) if shard_options is not None \
                else ShardGroupOptions()
            opts.num_shards = num_shards
            self._shard_group = ShardGroup(self, address, opts)
            self._endpoint = self._shard_group.start()
            self._running = True
            self._stopped_event.clear()
            return self._endpoint
        ep = address if isinstance(address, EndPoint) else str2endpoint(address)
        if self.options.enable_builtin_services:
            from brpc_tpu.builtin.services import add_builtin_services
            from brpc_tpu.bvar.default_variables import (
                expose_default_variables)
            add_builtin_services(self)
            expose_default_variables()   # process_* vars (idempotent)
            # socket traffic + fast-lane counters follow the same
            # lifecycle: their import-time expose is stripped forever
            # by an unexpose_all() (test fixtures) — re-register here
            # like the process_* vars, so /vars keeps them for any
            # server started afterward in the process
            from brpc_tpu.rpc.server_dispatch import (nlimit_shed,
                                                      npriority_shed,
                                                      nshed)
            from brpc_tpu.transport.socket import (_wqueue_peak_window,
                                                   npluck_defer,
                                                   npluck_fast, nreads,
                                                   nwqueue_bytes, nwrites)
            for var, name in ((nwrites, "socket_writes"),
                              (nreads, "socket_read_bytes"),
                              (npluck_fast, "pluck_fast_responses"),
                              (npluck_defer, "pluck_defers"),
                              (nwqueue_bytes, "socket_wqueue_bytes"),
                              # the shed counters are anomaly-watchdog
                              # keys: their trend rings (and the
                              # /status saturation links) must survive
                              # an unexpose_all like every counter here
                              (nshed, "server_deadline_shed"),
                              (nlimit_shed, "server_limit_shed"),
                              (npriority_shed, "server_priority_shed")):
                var.expose(name)
            from brpc_tpu.bvar.reducer import PassiveStatus
            wq_peak = _wqueue_peak_window()
            PassiveStatus(lambda: wq_peak.get_value() or 0).expose(
                "socket_wqueue_peak_10s")
            # connection-cost census + stall-watchdog bvars follow the
            # same re-expose lifecycle as the socket counters above
            from brpc_tpu.transport.event_dispatcher import (
                expose_stall_vars)
            from brpc_tpu.transport.socket import expose_conn_census_vars
            expose_conn_census_vars()
            expose_stall_vars()
            # syscall-accounting floor (syscalls_recv/writev/accept +
            # the syscalls_per_rpc derived key) — same survival rule
            from brpc_tpu.transport.syscall_stats import (
                expose_syscall_vars)
            expose_syscall_vars()
            from brpc_tpu.rpc.usercode import expose_usercode_vars
            expose_usercode_vars()
            # the process-wide stream_* sums: same survival rule
            from brpc_tpu.rpc.stream import expose_stream_vars
            expose_stream_vars()
            # per-backend client stat cells (labeled prometheus family)
            # follow the same re-expose lifecycle
            from brpc_tpu.rpc.backend_stats import expose_backend_vars
            expose_backend_vars()
            # device-lane stat cells + the ici_* counters (lane status,
            # unpulled/leaked/reclaimed) — the unexpose_all survival
            # rule again: a restart must not drop them from /vars
            from brpc_tpu.transport.device_stats import expose_device_vars
            expose_device_vars()
            import sys as _sys
            _ici_mod = _sys.modules.get("brpc_tpu.transport.ici")
            if _ici_mod is not None:
                _ici_mod.expose_ici_vars()
            # overload-control gauges (limiter limit + inflight) for
            # prometheus and the merged shard views
            _expose_limiter_vars(self)
            # server-wide trend triple for /timeline + cluster_top's
            # spark columns: processed/errors as DECLARED delta series
            # (a monotone passive graphs as qps only when its ring
            # knows it is a counter), worst instant method p99 as a
            # max series — all following the unexpose_all re-expose
            # lifecycle like every counter above. Weakly bound like
            # _expose_limiter_vars: the registry outlives any one
            # Server, and a strong closure would pin a stopped server
            # (and its reservoirs) for the process lifetime
            from brpc_tpu.bvar.series import declare_series_kind
            wref = weakref.ref(self)

            def _trend(attr_fn, default=0):
                s = wref()
                return attr_fn(s) if s is not None else default

            def _worst_p99(srv):
                best = 0.0
                for lr in list(srv.method_status.values()):
                    try:
                        best = max(best, lr.latency_percentile(0.99))
                    except Exception:
                        pass
                return round(best, 1)
            PassiveStatus(lambda: _trend(lambda s: s.nprocessed)).expose(
                "server_processed")
            PassiveStatus(lambda: _trend(lambda s: s.nerror)).expose(
                "server_errors")
            PassiveStatus(lambda: _trend(_worst_p99, 0.0)).expose(
                "server_latency_p99_us")
            declare_series_kind("server_processed", "delta")
            declare_series_kind("server_errors", "delta")
            declare_series_kind("server_latency_p99_us", "max")
            # scheduler saturation trio (runqueue depth/peak, worker
            # busy fraction) + fiber counters: /vars + prometheus
            self._control.expose_vars()
            # best-effort: SIGUSR2 -> fiber stacks on stderr, so
            # tools/fiber_stacks.py <pid> works like the reference's
            # gdb_bthread_stack.py (no-op off the main thread)
            from brpc_tpu.fiber.stacks import enable_stack_dump_signal
            enable_stack_dump_signal()
        # serving lane: build THIS process's model replica + batcher and
        # register the engine with the fiber workers before traffic can
        # land. A shard worker reaches here post-fork with the module
        # registry freshly cleared, so each shard runs a private
        # replica — the supervisor (shard-group path above) runs none.
        if self._serving is not None:
            self._serving.on_server_start(self)
        transport = get_transport(ep.scheme)
        self._listener = transport.listen(ep, self._on_new_conn)
        self._endpoint = self._listener.endpoint
        self._running = True
        self._stopped_event.clear()
        self._maybe_install_sigterm()
        # flight recorder: continuous profiler + event-loop stall
        # watchdog ride a serving process (honors the hz flag at
        # runtime; a forked shard re-starts its own — the postfork
        # registry dropped the parent's recorder)
        from brpc_tpu.builtin.flight_recorder import global_recorder
        global_recorder().ensure_running()
        # incident time machine: re-expose the incident bvars (the PR 2
        # unexpose_all survival rule), hand the manager this server for
        # its bundler snapshots, and prime the artifact ledger so
        # artifacts surviving a restart show up immediately
        from brpc_tpu.incident.manager import (attach_incident_server,
                                               expose_incident_vars)
        expose_incident_vars()
        attach_incident_server(self)
        # trend rings + anomaly watchdog: make sure the bvar sampler's
        # tick thread runs even with no windowed reducers yet, and bind
        # the watchdog's annotation imports on THIS thread before the
        # sampler can need them (the PR 8 sampler-import rule)
        from brpc_tpu.bvar.series import ensure_series
        ensure_series()
        return self._endpoint

    def _maybe_install_sigterm(self) -> None:
        """graceful_quit_on_sigterm (server.cpp graceful Stop/Join:691):
        SIGTERM drains running servers instead of killing the process
        mid-request. One process-wide handler over a weak registry —
        start/stop cycles must not chain handlers or pin dead Servers."""
        from brpc_tpu.butil.flags import flag
        if not flag("graceful_quit_on_sigterm"):
            return
        _sigterm_registry.add(self)
        _install_sigterm_handler_once()

    @property
    def endpoint(self) -> Optional[EndPoint]:
        return self._endpoint

    def _on_new_conn(self, conn) -> None:
        sock = Socket(conn, on_input=self._messenger.on_new_messages,
                      control=self._control)
        sock.user_data["server"] = self
        if self._native_echo is not None:
            # native per-event serving (fastcore serve_drain); the hook
            # re-checks runtime gates (flags, cut-through state) per
            # pass and self-disables on non-fd transports
            fdr = self._fast_drain_hook
            if fdr is None:    # resolve once; False = unavailable
                from brpc_tpu.rpc.server_dispatch import make_fast_drain
                fdr = self._fast_drain_hook = make_fast_drain(self) or False
            if fdr is not False:
                sock.fast_drain = fdr
        with self._conns_lock:
            self._conns.append(sock)
            # opportunistic sweep of dead conns
            if len(self._conns) > 64:
                self._conns = [s for s in self._conns if not s.failed]

    def connections(self) -> List[Socket]:
        with self._conns_lock:
            return [s for s in self._conns if not s.failed]

    def stop(self) -> None:
        """Stop accepting; existing connections are closed after in-flight
        requests drain (graceful, server.cpp:691)."""
        if not self._running:
            return
        self._running = False
        _sigterm_registry.discard(self)
        if self._shard_group is not None:
            self._shard_group.stop()
            self._stopped_event.set()
            return
        if self._listener is not None:
            self._listener.stop()
        if self._serving is not None:
            # unregister the engine from the worker loops and retire
            # in-flight sequences (their clients are being drained)
            self._serving.on_server_stop(self)

    def join(self, timeout_s: float = 5.0) -> None:
        """Wait for in-flight requests, then close connections."""
        if self._shard_group is not None:
            self._shard_group.join(timeout_s)
            return
        import time
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._concurrency_lock:
                if self.concurrency == 0:
                    break
            time.sleep(0.005)
        with self._conns_lock:
            conns, self._conns = self._conns, []
        for s in conns:
            s.set_failed(ConnectionError("server stopped"))
        self._stopped_event.set()

    def run_until_asked_to_quit(self) -> None:
        """Block until SIGINT/SIGTERM, then drain and stop.

        Long-running example/tool servers get two safeguards for free
        (a chip belongs to one process at a time — an orphaned
        jax-capable process keeps it from every later client): a
        parent-death watchdog (orphaned →
        exit) and a pidfile under .pids/ so the bench preflight can
        reap leftovers. Opt out with BRPC_TPU_NO_PARENT_WATCHDOG=1
        (daemons intentionally outliving their launcher)."""
        import os
        import signal
        ev = threading.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, lambda *_: ev.set())
        pidfile = None
        watchdog = not os.environ.get("BRPC_TPU_NO_PARENT_WATCHDOG")
        from brpc_tpu.butil.pidfile import remove_pidfile, write_pidfile
        pidfile = write_pidfile(f"server-{self._endpoint}")
        parent = os.getppid()
        try:
            while not ev.is_set():
                ev.wait(1.0)
                if watchdog and os.getppid() != parent:
                    break     # orphaned: parent died without SIGTERM
        finally:
            remove_pidfile(pidfile)
        self.stop()
        self.join()

    def _postfork_child_reset(self) -> None:
        """Re-arm this Server for a forked shard worker: the template's
        services/options survive the fork as plain data, but every
        runtime organ — TaskControl, InputMessenger, listener, conns,
        per-method recorders — referenced the PARENT's (now reset)
        machinery and must be rebuilt against the child's fresh
        singletons before start() runs here."""
        self._control = global_control()
        self._messenger = InputMessenger(control=self._control)
        self._listener = None
        self._endpoint = None
        self._conns = []
        self._conns_lock = threading.Lock()
        self._concurrency_lock = threading.Lock()
        self._running = False
        self._stopped_event = threading.Event()
        self._fast_drain_hook = None
        self.method_status = {}
        self.concurrency = 0
        self.nprocessed = 0
        self.nerror = 0
        self._build_limiters()   # fresh inflight counts + locks
        self._shard_group = None
        if self.session_local_pool is not None:
            from brpc_tpu.rpc.data_pool import SimpleDataPool
            self.session_local_pool = SimpleDataPool(
                self.options.session_local_data_factory,
                reset=self.options.session_local_data_reset)

    # ----------------------------------------------------------- accounting
    def on_request_start(self, method_key: Optional[str] = None,
                         nbytes: int = 0, level: int = 0,
                         level_counted: bool = False) -> float:
        """Admission gate, both dispatch paths (classic AND the turbo
        lane) plus every protocol front-end: consult the server-wide
        limiter, then the method's (when configured). Returns the
        request's admitted COST (>= 1.0, truthy — weighted slots when
        ``ServerOptions(request_costs=True)``, else exactly 1.0) or
        0.0 (falsy) when the caller must reject with ELIMIT; the SAME
        cost must ride to on_request_end so the weighted release
        balances. ``level`` is the request's admission level — limiter
        rejects feed it to the priority-admission controller as
        overload evidence (``level_counted`` = the engaged dispatch
        path already tallied it through admit_level). Limiter locks
        are leaves — never taken under _concurrency_lock."""
        cm = self._cost_model
        cost = cm.request_cost(method_key, nbytes) if cm is not None \
            else 1.0
        lim = self._limiter
        if lim is not None and not lim.on_requested(cost):
            _count_limit_shed()
            adm = self._admission
            if adm is not None:
                adm.signal_overload(level, level_counted)
            return 0.0
        if self._method_limiters and method_key is not None:
            ml = self._method_limiters.get(method_key)
            if ml is not None and not ml.on_requested(cost):
                if lim is not None:
                    # release the server-wide slot the gate above took
                    lim.on_responded(0.0, True, cost)
                _count_limit_shed()
                adm = self._admission
                if adm is not None:
                    adm.signal_overload(level, level_counted)
                return 0.0
        with self._concurrency_lock:
            self.concurrency += 1
        return cost

    def account_native_batch(self, method_key: str, n: int,
                             total_us: float) -> None:
        """Stats for a batch the C serving loop handled (serve_scan):
        native methods never block, so they bypass the concurrency
        gate; processed counts and /status latency still land."""
        _syscall_stats.note_rpc_messages(n)
        with self._concurrency_lock:
            self.nprocessed += n
        lr = self.method_status.get(method_key)
        if lr is None:
            lr = self.method_status.setdefault(method_key, LatencyRecorder())
        lr.record_batch(total_us / n, n)

    def on_request_end(self, method_key: str, latency_us: float,
                       failed: bool, cost: float = 1.0):
        with self._concurrency_lock:
            self.concurrency -= 1
            self.nprocessed += 1
            if failed:
                self.nerror += 1
        lim = self._limiter
        if lim is not None:
            lim.on_responded(latency_us, failed, cost)
        if self._method_limiters:
            ml = self._method_limiters.get(method_key)
            if ml is not None:
                ml.on_responded(latency_us, failed, cost)
        lr = self.method_status.get(method_key)
        if lr is None:
            lr = self.method_status.setdefault(method_key, LatencyRecorder())
        lr.record(latency_us)

    @property
    def is_running(self) -> bool:
        return self._running
