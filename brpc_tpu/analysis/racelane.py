"""racelane: the lock model's dynamic complement — seeded schedule
perturbation and a runtime lock-order assert.

Static rules prove the acquisition GRAPH is clean; this module attacks
the schedules. ``install(seed=N)`` replaces ``threading.Lock`` /
``threading.RLock`` with instrumented twins that

  * inject a DETERMINISTIC yield/reorder point at lock acquisitions —
    whether acquisition #k at site S yields is a pure function of
    ``(seed, S, k)``, so a race found at seed N reproduces at seed N,
    every run (the chaos-lane discipline applied to the GIL scheduler:
    a yield right before an acquire is exactly the window a racing
    thread needs to get between a check and its act);
  * name themselves from their creation site (``module:attr`` parsed
    from the assignment source line — the same naming the static lock
    model uses), and, under ``BRPC_TPU_LOCK_DEBUG=1``, assert the
    DECLARED acquisition order from ``LOCK_ORDER`` at every acquire: a
    ranked lock taken while holding a higher-ranked one is recorded
    (and raised in strict mode) with both holders named.

The declared order below is the sanctioned registry published in
``docs/invariants.md`` — one line per lock, outermost first. Locks not
listed are unranked: they perturb but never trip the order assert.
Runtime naming matches registry rows by UNIQUE attribute suffix
(``_arb_lock``, ``_handoff_lock``, ...); rows whose attr is the generic
``_lock`` are ambiguous at runtime and covered by the static
lock-cycle rule only.

Wiring: ``brpc_tpu/__init__`` calls ``maybe_install_from_env()`` so
``BRPC_TPU_LOCK_DEBUG=1`` (with optional ``BRPC_TPU_LOCK_SEED``)
instruments every lock created after package import — tests spawn
their victim in a subprocess with the env set. The tier-2 lane
(``tests/test_racelane.py``) and the preflight smoke
(``python -m brpc_tpu.analysis.racelane --smoke``) replay the lint's
suspicious pairs as concrete interleavings on two threads.
"""

from __future__ import annotations

import linecache
import os
import re
import sys
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

# ------------------------------------------------------ declared order
#
# The sanctioned lock acquisition order, OUTERMOST FIRST: a thread may
# only take a lock with a HIGHER rank index than everything it already
# holds. One line per lock, owner module named — docs/invariants.md
# publishes this table verbatim. Extend deliberately: append where the
# lock nests, never reorder existing entries without re-running the
# static lock-cycle rule and the racelane lane.
LOCK_ORDER: List[Tuple[str, str]] = [
    # (qualified lock name, owner module)
    ("Server._conns_lock",          "rpc/server.py"),
    ("ShardGroup._lock",            "rpc/shard_group.py"),
    ("ClusterChannel._sockets_lock", "rpc/cluster_channel.py"),
    ("Channel._socket_lock",        "rpc/channel.py"),
    ("Channel._pool_lock",          "rpc/channel.py"),
    ("Controller._arb_lock",        "rpc/controller.py"),
    ("Controller._lb_lock",         "rpc/controller.py"),
    ("LoadBalancer._lock",          "rpc/load_balancer.py"),
    ("CircuitBreaker._lock",        "rpc/circuit_breaker.py"),
    ("HealthChecker._lock",         "rpc/health_check.py"),
    ("backend_stats:_registry_lock", "rpc/backend_stats.py"),
    ("BackendStats._ring_lock",     "rpc/backend_stats.py"),
    ("BackendCell._lock",           "rpc/backend_stats.py"),
    ("ServingEngine._decode_lock",  "serving/engine.py"),
    ("ContinuousBatcher._lock",     "serving/batcher.py"),
    ("_StreamSender._lock",         "serving/service.py"),
    ("FlightRecorder._lock",        "builtin/flight_recorder.py"),
    ("Stream._grant_lock",          "rpc/stream.py"),
    ("ProgressiveAttachment._lock", "rpc/progressive.py"),
    ("Socket._handoff_lock",        "transport/socket.py"),
    ("Socket.pending_lock",         "transport/socket.py"),
    ("Socket._failed_cb_lock",      "transport/socket.py"),
    ("Socket._lock",                "transport/socket.py"),
    ("EventDispatcher._lock",       "transport/event_dispatcher.py"),
    ("socket_map:_glock",           "transport/socket_map.py"),
    ("IciConn._pump_lock",          "transport/ici.py"),
    ("IciConn._flush_lock",         "transport/ici.py"),
    ("IciConn._lock",               "transport/ici.py"),
    # leaf: device transfer cell — stamped by BatchTracker settle paths
    # that run under IciConn flush/pump holds; never wraps another
    # acquisition (transport/device_stats.py)
    ("DeviceCell._lock",            "transport/device_stats.py"),
    ("BlockPool._lock",             "butil/iobuf.py"),
    ("variable:_registry_lock",     "bvar/variable.py"),
    ("postfork:_lock",              "butil/postfork.py"),
    ("resource_census:_lock",       "butil/resource_census.py"),
    # leaf: drained inside Channel._retry_taken_call's _arb_lock hold
    # (the one sanctioned nesting); never wraps another acquisition
    ("RetryBudget._lock",           "rpc/retry_policy.py"),
    # leaf: the traffic recorder's queue lock — taken bare on the
    # dispatch completion path (on_complete) and by the writer's O(1)
    # queue swap; disk writes NEVER run under it (blocking-under-lock
    # mutation pin in tests/test_graftlint.py)
    ("Recorder._lock",              "traffic/capture.py"),
    # leaf: the trend-ring registry — settled on the bvar sampler's
    # tick thread AFTER every variable read (get_value / passive
    # callbacks run before the lock is taken); guards ring mutation
    # only, never wraps another acquisition (bvar/series.py)
    ("SeriesCollector._lock",       "bvar/series.py"),
    # leaf: the anomaly watchdog's key-state + incident ring — same
    # tick thread; span/flight-recorder annotation fires OUTSIDE it
    # (bvar/anomaly.py)
    ("AnomalyWatchdog._lock",       "bvar/anomaly.py"),
    # leaf: the DAGOR admission controller's window histogram — taken
    # bare on the dispatch admission path (admit_level) and by the
    # overload organs AFTER their own leaf locks released
    # (signal_overload runs once on_requested has returned False);
    # never wraps another acquisition (rpc/admission.py)
    ("AdmissionController._lock",   "rpc/admission.py"),
    # leaf: the channel-group budget registry — the shared bucket is
    # BUILT outside it (RetryBudget's constructor exposes a bvar, and
    # bvar registration must never nest under a registry lock); the
    # lock guards the dict insert/snapshot only (rpc/retry_policy.py)
    ("retry_policy:_group_lock",    "rpc/retry_policy.py"),
    # leaf: the incident manager's window state — arm/seal decisions
    # settle under it on the sampler tick, but recorder control, the
    # bundler thread spawn, and every disk write fire OUTSIDE it;
    # never wraps another acquisition (incident/manager.py)
    ("IncidentManager._lock",       "incident/manager.py"),
    # leaf: one per-method serving stat cell — the generation
    # tracker's waypoint stamps are plain attribute writes, so the
    # lock is taken ONCE per request lifetime (the settle latch +
    # counter/reservoir writes share the acquisition), always bare:
    # settles fire from _fire / the service shed path, outside every
    # batcher lock (serving/serving_stats.py)
    ("ServingCell._cell_lock",      "serving/serving_stats.py"),
    # leaf: the flight deck's bounded step ring — the batcher appends
    # its per-iteration record AFTER releasing its own lock and firing
    # callbacks; guards ring mutation only, never wraps another
    # acquisition (serving/serving_stats.py)
    ("ServingStats._ring_lock",     "serving/serving_stats.py"),
]

_RANK: Dict[str, int] = {name: i for i, (name, _) in enumerate(LOCK_ORDER)}

_ASSIGN_RE = re.compile(
    r"(?:self\.)?([A-Za-z_][A-Za-z0-9_]*)\s*=\s*"
    r"(?:threading\.)?(?:Lock|RLock)\s*\(")

# fallback for factory-indirected creation (Controller._LAZY via
# __getattr__): the creating line is `v = factory()`, but the frame
# ABOVE it is the attribute access (`with cntl._arb_lock:`) — a
# lock-ish attribute token there names the lock
_ATTR_RE = re.compile(
    r"[.\s(\[]([A-Za-z_][A-Za-z0-9_]*(?:lock|mutex)[A-Za-z0-9_]*)",
    re.IGNORECASE)


class LockOrderViolation(AssertionError):
    """A ranked lock was acquired while a higher-ranked one was held."""


class _State:
    """Module state for one install() session."""

    def __init__(self):
        self.installed = False
        self.seed = 0
        self.strict = False
        self.perturb = True
        self.yield_period = 7          # acquire #k yields when
        #                                hash(site, k, seed) % period == 0
        self.real_lock = None          # saved threading.Lock
        self.real_rlock = None         # saved threading.RLock
        self.acquires = 0              # global acquisition counter
        self.yields = 0
        self.violations: List[dict] = []
        self.lock_names: List[str] = []   # names seen at creation
        # per-THREAD ownership: .held = [(name, rank)] in acquisition
        # order, .counts = {id(lock): recursion depth}. Ownership must
        # be thread-local — an instance-level depth would make thread B
        # skip the order check whenever thread A happens to hold the
        # lock, which is exactly the moment the check matters — and
        # keyed by INSTANCE, not creation-site name: holding another
        # object's same-named lock is nesting to order-check, not
        # recursion to wave through (two Channels, two Sockets)
        self.tl = threading.local()

    def held(self) -> list:
        h = getattr(self.tl, "held", None)
        if h is None:
            h = self.tl.held = []
        return h

    def counts(self) -> dict:
        c = getattr(self.tl, "counts", None)
        if c is None:
            c = self.tl.counts = {}
        return c


_state = _State()


def _creation_site_name(depth: int = 2) -> str:
    """Name a lock from its creation source line — 'module:attr' like
    the static model. A direct assignment names at the creating frame;
    factory indirection (the Controller._LAZY `v = factory()` path)
    walks a few frames up to the attribute ACCESS that triggered the
    lazy creation (`with cntl._arb_lock:`) and names from its lock-ish
    token — so the real registry rows rank at runtime, not just the
    synthetic smoke locks."""
    try:
        for d in range(depth, depth + 4):
            try:
                f = sys._getframe(d)
            except ValueError:
                break
            fn, ln = f.f_code.co_filename, f.f_lineno
            line = linecache.getline(fn, ln)
            m = _ASSIGN_RE.search(line) or _ATTR_RE.search(line)
            if m:
                mod = os.path.basename(fn)
                if mod.endswith(".py"):
                    mod = mod[:-3]
                # self._x in a class: the runtime cannot see the class
                # name cheaply, so the registry matches by unique attr
                # suffix
                return f"{mod}:{m.group(1)}"
        return "<anon>:<anon>"
    except Exception:
        return "<anon>:<anon>"


def _rank_of(name: str) -> Optional[int]:
    attr = name.split(":")[-1]
    if name in _RANK:
        return _RANK[name]
    # unique attr suffix ('_arb_lock' names exactly one registry row)
    hits = [r for n, r in _RANK.items()
            if n.split(".")[-1] == attr or n.split(":")[-1] == attr]
    if len(hits) == 1:
        return hits[0]
    return None


def _registry_name(name: str) -> str:
    attr = name.split(":")[-1]
    hits = [n for n in _RANK
            if n.split(".")[-1] == attr or n.split(":")[-1] == attr]
    return hits[0] if len(hits) == 1 else name


def _perturb_point(site: str) -> None:
    """The deterministic yield: whether acquisition #k at this site
    yields is a pure function of (seed, site, k)."""
    st = _state
    st.acquires += 1
    if not st.perturb:
        return
    k = st.acquires
    # crc32, NOT builtin hash(): str hashing is PYTHONHASHSEED-salted
    # per process, and the whole point is that the yield schedule is a
    # pure function of (seed, site, k) ACROSS runs
    h = zlib.crc32(f"{st.seed}|{site}|{k}".encode())
    if h % st.yield_period == 0:
        st.yields += 1
        # a zero sleep is a real GIL release point: the OS scheduler
        # may run any other ready thread here
        time.sleep(0)


def _order_check(name: str, rank: Optional[int]) -> None:
    st = _state
    if rank is None:
        return
    held = st.held()
    for hname, hrank in held:
        if hrank is not None and hrank > rank:
            v = {"acquiring": _registry_name(name),
                 "acquiring_rank": rank,
                 "holding": _registry_name(hname),
                 "holding_rank": hrank,
                 "thread": threading.current_thread().name}
            st.violations.append(v)
            if st.strict:
                raise LockOrderViolation(
                    f"lock order inversion: acquiring "
                    f"{v['acquiring']} (rank {rank}) while holding "
                    f"{v['holding']} (rank {hrank}) — the declared "
                    "order in analysis/racelane.py:LOCK_ORDER says "
                    "the opposite nesting")
            break


class _DebugLockBase:
    """Shared instrumentation over a real lock primitive."""

    _factory = None        # set by install()

    def __init__(self):
        self._inner = self._factory()
        self.name = _creation_site_name(2)
        self.rank = _rank_of(self.name)
        _state.lock_names.append(self.name)

    # -- the threading.Lock protocol ---------------------------------
    def acquire(self, blocking: bool = True, timeout: float = -1):
        counts = _state.counts()
        mine = counts.get(id(self), 0)
        if blocking:
            _perturb_point(self.name)
            if mine == 0:
                # order is asserted on acquisition INTENT, before the
                # inner acquire: the deadlocked half of an AB/BA pair
                # never returns from acquire, so a post-acquire check
                # would record nothing exactly when it matters most.
                # (try-acquires are deadlock-safe by construction and
                # stay out of the assert.) In strict mode this raises
                # BEFORE anything is held — nothing leaks.
                _order_check(self.name, self.rank)
        got = self._inner.acquire(blocking, timeout)
        if got:
            counts[id(self)] = mine + 1
            if mine == 0:
                _state.held().append((self.name, self.rank))
        return got

    def release(self):
        self._inner.release()
        counts = _state.counts()
        mine = counts.get(id(self), 0)
        if mine:       # a cross-thread Lock release skips bookkeeping
            if mine == 1:
                counts.pop(id(self))     # no stale id-keyed entries
                held = _state.held()
                for i in range(len(held) - 1, -1, -1):
                    if held[i][0] == self.name:
                        del held[i]
                        break
            else:
                counts[id(self)] = mine - 1

    def locked(self):
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def _at_fork_reinit(self):
        self._inner = self._factory()


class DebugLock(_DebugLockBase):
    pass


class DebugRLock(_DebugLockBase):
    """RLock twin; also speaks the Condition protocol (_is_owned /
    _release_save / _acquire_restore) — the stdlib fallback probes
    ownership with a NON-reentrant acquire(False), which an RLock
    answers wrongly, so delegation here is load-bearing."""

    def _is_owned(self):
        return self._inner._is_owned()

    def _release_save(self):
        state = self._inner._release_save()
        counts = _state.counts()
        depth = counts.pop(id(self), 0)
        held = _state.held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] == self.name:
                del held[i]
                break
        return (state, depth)

    def _acquire_restore(self, saved):
        state, depth = saved
        self._inner._acquire_restore(state)
        _state.counts()[id(self)] = depth
        _state.held().append((self.name, self.rank))


def install(seed: Optional[int] = None, strict: bool = False,
            perturb: bool = True, yield_period: int = 7) -> None:
    """Instrument every lock created from now on. Idempotent."""
    st = _state
    if not st.installed:
        st.real_lock = threading.Lock
        st.real_rlock = threading.RLock
        DebugLock._factory = staticmethod(st.real_lock)
        DebugRLock._factory = staticmethod(st.real_rlock)
        threading.Lock = DebugLock
        threading.RLock = DebugRLock
        st.installed = True
    st.seed = 0 if seed is None else int(seed)
    st.strict = bool(strict)
    st.perturb = bool(perturb)
    st.yield_period = max(2, int(yield_period))


def uninstall() -> None:
    st = _state
    if st.installed:
        threading.Lock = st.real_lock
        threading.RLock = st.real_rlock
        st.installed = False


def installed() -> bool:
    return _state.installed


def violations() -> List[dict]:
    return list(_state.violations)


def clear_violations() -> None:
    _state.violations.clear()


def stats() -> dict:
    return {"installed": _state.installed, "seed": _state.seed,
            "acquires": _state.acquires, "yields": _state.yields,
            "locks_created": len(_state.lock_names),
            "violations": len(_state.violations)}


def maybe_install_from_env() -> bool:
    """The brpc_tpu/__init__ hook: BRPC_TPU_LOCK_DEBUG=1 instruments
    (order-asserting, perturbing with BRPC_TPU_LOCK_SEED, strict with
    BRPC_TPU_LOCK_DEBUG=strict)."""
    mode = os.environ.get("BRPC_TPU_LOCK_DEBUG", "")
    if mode not in ("1", "strict"):
        return False
    seed = 0
    try:
        seed = int(os.environ.get("BRPC_TPU_LOCK_SEED", "0"))
    except ValueError:
        pass
    install(seed=seed, strict=(mode == "strict"))
    return True


# ----------------------------------------------------------- replays

def replay_pair(setup, thread_a, thread_b, seed: int,
                timeout_s: float = 5.0) -> dict:
    """Replay a suspicious lock pair as a concrete interleaving: run
    ``thread_a``/``thread_b`` (callables taking the object built by
    ``setup()``) on two threads under seeded perturbation and report
    violations + completion (a hang within the timeout = potential
    deadlock, reported, threads abandoned as daemons)."""
    clear_violations()
    # apply the REQUESTED seed (replaying a race found at seed N must
    # actually run seed N, not whatever install() last set) and reset
    # the acquisition counter the yield schedule is keyed on, so the
    # same replay sees the same k sequence, run after run, process
    # after process
    _state.seed = int(seed)
    _state.acquires = 0
    obj = setup()
    done = [False, False]

    def run(fn, i):
        try:
            fn(obj)
        finally:
            done[i] = True

    ta = threading.Thread(target=run, args=(thread_a, 0), daemon=True)
    tb = threading.Thread(target=run, args=(thread_b, 1), daemon=True)
    ta.start()
    tb.start()
    ta.join(timeout_s)
    tb.join(timeout_s)
    return {"seed": seed, "completed": all(done),
            "violations": violations(),
            "stats": stats()}


# ------------------------------------------------- field-race replay
#
# The guarded-by rule's dynamic complement: a flagged site is a source
# LINE that mutates a field with the inferred guard not held. Replaying
# it means running the two implicated code paths on two threads while a
# per-thread trace hook injects a seeded GIL yield every time a racer
# is ABOUT to execute a flagged line — exactly the window a racing peer
# needs between the site's check and its act. A finding whose replay
# breaks the caller-supplied invariant ships as CONFIRMED with this
# reproducer (seed + sites); the rest stay ranked PLAUSIBLE.

def _parse_site(site) -> Tuple[str, int]:
    """'pkg/mod.py:123' or ('mod.py', 123) -> ('mod.py', 123)."""
    if isinstance(site, str):
        path, _, ln = site.rpartition(":")
        return os.path.basename(path), int(ln)
    path, ln = site
    return os.path.basename(str(path)), int(ln)


# One yield hands the interpreter to the peer for this many of ITS
# watched lines at most (the hash picks 1..N), and gives up after this
# long when the peer cannot advance (blocked on a lock the yielder
# holds, parked in a join, asleep).
_HANDOFF_MAX_LINES = 16
_HANDOFF_TIMEOUT_S = 0.001


def _make_tracer(files, lines, quals, seed, tix, progress, waiting, done):
    """One tracer per racer thread. The call-event filter keeps the
    line hook out of every frame not under watch, so the replay's
    overhead stays on the implicated functions only. Yield decisions
    are a pure function of (seed, thread index, site, hit #) — the
    schedule replays exactly, run after run.

    A yield is a HANDOFF, not a nap: the yielder stands still until
    the peer has executed the number of watched lines the hash drew
    (``progress`` counts them per racer), so the peer really runs
    inside the window between the yielder's check and its act. A
    fixed 20us sleep left that to the OS: on a quiet box the sleeper
    was back before the peer's thread had been scheduled, and a race
    the finding describes never happened in the replay. The wait ends
    early when the peer finishes, when it is itself standing at a
    yield (two yielders must not wait on each other), or after
    _HANDOFF_TIMEOUT_S."""
    k = [0]
    peer = 1 - tix

    leaves = {q.split(".")[-1] for q in quals}

    def match_qual(code) -> bool:
        # suffix match on a dot boundary: functions built inside a
        # factory carry '<locals>.' prefixes in co_qualname. Before
        # 3.11 code objects have no co_qualname — fall back to the
        # bare name (a looser match that only ever ADDS yield points)
        qual = getattr(code, "co_qualname", None)
        if qual is None:
            return code.co_name in leaves
        return any(qual == q or qual.endswith("." + q) for q in quals)

    def line_hook(frame, event, arg):
        if event != "line":
            return line_hook
        code = frame.f_code
        bn = os.path.basename(code.co_filename)
        if (bn, frame.f_lineno) in lines or match_qual(code):
            k[0] += 1
            progress[tix] += 1
            h = zlib.crc32(f"{seed}|{tix}|{bn}:{frame.f_lineno}|"
                           f"{k[0]}".encode())
            if h % 2 == 0:
                _state.yields += 1
                want = progress[peer] + 1 + (h >> 1) % _HANDOFF_MAX_LINES
                give_up = time.monotonic() + _HANDOFF_TIMEOUT_S
                waiting[tix] = True
                try:
                    # a POSITIVE sleep: a zero sleep often re-acquires
                    # the GIL before the peer's condvar wakes
                    time.sleep(0.00002)
                    while (progress[peer] < want and not done[peer]
                           and not (tix and waiting[peer])
                           and time.monotonic() < give_up):
                        time.sleep(0.00002)
                finally:
                    waiting[tix] = False
        return line_hook

    def call_hook(frame, event, arg):
        code = frame.f_code
        if os.path.basename(code.co_filename) in files or \
                match_qual(code):
            return line_hook
        return None

    return call_hook


def replay_field_race(setup, racer_a, racer_b, sites, seed: int = 0,
                      check=None, timeout_s: float = 10.0) -> dict:
    """Replay a guarded-by finding as a concrete interleaving.

    ``setup()`` builds the victim object; ``racer_a``/``racer_b`` are
    the two implicated code paths (callables taking the object);
    ``sites`` mixes flagged source lines (``'path.py:123'`` strings or
    ``(file, line)`` pairs) with function qualnames (every line of the
    function is a yield point — drift-proof against edits). After both
    racers finish, ``check(obj)`` validates the field's invariant; its
    message is the reproducer's evidence. Returns ``{seed, completed,
    site_yields, ok, evidence}``."""
    lines = set()
    quals = set()
    for s in sites:
        if isinstance(s, str) and ":" not in s:
            quals.add(s)
        else:
            lines.add(_parse_site(s))
    files = {f for f, _ in lines}
    y0 = _state.yields
    obj = setup()
    done = [False, False]
    progress = [0, 0]        # watched lines each racer has executed
    waiting = [False, False]  # racer stands at a yield
    errs: List[str] = []
    # both racers align here before racing: without it the first
    # thread routinely finishes before the second's OS thread even
    # starts, and a serialized run can confirm nothing
    barrier = threading.Barrier(2)

    def run(fn, i):
        barrier.wait(timeout_s)
        sys.settrace(_make_tracer(files, lines, quals, seed, i,
                                  progress, waiting, done))
        try:
            fn(obj)
        except Exception as e:   # noqa: BLE001 - the report carries it
            errs.append(f"racer_{'ab'[i]}: {e!r}")
        finally:
            sys.settrace(None)
            done[i] = True

    ta = threading.Thread(target=run, args=(racer_a, 0), daemon=True)
    tb = threading.Thread(target=run, args=(racer_b, 1), daemon=True)
    ta.start()
    tb.start()
    ta.join(timeout_s)
    tb.join(timeout_s)
    completed = all(done)
    evidence = list(errs)
    ok = completed and not errs
    if ok and check is not None:
        try:
            verdict = check(obj)
            if verdict not in (None, True):
                ok = False
                evidence.append(str(verdict))
        except AssertionError as e:
            ok = False
            evidence.append(str(e) or "invariant check failed")
    if not completed:
        evidence.append(f"racers hung past {timeout_s}s "
                        "(potential deadlock; daemons abandoned)")
    return {"seed": seed, "completed": completed,
            "site_yields": _state.yields - y0,
            "ok": ok, "evidence": evidence}


# The suspicious-pair list the preflight smoke replays: each entry is a
# named builder returning (setup, racer_a, racer_b, sites, check,
# expect_race). `expect_race=True` rows are positive controls — the
# replay MUST break their invariant (the harness detects real races);
# `False` rows are fixed findings — the replay must leave the
# invariant intact (the regression stays dead at this seed).

def _pair_unguarded_counter():
    """Positive control: the textbook lost update. The read-modify-
    write is split across two lines so the line hook can yield inside
    the window; 2x200 increments with no lock must lose some."""
    class _Cell:
        def __init__(self):
            self.x = 0

        def bump(self):
            t = self.x
            self.x = t + 1

    def racer(o):
        for _ in range(200):
            o.bump()

    def check(o):
        assert o.x == 400, f"lost update: {o.x}/400 after 2x200 bumps"

    return _Cell, racer, racer, ["_Cell.bump"], check, True


def _pair_guarded_counter():
    """The same counter with its guard held: zero lost updates under
    the identical yield schedule — the twin that proves detection is
    the race, not the harness."""
    class _Cell:
        def __init__(self):
            self._lock = threading.Lock()
            self.x = 0

        def bump(self):
            with self._lock:
                t = self.x
                self.x = t + 1

    def racer(o):
        for _ in range(200):
            o.bump()

    def check(o):
        assert o.x == 400, f"guarded counter lost updates: {o.x}"

    return _Cell, racer, racer, ["_Cell.bump"], check, False


def _pair_taskcontrol_stop_vs_start():
    """The fixed ISSUE-16 finding: TaskControl.stop_and_join used to
    clear _threads and drop _started/_stop with no lock while start()
    published the pool under _start_lock — a start() landing in the
    teardown window left a pool that CLAIMS started with every worker
    dead (spawned fibers never run). Yields at every line of both
    verbs drive the interleaving; the invariant is 'started implies a
    live worker'."""
    from brpc_tpu.fiber.scheduler import TaskControl

    def setup():
        return TaskControl(concurrency=2, name="racelane_tc")

    def starter(tc):
        for _ in range(6):
            tc.start()
            time.sleep(0)

    def stopper(tc):
        for _ in range(6):
            tc.stop_and_join(timeout=2.0)

    def check(tc):
        try:
            with tc._start_lock:
                started = tc._started
                alive = [t for t in tc._threads if t.is_alive()]
            assert not started or alive, (
                "pool claims started with no live worker: start() "
                "landed inside stop_and_join's teardown window")
        finally:
            tc.stop_and_join(timeout=2.0)

    return (setup, starter, stopper,
            ["TaskControl.start", "TaskControl.stop_and_join"],
            check, False)


SUSPICIOUS_PAIRS = [
    ("unguarded-counter", _pair_unguarded_counter),
    ("guarded-counter", _pair_guarded_counter),
    ("taskcontrol-stop-vs-start", _pair_taskcontrol_stop_vs_start),
]


def replay_suspicious_pairs(seed: int = 0) -> dict:
    """Run every registered pair; ok = every positive control raced
    and every fixed finding held its invariant."""
    out: dict = {"pairs": {}, "ok": True}
    for name, build in SUSPICIOUS_PAIRS:
        setup, ra, rb, sites, check, expect_race = build()
        r = replay_field_race(setup, ra, rb, sites, seed=seed,
                              check=check)
        raced = not r["ok"]
        good = r["completed"] and (raced == expect_race)
        out["pairs"][name] = {"expect_race": expect_race,
                              "raced": raced, **r}
        out["ok"] = out["ok"] and good
    return out


# ------------------------------------------------------------- smoke

def _smoke() -> dict:
    """The preflight lane: (1) a seeded synthetic AB/BA inversion must
    be DETECTED deterministically (same seed, same verdict, run twice);
    (2) the real serving batcher under perturbation + order assert runs
    a submit/step/cancel storm with zero violations."""
    report: dict = {"ok": False}
    try:
        seed = int(os.environ.get("BRPC_TPU_LOCK_SEED", "0") or "0")
    except ValueError:
        seed = 0
    if not _state.installed:
        install(seed=seed)
    else:
        # the package import hook installed with the seed the env had
        # THEN — a --seed passed to the CLI must still win
        _state.seed = seed

    # -- (1) synthetic inversion: two registry-ranked locks taken in
    # the wrong order on thread B while thread A uses the sanctioned
    # order. The order assert must flag B's inversion both runs.
    def build_pair():
        class _Arb:                       # mimic the registry rows
            pass
        o = _Arb()
        o._arb_lock = threading.RLock()   # rank: Controller._arb_lock
        o._lb_lock = threading.Lock()     # rank: Controller._lb_lock
        return o

    def good_path(o):
        for _ in range(20):
            with o._arb_lock:
                with o._lb_lock:          # sanctioned: arb then lb
                    pass

    def bad_path(o):
        for _ in range(20):
            with o._lb_lock:
                with o._arb_lock:         # inversion: lb then arb
                    pass

    runs = []
    for _ in range(2):
        r = replay_pair(build_pair, good_path, bad_path, _state.seed,
                        timeout_s=2.0)
        runs.append({"completed": r["completed"],
                     "deadlocked": not r["completed"],
                     "violations": len(r["violations"]),
                     "first": (r["violations"][0]
                               if r["violations"] else None)})
    report["seeded_inversion"] = runs
    # the assert fires on acquisition INTENT: the inversion is recorded
    # even when the pair genuinely deadlocks (the perturbation makes
    # that likely — which is the point; the replay abandons the
    # daemonized pair and reports the hang as evidence)
    detected = all(r["violations"] > 0 for r in runs)
    deterministic = (runs[0]["first"] is not None
                     and runs[1]["first"] is not None
                     and runs[0]["first"]["acquiring"]
                     == runs[1]["first"]["acquiring"]
                     and runs[0]["first"]["holding"]
                     == runs[1]["first"]["holding"])
    report["inversion_detected"] = detected
    report["inversion_deterministic"] = deterministic

    # -- (2) real code under perturbation: batcher submit/step/cancel
    clear_violations()
    from brpc_tpu.serving.batcher import ContinuousBatcher, GenRequest
    b = ContinuousBatcher(max_batch=2, max_waiting=8)
    errs: List[str] = []

    def submitter():
        for i in range(24):
            try:
                b.submit(GenRequest([1, 2, 3], 4))
            except Exception as e:   # noqa: BLE001 - report, don't die
                errs.append(f"submit: {e!r}")

    def stepper():
        for _ in range(60):
            try:
                b.step()
            except Exception as e:   # noqa: BLE001
                errs.append(f"step: {e!r}")

    ts = [threading.Thread(target=submitter, daemon=True),
          threading.Thread(target=stepper, daemon=True)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30.0)
    b.stop()
    real_viol = violations()
    report["real_code"] = {"errors": errs[:5],
                           "violations": real_viol[:5],
                           "stats": stats()}
    report["real_code_clean"] = not errs and not real_viol

    # -- (3) the guarded-by suspicious-pair list: positive controls
    # must race, fixed findings must hold their invariant
    report["field_races"] = replay_suspicious_pairs(_state.seed)
    report["ok"] = bool(detected and deterministic
                        and report["real_code_clean"]
                        and report["field_races"]["ok"])
    return report


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import json
    p = argparse.ArgumentParser(
        prog="racelane",
        description="seeded lock-schedule perturbation + order assert")
    p.add_argument("--smoke", action="store_true",
                   help="run the seeded-interleaving smoke (JSON out)")
    p.add_argument("--seed", type=int, default=None)
    args = p.parse_args(argv)
    if args.seed is not None:
        os.environ["BRPC_TPU_LOCK_SEED"] = str(args.seed)
    if not args.smoke:
        p.print_help()
        return 2
    report = _smoke()
    print(json.dumps(report, indent=2, default=str))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    # delegate to the canonical module object: under -m the package
    # __init__ may already have imported (and installed from) the
    # brpc_tpu.analysis.racelane copy — running the smoke on a second
    # __main__ copy would split _state across two modules
    from brpc_tpu.analysis import racelane as _canonical

    sys.exit(_canonical.main())
