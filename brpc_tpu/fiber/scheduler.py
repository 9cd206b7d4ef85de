"""M:N fiber scheduler: work-stealing worker threads stepping coroutines.

TPU-native re-design of the reference's bthread runtime (SURVEY.md §2.2):

  TaskControl (task_control.h:42)  -> TaskControl: owns N workers + parking
  TaskGroup   (task_group.h:70)    -> TaskGroup: per-worker run queues
  WorkStealingQueue                -> collections.deque (owner pops right /
                                      thieves pop left; GIL-atomic)
  ParkingLot  (parking_lot.h:31)   -> condition variable + signal counter
  fcontext asm switch              -> coroutine send/StopIteration stepping
  _bound_rq (fork's group-bound    -> Fiber.bound_group pinning, the hook
   bthreads, task_group.h:230)        TPU device affinity hangs off

A *fiber* wraps a Python coroutine. Workers pop a fiber and ``step`` it:
one ``coro.send`` advances it until it either finishes (StopIteration) or
awaits a scheduler token (a ``SchedAwaitable``), which re-registers the
fiber with whatever will wake it (butex, timer, device poller, io).
Plain callables are wrapped in a trivial coroutine; they may block their
worker thread (the reference's usercode_in_pthread escape hatch).

Unlike bthread's start_urgent, a running Python frame can't be preempted,
so ``spawn_urgent`` pushes to the *head* of the local queue instead
(runs at the next suspension point).
"""

from __future__ import annotations

import inspect
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, List, Optional

from brpc_tpu.butil import thread_cpu
from brpc_tpu.butil.fast_rand import fast_rand_less_than
from brpc_tpu.bvar.reducer import Adder, Maxer, PassiveStatus

_wake_rec = None
_wake_rec_lock = threading.Lock()


def _wake_recorder():
    """LatencyRecorder for wake-to-run latency, exposed lazily as
    fiber_wake (the import is deferred to dodge the bvar->fiber
    circular import at module load). Re-exposes if the registry was
    cleared (bvar's unexpose_all test helper) — this recorder is a
    process-global singleton, so a dropped exposure would otherwise be
    permanent."""
    global _wake_rec
    if _wake_rec is None:
        with _wake_rec_lock:
            if _wake_rec is None:
                from brpc_tpu.bvar.latency_recorder import LatencyRecorder
                _wake_rec = LatencyRecorder().expose("fiber_wake")
    if getattr(_wake_rec, "_name", None) != "fiber_wake":
        _wake_rec.expose("fiber_wake")
    return _wake_rec

FIBER_STATE_READY = 0
FIBER_STATE_RUNNING = 1
FIBER_STATE_SUSPENDED = 2
FIBER_STATE_DONE = 3


class SchedAwaitable:
    """Base of everything a fiber may ``await``. ``_register(fiber)`` must
    arrange a future ``TaskControl.schedule(fiber, value)`` exactly once."""

    def _register(self, fiber: "Fiber") -> None:
        raise NotImplementedError

    def __await__(self):
        result = yield self
        return result


class _YieldNow(SchedAwaitable):
    def _register(self, fiber: "Fiber") -> None:
        fiber.control.schedule(fiber, None, to_tail=True)


def yield_now() -> SchedAwaitable:
    """Cooperatively reschedule (bthread_yield)."""
    return _YieldNow()


class Fiber:
    """One unit of M:N execution (bthread's TaskMeta)."""

    __slots__ = (
        "coro", "control", "state", "result", "exception", "bound_group",
        "locals", "_done_event", "_joiner_butex", "_resume_value", "name",
        "_key_destructors", "_ready_ns",
    )

    def __init__(self, coro, control: "TaskControl", name: str = ""):
        self.coro = coro
        self.control = control
        self.state = FIBER_STATE_READY
        self.result: Any = None
        self.exception: Optional[BaseException] = None
        self.bound_group: Optional[int] = None
        self.locals: dict = {}
        self.name = name
        self._done_event = None    # lazily created on first join()
        self._joiner_butex = None  # lazily created Butex for fiber joiners
        self._resume_value: Any = None
        self._key_destructors: List[Callable] = []
        self._ready_ns = 0

    # ---------------------------------------------------------------- join
    def done(self) -> bool:
        return self.state == FIBER_STATE_DONE

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block the calling *thread* until the fiber finishes. Safe from
        non-fiber threads; inside a fiber prefer ``await fiber.join_async()``."""
        if self.state == FIBER_STATE_DONE:
            return True
        # the done Event is lazy (most fibers are never thread-joined):
        # create under the lock and re-check, so a _finish racing this
        # join either sees the event or already published DONE
        with _joiner_init_lock:
            if self.state == FIBER_STATE_DONE:
                return True
            ev = self._done_event
            if ev is None:
                ev = self._done_event = threading.Event()
        return ev.wait(timeout)

    def join_async(self) -> SchedAwaitable:
        """Awaitable join for use inside another fiber."""
        from brpc_tpu.fiber.butex import Butex
        if self._joiner_butex is None:
            with _joiner_init_lock:
                if self._joiner_butex is None:
                    self._joiner_butex = Butex(0)
        butex = self._joiner_butex

        class _Join(SchedAwaitable):
            def _register(_self, fiber):
                if self.done():
                    fiber.control.schedule(fiber, None)
                else:
                    butex.add_waiter(fiber, expected=0)
        return _Join()

    def value(self) -> Any:
        if self.exception is not None:
            raise self.exception
        return self.result

    def _finish(self, result, exc) -> None:
        self.result = result
        self.exception = exc
        for d in self._key_destructors:
            try:
                d(self)
            except Exception:
                pass
        self.state = FIBER_STATE_DONE
        if self._joiner_butex is not None:
            self._joiner_butex.set_and_wake_all(1)
        # pair with join()'s lazy creation: after DONE is published, any
        # event a joiner managed to install must still be set
        ev = self._done_event
        if ev is None:
            with _joiner_init_lock:
                ev = self._done_event
        if ev is not None:
            ev.set()
        self.control.nfibers.add(-1)
        if exc is not None and not isinstance(exc, SystemExit):
            self.control.on_fiber_error(self, exc)


_joiner_init_lock = threading.Lock()


class _CurrentCell:
    """Per-thread mutable holder of the fiber being stepped. A PLAIN
    object (not thread-local storage) registered by thread ident, so
    the flight-recorder sampler can read any thread's current fiber
    from outside — attributing a stack sample to the RPC method that
    fiber is serving. Reads are racy by design: a torn read costs one
    misattributed sample, never a crash."""

    __slots__ = ("current",)

    def __init__(self):
        self.current: Optional[Fiber] = None


class _WorkerTLS(threading.local):
    def __init__(self):
        self.group: Optional["TaskGroup"] = None
        self.inline_depth: int = 0
        # threading.local runs __init__ on each thread's FIRST attribute
        # touch, on that thread — so this registration executes exactly
        # once per thread, keyed by its own ident
        self.cell = _CurrentCell()
        _cell_by_thread[threading.get_ident()] = self.cell


# thread ident -> that thread's _CurrentCell (see _WorkerTLS.__init__);
# entries of dead threads are pruned by the sampler against the live
# tid set from sys._current_frames()
_cell_by_thread: dict = {}


_tls = _WorkerTLS()


def current_fiber() -> Optional[Fiber]:
    return _tls.cell.current


def thread_current_fiber(tid: int) -> Optional[Fiber]:
    """The fiber currently being stepped on thread ``tid`` (racy
    snapshot for samplers/watchdogs), or None for non-fiber threads and
    threads between steps."""
    cell = _cell_by_thread.get(tid)
    return cell.current if cell is not None else None


_prune_suspects: set = set()


def prune_thread_registry(live_tids) -> None:
    """Drop cells of dead threads (sampler housekeeping). TWO-strike:
    a cell is only removed when its thread was absent from two
    CONSECUTIVE live snapshots — a brand-new thread can register its
    cell between the sampler's frames snapshot and this prune, and a
    one-shot prune would delete it forever (threading.local.__init__
    never reruns, so the cell could not come back)."""
    global _prune_suspects
    # snapshot: another thread's FIRST _tls touch inserts mid-iteration
    gone = {tid for tid in list(_cell_by_thread) if tid not in live_tids}
    for tid in gone & _prune_suspects:
        _cell_by_thread.pop(tid, None)
    _prune_suspects = gone


def current_group() -> Optional["TaskGroup"]:
    return _tls.group


class ParkingLot:
    """Futex-style idle-worker parking (bthread/parking_lot.h:31)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._signals = 0

    def signal_count(self) -> int:
        return self._signals

    def signal(self, n: int = 1) -> None:
        with self._cond:
            self._signals += 1
            self._cond.notify(n)

    def wait(self, expected: int, timeout: float = 1.0) -> None:
        with self._cond:
            if self._signals == expected:
                self._cond.wait(timeout)


class TaskGroup:
    """Per-worker scheduler state (bthread/task_group.h:70)."""

    def __init__(self, control: "TaskControl", index: int):
        self.control = control
        self.index = index
        self.rq: Deque[Fiber] = deque()         # local queue: owner pops right
        self.remote_rq: Deque[Fiber] = deque()  # pushed by non-workers
        self.bound_rq: Deque[Fiber] = deque()   # group-pinned fibers (fork's _bound_rq)
        self.nsteals = 0
        self.nswitches = 0
        self.nwakes = 0

    # owner-side pop order: bound first (pinned work can't run elsewhere),
    # then local LIFO for cache locality, then remote FIFO
    def pop_local(self) -> Optional[Fiber]:
        try:
            return self.bound_rq.popleft()
        except IndexError:
            pass
        try:
            return self.rq.pop()
        except IndexError:
            pass
        try:
            return self.remote_rq.popleft()
        except IndexError:
            return None

    def steal_from(self) -> Optional[Fiber]:
        """Thieves take the oldest local/remote task; bound tasks are never
        stolen."""
        try:
            return self.rq.popleft()
        except IndexError:
            pass
        try:
            return self.remote_rq.popleft()
        except IndexError:
            return None


class TaskControl:
    """Owns the worker pthreads (bthread/task_control.h:42)."""

    def __init__(self, concurrency: Optional[int] = None, name: str = "fiber"):
        if concurrency is None:
            # like bthread's default (8+1 workers even on small hosts):
            # fibers may run blocking user code, so a floor of spare workers
            # matters more than matching core count under the GIL
            concurrency = max(8, os.cpu_count() or 0)
        self.name = name
        self.concurrency = concurrency
        self.groups: List[TaskGroup] = [TaskGroup(self, i) for i in range(concurrency)]
        self.parking_lot = ParkingLot()
        self._threads: List[threading.Thread] = []
        self._stop = False
        self.nfibers = Adder(0)
        self.nfibers_created = Adder(0)
        # saturation instrumentation (the scheduler half of the rpcz
        # timeline story: when spans show queue_us growing, these name
        # the culprit). busy_ns accumulates worker time spent stepping
        # fibers — windowed into a busy fraction; runq_peak records the
        # deepest run queue seen at schedule() time — windowed into a
        # per-interval high-water mark.
        self.busy_ns = Adder(0)
        self.runq_peak = Maxer()
        self._busy_window = None       # PerSecond, created on first use
        self._runq_peak_window = None  # Window, created on first use
        self._error_handlers: List[Callable] = []
        self._started = False
        self._start_lock = threading.Lock()

    # -------------------------------------------------------------- start
    def start(self) -> None:
        with self._start_lock:
            if self._started:
                return
            self._started = True
            for g in self.groups:
                t = threading.Thread(target=self._worker, args=(g,),
                                     name=f"{self.name}_w{g.index}", daemon=True)
                self._threads.append(t)
                t.start()

    def stop_and_join(self, timeout: float = 5.0) -> None:
        self._stop = True
        with self._start_lock:
            # claim the pool under the same lock start() publishes it
            # with; _started stays True through the join so a racing
            # start() keeps no-opping instead of spawning a doomed
            # pool that would only see _stop and exit
            threads = list(self._threads)
            self._threads.clear()
        for _ in threads:
            self.parking_lot.signal(len(threads))
        for t in threads:
            t.join(timeout)
        with self._start_lock:
            # both flags flip in one critical section: dropping
            # _started with _stop still True would let a racing
            # start() spawn workers that instantly see _stop and
            # exit — a pool that claims started with nothing alive
            self._started = False
            self._stop = False

    # -------------------------------------------------------------- spawn
    def spawn(self, fn: Callable | Any, *args, name: str = "", urgent: bool = False,
              bound_group: Optional[int] = None, **kwargs) -> Fiber:
        """Start a fiber from a coroutine function, coroutine object, or
        plain callable (bthread_start_background / start_urgent)."""
        if inspect.iscoroutine(fn):
            coro = fn
        elif inspect.iscoroutinefunction(fn):
            coro = fn(*args, **kwargs)
        else:
            async def _runner():
                r = fn(*args, **kwargs)
                if inspect.isawaitable(r):
                    r = await r
                return r
            coro = _runner()
        fiber = Fiber(coro, self, name=name)
        if bound_group is not None:
            fiber.bound_group = bound_group % self.concurrency
        self.nfibers.add(1)
        self.nfibers_created.add(1)
        if not self._started:
            self.start()
        # note: the local queue is LIFO for the owner (Chase-Lev bottom), so a
        # plain push already runs next — bthread_start_urgent's "run NOW with
        # caller requeued" can't preempt a Python frame, and `urgent` adds
        # nothing beyond the LIFO push; it is accepted for API parity only
        self.schedule(fiber, None)
        return fiber

    def spawn_many(self, works, name: str = "") -> List[Fiber]:
        """Batch spawn with ONE parking-lot signal for the whole run —
        the amortized wake of a pipelined burst spill (N messages
        fanned out used to pay N condvar signals from the dispatcher
        thread, the per-burst scheduler cost the batched frame
        pipeline exists to remove). Semantics match N spawn() calls
        in submission order; accepts coroutines, coroutine functions
        and plain callables like spawn."""
        fibers: List[Fiber] = []
        if not works:
            return fibers
        if not self._started:
            self.start()
        g = _tls.group
        local = g is not None and g.control is self
        tgt = g if local else self.groups[
            fast_rand_less_than(self.concurrency)]
        for fn in works:
            if inspect.iscoroutine(fn):
                coro = fn
            elif inspect.iscoroutinefunction(fn):
                coro = fn()
            else:
                async def _runner(fn=fn):
                    r = fn()
                    if inspect.isawaitable(r):
                        r = await r
                    return r
                coro = _runner()
            fiber = Fiber(coro, self, name=name)
            self.nfibers.add(1)
            self.nfibers_created.add(1)
            fiber._ready_ns = time.perf_counter_ns()
            fiber.state = FIBER_STATE_READY
            if local:
                tgt.rq.append(fiber)       # owner-LIFO, like schedule()
            else:
                tgt.remote_rq.append(fiber)
            fibers.append(fiber)
        self.runq_peak.update(
            len(tgt.rq) + len(tgt.remote_rq) + len(tgt.bound_rq))
        self.parking_lot.signal(len(fibers))
        return fibers

    def run_inline(self, fn: Callable | Any, *args, name: str = "",
                   max_depth: int = 8, **kwargs) -> Fiber:
        """Step a new fiber on the CALLING thread until it completes or
        first suspends — the reference's process-in-place discipline
        (input_messenger.cpp:183 runs the last message in the receiving
        context) generalized: a handler chain that never blocks pays
        zero fiber wakes and zero cross-thread handoffs. On the first
        real suspension the remainder parks exactly like a spawned
        fiber (the awaitable registers it for a normal wake).

        ``max_depth`` bounds same-thread nesting (an inline handler
        whose write triggers the peer's inline processing recurses on
        this stack); past the cap we fall back to spawn."""
        depth = _tls.inline_depth
        if depth >= max_depth:
            return self.spawn(fn, *args, name=name, **kwargs)
        if inspect.iscoroutine(fn):
            coro = fn
        elif inspect.iscoroutinefunction(fn):
            coro = fn(*args, **kwargs)
        else:
            return self.spawn(fn, *args, name=name, **kwargs)
        fiber = Fiber(coro, self, name=name)
        self.nfibers.add(1)
        self.nfibers_created.add(1)
        if not self._started:
            # a suspension hands the continuation to the workers
            self.start()
        group = _tls.group or self.groups[0]
        _tls.inline_depth = depth + 1
        try:
            self._step(group, fiber)
        finally:
            _tls.inline_depth = depth
        return fiber

    def schedule(self, fiber: Fiber, resume_value: Any, to_tail: bool = False) -> None:
        """Make a ready fiber runnable (ready_to_run / ready_to_run_remote)."""
        fiber._resume_value = resume_value
        fiber._ready_ns = time.perf_counter_ns()
        fiber.state = FIBER_STATE_READY
        if fiber.bound_group is not None:
            g = self.groups[fiber.bound_group]
            g.bound_rq.append(fiber)
            self.runq_peak.update(
                len(g.rq) + len(g.remote_rq) + len(g.bound_rq))
            self.parking_lot.signal(1)
            return
        g = _tls.group
        if g is not None and g.control is self:
            if to_tail:
                g.rq.appendleft(fiber)    # back of the owner's LIFO
            else:
                g.rq.append(fiber)        # Chase-Lev bottom: owner runs it next
        else:
            # remote push: spread by random target group
            g = self.groups[fast_rand_less_than(self.concurrency)]
            g.remote_rq.append(fiber)
        # saturation high-water mark: the depth of the queue this fiber
        # just joined (cheap: three lens + a thread-local max update)
        self.runq_peak.update(
            len(g.rq) + len(g.remote_rq) + len(g.bound_rq))
        self.parking_lot.signal(1)

    # ------------------------------------------------------------- worker
    def _worker(self, group: TaskGroup) -> None:
        from brpc_tpu.fiber import worker_module
        thread_cpu.set_role("worker")
        _tls.group = group
        worker_module.notify_start(group.index)
        while not self._stop:
            # co-scheduled engine work first (the fork's EloqModule hook:
            # TaskGroup::ProcessModulesTask runs before wait_task pops)
            ran_module = worker_module.process_modules(group.index) \
                if worker_module.has_modules() else False
            fiber = group.pop_local()
            if fiber is None:
                fiber = self._steal(group)
            if fiber is not None:
                self._step(group, fiber)
                continue
            if ran_module:
                continue          # engine made progress: don't park yet
            expected = self.parking_lot.signal_count()
            # re-check after reading the signal count (no lost wakeups)
            fiber = group.pop_local() or self._steal(group)
            if fiber is not None:
                self._step(group, fiber)
                continue
            self.parking_lot.wait(expected, timeout=0.5)
        worker_module.notify_stop(group.index)
        _tls.group = None

    def _steal(self, group: TaskGroup) -> Optional[Fiber]:
        n = self.concurrency
        offset = fast_rand_less_than(n)
        for i in range(n):
            g = self.groups[(offset + i) % n]
            if g is group:
                continue
            f = g.steal_from()
            if f is not None:
                group.nsteals += 1
                return f
        return None

    def _step(self, group: TaskGroup, fiber: Fiber) -> None:
        """Advance the fiber one leg: run until it finishes or awaits."""
        cell = _tls.cell
        prev = cell.current
        cell.current = fiber
        fiber.state = FIBER_STATE_RUNNING
        ready_ns = fiber._ready_ns
        group.nswitches += 1
        if ready_ns:
            # wake-to-run latency: schedule() -> this step (the p99 the
            # event-driven wake path is accountable for; /vars
            # fiber_wake). Sampled 1-in-16 WAKES per group — counting
            # wakes, not switches, so the sample can't systematically
            # miss (a switch-indexed sample only fired when the 16th
            # switch happened to be a wake), and the FIRST wake records
            # so the recorder is visible as soon as any fiber ran.
            group.nwakes += 1
            if (group.nwakes & 0xF) == 1:
                _wake_recorder().record(
                    (time.perf_counter_ns() - ready_ns) / 1e3)
        fiber._ready_ns = 0
        t0 = time.perf_counter_ns()
        try:
            token = fiber.coro.send(fiber._resume_value)
        except StopIteration as e:
            self.busy_ns.add(time.perf_counter_ns() - t0)
            cell.current = prev
            fiber._finish(e.value, None)
            return
        except BaseException as e:
            self.busy_ns.add(time.perf_counter_ns() - t0)
            cell.current = prev
            fiber._finish(None, e)
            return
        self.busy_ns.add(time.perf_counter_ns() - t0)
        cell.current = prev
        fiber.state = FIBER_STATE_SUSPENDED
        fiber._resume_value = None
        if token is None:
            # bare `yield` inside legacy generators: treat as yield_now
            self.schedule(fiber, None, to_tail=True)
        else:
            token._register(fiber)

    # -------------------------------------------------------------- misc
    def on_fiber_error(self, fiber: Fiber, exc: BaseException) -> None:
        for h in self._error_handlers:
            try:
                h(fiber, exc)
            except Exception:
                pass
        if not self._error_handlers:
            import logging
            logging.getLogger("brpc_tpu.fiber").exception(
                "fiber %r crashed", fiber.name, exc_info=exc)

    def add_error_handler(self, h: Callable) -> None:
        self._error_handlers.append(h)

    def runqueue_depth(self) -> int:
        """Instantaneous ready-but-not-running fiber count across all
        groups — nonzero under load means requests are waiting for a
        worker (the scheduler-side cause of span queue_us)."""
        return sum(len(g.rq) + len(g.remote_rq) + len(g.bound_rq)
                   for g in self.groups)

    def _saturation_windows(self):
        """Windowed views over busy_ns / runq_peak, created on first
        use (a Window registers with the background sampler — don't
        start that thread for TaskControls nobody inspects)."""
        if self._busy_window is None:
            from brpc_tpu.bvar.window import PerSecond, Window
            self._busy_window = PerSecond(self.busy_ns, 10)
            self._runq_peak_window = Window(self.runq_peak, 10)
        return self._busy_window, self._runq_peak_window

    def worker_busy_fraction(self) -> float:
        """Fraction of worker capacity spent stepping fibers over the
        sampler window: ~1.0 means every worker is saturated and new
        work queues (span queue_us inflates); ~0 means latency lives
        elsewhere (network, handler awaits)."""
        busy, _ = self._saturation_windows()
        per_s = busy.get_value() or 0.0
        if self.concurrency <= 0:
            return 0.0
        return min(1.0, per_s / 1e9 / self.concurrency)

    def saturation_snapshot(self) -> dict:
        """The /status saturation pane's scheduler half."""
        _, peak = self._saturation_windows()
        return {
            "workers": self.concurrency,
            "runqueue_depth": self.runqueue_depth(),
            "runqueue_peak_10s": peak.get_value() or 0,
            "worker_busy_fraction": round(self.worker_busy_fraction(), 4),
        }

    def expose_vars(self, prefix: str = "fiber") -> None:
        self.nfibers.expose(f"{prefix}_count")
        self.nfibers_created.expose(f"{prefix}_created")
        PassiveStatus(lambda: self.concurrency).expose(f"{prefix}_worker_count")
        PassiveStatus(lambda: sum(g.nswitches for g in self.groups)).expose(
            f"{prefix}_switch_count")
        PassiveStatus(lambda: sum(g.nsteals for g in self.groups)).expose(
            f"{prefix}_steal_count")
        # saturation trio (windowed where a point sample would alias):
        # depth is a live gauge; the peak and busy fraction read the
        # sampler's last-10s window (zero-defaulted: an empty window
        # must still render on /vars and the prometheus dump)
        PassiveStatus(self.runqueue_depth).expose(
            f"{prefix}_runqueue_depth")
        _, peak = self._saturation_windows()
        PassiveStatus(lambda: peak.get_value() or 0).expose(
            f"{prefix}_runqueue_peak_10s")
        PassiveStatus(self.worker_busy_fraction).expose(
            f"{prefix}_worker_busy_fraction")


# ----------------------------------------------------------------- globals
_global_control: Optional[TaskControl] = None
_global_lock = threading.Lock()


def global_control() -> TaskControl:
    global _global_control
    if _global_control is None:
        with _global_lock:
            if _global_control is None:
                _global_control = TaskControl()
    return _global_control


def set_concurrency(n: int) -> None:
    """bthread_setconcurrency: must run before the first spawn."""
    global _global_control
    with _global_lock:
        if _global_control is not None and _global_control._started:
            raise RuntimeError("fiber workers already started")
        _global_control = TaskControl(concurrency=n)


def spawn(fn, *args, **kwargs) -> Fiber:
    return global_control().spawn(fn, *args, **kwargs)


def spawn_urgent(fn, *args, **kwargs) -> Fiber:
    return global_control().spawn(fn, *args, urgent=True, **kwargs)


def _postfork_reset() -> None:
    """Fork hygiene: worker pthreads exist only in the parent; the
    inherited TaskControl believes it is _started but owns no threads,
    so every post-fork spawn would queue forever. Drop it (and the
    wake recorder, whose Window rides the parent's sampler) so the
    first post-fork spawn builds a fresh control with live workers."""
    global _global_control, _global_lock, _wake_rec, _wake_rec_lock
    _global_control = None
    _global_lock = threading.Lock()
    _wake_rec = None
    _wake_rec_lock = threading.Lock()
    # the cell registry names PARENT threads; only the forking thread
    # survives — re-register its own cell (its thread-local state
    # itself survives the fork)
    _cell_by_thread.clear()
    _cell_by_thread[threading.get_ident()] = _tls.cell


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("fiber.scheduler", _postfork_reset)


def _fiber_census() -> dict:
    """Resource census: live fiber count off the cheap Adder (the
    gc-walk in fiber.stacks is for on-demand stack dumps only). Peeks —
    a census scrape must not build a TaskControl."""
    c = _global_control
    if c is None:
        return {"count": 0, "workers": 0}
    return {"count": max(0, int(c.nfibers.get_value() or 0)),
            "workers": c.concurrency,
            "runqueue_depth": c.runqueue_depth()}


from brpc_tpu.butil import resource_census as _census  # noqa: E402
#   (census registration ships with the singleton it measures)

_census.register("fibers", _fiber_census)
