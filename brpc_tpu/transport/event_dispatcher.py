"""EventDispatcher: readiness poller for fd-based transports
(brpc/event_dispatcher.h:32 — epoll/kqueue there, selectors here).

One thread runs the selector and fires the callbacks on it. A socket's
read callback is its whole input pass (``Socket._on_readable_event`` ->
``_process_input_entry``): it reads until the fd is drained (for
``ici://`` that pumps the lane too), cuts the frames and processes each
message in place until it first suspends: a request up to its hop to a
fiber worker, a response through ``_fill_response``, the completion
hooks and a ``done=`` callback with whatever that issues. So one slow
callback holds every other socket's input (the stall watchdog below);
the reference's edge-trigger handlers only bump an atomic and maybe
spawn a bthread. Write-readiness registrations are one-shot (epollout
for blocked writers).

**The tick from inside.** The loop publishes three readings of
``time.monotonic_ns`` as plain attributes it alone writes: ``sleep_ns``
(it last finished what it had to do and went to ``select``), ``tick_ns``
(the first reading after ``select`` returned with something to fire)
and, only while spans record (``rpc/span.recording()``, asked once a
wake), ``callback_ns`` (read before each callback; 0 outside one).
A frame cut on the loop's thread inside a callback copies the three
(``wake_stamps``), and its span carries them next to the cut's own
stamp. While spans record the loop also sums its wall time: asleep and
awake (its ticks, first reading to last, and the duties that ran), and
of awake what the input pass spent reading, cutting and processing
(``Socket``, ``InputMessenger`` and ``tpu_std`` call ``lap``):
``loop_sums``. The sums live on the readings the loop takes anyway (a
tick's first and last) plus two a pass (the cut's start, the
processing's start) and one a callback after a tick's first. Spans off,
nothing of this reads a clock.
"""

from __future__ import annotations

import selectors
import socket as pysocket
import sys
import threading
import time
from typing import Callable, Dict, Hashable, Optional, Tuple

from brpc_tpu.butil import thread_cpu
from brpc_tpu.bvar.reducer import Adder, Maxer, PassiveStatus

# event-loop stall instrumentation (the flight recorder's watchdog
# half): the longest time one wakeup's callback batch held the event
# thread, over the sampler's 10s window. The dispatcher stamps tick
# start/end (two clock reads per non-empty batch); completed ticks
# update the Maxer here, in-progress ticks are caught by the flight
# recorder's sampler thread (note_stall), which sees a handler
# monopolizing the event thread BEFORE the tick ever completes.
_tick_ms_max = Maxer()
# ticks that overran the dispatcher_stall_ms budget (flight_recorder
# annotates the serving rpcz span when it catches one live)
nstalls = Adder()
_stall_win = None
_stall_win_lock = threading.Lock()


def _stall_window():
    """Windowed view over the tick-duration Maxer, created on first
    scrape (a Window registers with the background sampler thread).
    Locked double-check: a LOSING racer's Window would stay registered
    with the sampler forever and drain the delta-mode Maxer via
    reset() each tick, zeroing the kept window's samples."""
    global _stall_win
    if _stall_win is None:
        with _stall_win_lock:
            if _stall_win is None:
                from brpc_tpu.bvar.window import Window
                _stall_win = Window(_tick_ms_max, 10)
    return _stall_win


def stall_ms_max_10s() -> float:
    """Max tick duration over the sampler window, INCLUDING the
    current not-yet-sampled tick value (the bvar sampler snapshots
    1/s; a stall must be visible the moment it is recorded, not up to
    a second later)."""
    win = _stall_window().get_value() or 0.0
    live = _tick_ms_max.get_value() or 0.0
    return round(max(win, live), 3)


LOOP_SUMS = ("dispatcher_loop_us", "dispatcher_awake_us",
             "dispatcher_read_us", "dispatcher_cut_us",
             "dispatcher_process_us")

_stall_var = PassiveStatus(stall_ms_max_10s)
_quiet_wakes_var = PassiveStatus(lambda: dispatcher_quiet_wakes())
_loop_sum_vars = {name: PassiveStatus(lambda name=name: loop_sums()[name])
                  for name in LOOP_SUMS}


def expose_stall_vars() -> None:
    """(Re-)expose the watchdog bvars — called at import and again
    from Server.start, surviving a test fixture's unexpose_all like
    the other socket/scheduler counters."""
    nstalls.expose("dispatcher_stalls")
    _stall_var.expose("dispatcher_stall_ms_max_10s")
    _quiet_wakes_var.expose("dispatcher_quiet_wakes")
    for name, var in _loop_sum_vars.items():
        var.expose(name)


expose_stall_vars()


def note_stall(ms: float) -> None:
    """Record an in-progress tick overrun observed by the sampler."""
    _tick_ms_max.update(ms)


# the phases of the loop's awake time that are summed (``lap``): the
# input pass's read, cut and process; REST is a callback that has not
# said yet what it does (a writable callback never does), and the
# remainder of awake (those, the duties) is never stamped:
# awake - read - cut - process
REST, READ, CUT, PROCESS = range(4)

# the dispatcher whose loop is inside a tick while spans record, else
# None: the ONE test the cut and the input pass make with spans off
stamping: Optional["EventDispatcher"] = None


def _recording() -> bool:
    """``rpc.span.recording`` once that module is loaded (rpc imports
    this one; a process that never loaded it records no span). Looked
    up through sys.modules so the loop's thread imports nothing."""
    global _recording
    fn = getattr(sys.modules.get("brpc_tpu.rpc.span"), "recording", None)
    if fn is None:
        return False
    _recording = fn
    return fn()


def wake_stamps() -> Optional[Tuple[int, int, int]]:
    """``(sleep_ns, tick_ns, callback_ns)`` of the tick whose callback
    the calling thread is in, while spans record; else None: a frame a
    plucking joiner cut on its own thread, or a fiber's pass, has no
    tick."""
    d = stamping
    if d is None or threading.get_ident() != d._loop_ident:
        return None
    return d.sleep_ns, d.tick_ns, d.callback_ns


# the longest the loop sleeps in one select()
_MAX_SLEEP_S = 0.5
# _sleep_until while the loop sleeps with no duty's deadline in its
# timeout: a duty armed from another thread has to wake it
_NO_DEADLINE = float("inf")


class EventDispatcher:
    def __init__(self, name: str = "event_dispatcher"):
        self._selector = selectors.DefaultSelector()
        self._lock = threading.Lock()
        # fd -> [on_readable, on_writable(one-shot), armed_read_mask,
        #        oneshot_read]
        self._handlers: Dict[int, list] = {}
        self._wakeup_r, self._wakeup_w = pysocket.socketpair()
        self._wakeup_r.setblocking(False)
        self._selector.register(self._wakeup_r, selectors.EVENT_READ, None)
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._name = name
        # tick telemetry for the stall watchdog: _tick_start_ns is
        # nonzero exactly while this wakeup's callback batch runs on
        # the event thread; _tick_seq disambiguates ticks so the
        # watchdog annotates each overrun once
        self._tick_start_ns = 0
        self._tick_seq = 0
        # the tick from inside (module docstring): the three stamps a
        # cut copies, and the sums, all written by the loop alone
        self.sleep_ns = 0
        self.tick_ns = 0
        self.callback_ns = 0
        self._loop_ns = 0
        self._awake_ns = 0
        self._phase_ns = [0, 0, 0, 0]   # by phase; REST's is never read
        self._phase = REST
        self._lap_ns = 0
        # end of the last loop iteration that was summed; 0 where spans
        # did not record then, so its sleep is of unknown length
        self._summed_to_ns = 0
        # epoll interest changes take effect while another thread sits
        # in epoll_wait — pause/resume need no wakeup-pipe kick there
        # (one write + one dispatcher wake per call otherwise; the
        # pluck lane pays that pair per sync RPC). Select/poll-backed
        # selectors snapshot their fd set per call and DO need the kick.
        self._rearm_needs_wakeup = not isinstance(
            self._selector, getattr(selectors, "EpollSelector", ()))
        # quiet duties: key -> (deadline, callback), work a consumer
        # owes once its deadline passes (the lane's idle ACK), run on
        # the event thread at the end of the first tick after the
        # deadline or, when the loop has nothing else to do, by a
        # select() whose timeout is the nearest deadline. _duties and
        # _duty_next (the nearest deadline) under _lock. _sleep_until,
        # written by the loop alone: monotonic seconds at which the
        # select() it sits in ends at the latest, 0.0 while it is not
        # in one (it looks at the duties before it sleeps again)
        self._duties: Dict[Hashable, Tuple[float, Callable[[], None]]] = {}
        self._duty_next = _NO_DEADLINE
        self._sleep_until = 0.0
        self._loop_ident: Optional[int] = None
        self._quiet_wakes = 0

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(target=self._run, name=self._name,
                                            daemon=True)
            self._thread.start()

    def _wakeup(self):
        # registry changes made FROM the dispatcher thread (inline
        # processing re-arming reads mid-event) need no pipe write: the
        # loop re-enters select() right after the callback returns
        if threading.current_thread() is self._thread:
            return
        try:
            self._wakeup_w.send(b"x")
        except (BlockingIOError, OSError):
            pass

    def add_consumer(self, fd: int, on_readable: Callable[[], None],
                     oneshot_read: bool = False) -> None:
        """Register read-readiness callbacks for fd.

        ``oneshot_read=True`` gives edge-trigger-style semantics: after a
        read event fires, read interest is DISARMED until the consumer
        calls resume_read(fd) (typically once its drain hits EAGAIN).
        Level-triggered polling would otherwise spin the dispatcher for
        the whole time a drain fiber works through a bulk transfer —
        the reason the reference uses EPOLLET (event_dispatcher.h:32)."""
        with self._lock:
            self._handlers[fd] = [on_readable, None, selectors.EVENT_READ,
                                  oneshot_read]
            try:
                self._selector.register(fd, selectors.EVENT_READ, fd)
            except KeyError:
                self._selector.modify(fd, selectors.EVENT_READ, fd)
            self._ensure_thread()
        self._wakeup()

    def pause_read(self, fd: int) -> None:
        """Drop read interest until resume_read (level-triggered
        consumers use this for busy periods, so pending data doesn't
        spin the select loop while a handler is parked)."""
        with self._lock:
            h = self._handlers.get(fd)
            if h is None or not (h[2] & selectors.EVENT_READ):
                return
            h[2] &= ~selectors.EVENT_READ
            mask = h[2] | (selectors.EVENT_WRITE if h[1] else 0)
            try:
                if mask:
                    self._selector.modify(fd, mask, fd)
                else:
                    self._selector.unregister(fd)
            except (KeyError, ValueError, OSError):
                pass
        if self._rearm_needs_wakeup:
            self._wakeup()

    def resume_read(self, fd: int) -> None:
        """Re-arm read interest after a one-shot read fire (safe to call
        when already armed or after remove_consumer)."""
        with self._lock:
            h = self._handlers.get(fd)
            if h is None or h[2] & selectors.EVENT_READ:
                return
            h[2] |= selectors.EVENT_READ
            mask = h[2] | (selectors.EVENT_WRITE if h[1] else 0)
            try:
                self._selector.modify(fd, mask, fd)
            except (KeyError, ValueError, OSError):
                try:
                    self._selector.register(fd, mask, fd)
                except (KeyError, ValueError, OSError):
                    return
        if self._rearm_needs_wakeup:
            self._wakeup()

    def request_writable(self, fd: int, on_writable: Callable[[], None]) -> None:
        """One-shot write-readiness callback (the epollout dance the
        reference does for connecting/blocked sockets)."""
        with self._lock:
            h = self._handlers.get(fd)
            if h is None:
                self._handlers[fd] = [None, on_writable, 0, False]
                self._selector.register(fd, selectors.EVENT_WRITE, fd)
            else:
                h[1] = on_writable
                mask = h[2] | selectors.EVENT_WRITE
                try:
                    self._selector.modify(fd, mask, fd)
                except KeyError:
                    self._selector.register(fd, mask, fd)
            self._ensure_thread()
        self._wakeup()

    def remove_consumer(self, fd: int) -> None:
        with self._lock:
            self._handlers.pop(fd, None)
            try:
                self._selector.unregister(fd)
            except (KeyError, ValueError, OSError):
                pass
        self._wakeup()

    def arm_quiet_duty(self, key: Hashable, deadline: float,
                       callback: Callable[[], None]) -> None:
        """Have ``callback`` run once on the event thread, at the end of
        the first tick after ``deadline`` (time.monotonic() seconds), or
        by a select() timeout when no fd event comes before it. One duty
        a key: arming again replaces it. Armed from the event thread
        (inside an fd callback) this is a dict store: the loop looks at
        the duties before it sleeps. From another thread the loop is
        woken only if the select() it sits in would outlast the
        deadline."""
        on_loop = threading.get_ident() == self._loop_ident
        with self._lock:
            self._duties[key] = (deadline, callback)
            if deadline < self._duty_next:
                self._duty_next = deadline
            if on_loop:
                return
            wake = deadline < self._sleep_until
            self._ensure_thread()     # a loop that starts now sees it
        if wake:
            self._wakeup()

    def drop_quiet_duty(self, key: Hashable) -> None:
        """Forget ``key``'s duty (its owner closed). A loop that sleeps
        for its deadline wakes once for nothing."""
        with self._lock:
            self._duties.pop(key, None)

    def _sleep_for_duties(self) -> float:
        """The next select()'s timeout, published as _sleep_until in the
        same hold that read the duties: an arm from another thread
        either came first and is counted here, or reads what this
        wrote."""
        with self._lock:
            now = time.monotonic()
            timeout = min(_MAX_SLEEP_S, max(0.0, self._duty_next - now))
            self._sleep_until = now + timeout
        return timeout

    def _run_due_duties(self, rec: bool = False) -> None:
        now = time.monotonic()
        if now < self._duty_next:
            return
        with self._lock:
            due = [(key, cb) for key, (deadline, cb) in self._duties.items()
                   if deadline <= now]
            for key, _ in due:
                del self._duties[key]
            self._duty_next = min(
                (deadline for deadline, _ in self._duties.values()),
                default=_NO_DEADLINE)
        for _, cb in due:
            try:
                cb()
            except Exception:
                import logging
                logging.getLogger("brpc_tpu.transport").exception(
                    "quiet duty failed")
        if rec and due:
            # duties ran (the lane's bare ACK is a write): awake time,
            # and the loop goes to sleep after them
            self._sum_awake(int(now * 1e9), time.monotonic_ns())

    def _run(self):
        thread_cpu.set_role("dispatcher")
        self._loop_ident = threading.get_ident()
        while not self._stop:
            # written BEFORE the test of _duties: an arm from another
            # thread stores its duty and then reads this, so it either
            # stored in time for the test or sees that it has to wake us
            self._sleep_until = _NO_DEADLINE
            timeout = self._sleep_for_duties() if self._duties \
                else _MAX_SLEEP_S
            try:
                events = self._selector.select(timeout=timeout)
            except OSError:
                continue
            self._sleep_until = 0.0
            rec = _recording()
            if not rec and self._summed_to_ns:
                self._summed_to_ns = 0      # the next sleep: unknown
            if not events and timeout < _MAX_SLEEP_S:
                self._quiet_wakes += 1
            # resolve the WHOLE event batch under one lock hold (a
            # deep wakeup used to pay one acquire/release per ready
            # fd), then fire callbacks outside the lock in event order
            fired = []
            with self._lock:
                for key, mask in events:
                    if key.data is None:  # wakeup pipe
                        try:
                            while self._wakeup_r.recv(4096):
                                pass
                        except (BlockingIOError, OSError):
                            pass
                        continue
                    fd = key.data
                    h = self._handlers.get(fd)
                    if h is None:
                        continue
                    on_readable = on_writable = None
                    rearm = False
                    if mask & selectors.EVENT_READ:
                        on_readable = h[0]
                        if h[3]:              # one-shot read: disarm
                            h[2] &= ~selectors.EVENT_READ
                            rearm = True
                    if mask & selectors.EVENT_WRITE:
                        on_writable, h[1] = h[1], None  # one-shot
                        rearm = True
                    if rearm:
                        new_mask = (h[2] | (selectors.EVENT_WRITE
                                            if h[1] else 0))
                        try:
                            if new_mask:
                                self._selector.modify(fd, new_mask, fd)
                            else:
                                # keep the handler: resume_read /
                                # request_writable re-register later
                                self._selector.unregister(fd)
                                if h[0] is None:
                                    del self._handlers[fd]
                        except (KeyError, ValueError, OSError):
                            pass
                    if on_readable is not None:
                        fired.append((fd, on_readable))
                    if on_writable is not None:
                        fired.append((fd, on_writable))
            if fired:
                self._fire(fired, rec)
            if self._duties:
                self._run_due_duties(rec)

    def _fire(self, fired, rec: bool = False) -> None:
        """One tick: this wakeup's fd callbacks, in event order. ``rec``:
        spans record, so each callback's start is stamped."""
        global stamping
        self._tick_seq += 1
        self.tick_ns = self._tick_start_ns = now = time.monotonic_ns()
        if rec:
            stamping = self
        try:
            for fd, cb in fired:
                if rec:
                    # the tick's first callback begins with the tick;
                    # a later one's reading also ends the lap the one
                    # before it left open
                    if self.callback_ns:
                        now = time.monotonic_ns()
                    self._begin_callback(now)
                try:
                    cb()
                except Exception:
                    import logging
                    logging.getLogger("brpc_tpu.transport").exception(
                        "event callback failed for fd %d", fd)
        finally:
            # the tick's end: where no duty follows, the loop sleeps now
            now = time.monotonic_ns()
            if rec:
                stamping = None
                self._begin_callback(now)       # ends the last lap
                self.callback_ns = 0
                self._sum_awake(self._tick_start_ns, now)
            self.sleep_ns = now
            dur_ms = (now - self._tick_start_ns) / 1e6
            self._tick_start_ns = 0
            if dur_ms > 1.0:
                # sub-ms ticks are the normal case and not worth a
                # Maxer lock; anything longer feeds the stall gauge
                _tick_ms_max.update(dur_ms)

    def _begin_callback(self, now: int) -> None:
        """``now`` starts a callback (or ends the tick): the lap the
        callback before it left open ends here, on a reading the loop
        takes anyway."""
        # graftlint: disable=guarded-by -- the lap state is the loop's
        # thread's alone: here it is the loop, and lap() returns at
        # once on any other thread (an ident test the rule cannot
        # see); plain ints, and loop_sums() reads whole values
        self._phase_ns[self._phase] += now - self._lap_ns
        # graftlint: disable=guarded-by -- as above: the loop's alone
        self.callback_ns = self._lap_ns = now
        # graftlint: disable=guarded-by -- as above: the loop's alone
        self._phase = REST

    def _sum_awake(self, start_ns: int, end_ns: int) -> None:
        """[start_ns, end_ns] was awake time; the loop slept from the
        end of what was summed last, where spans recorded then too (a
        sleep entered with spans off is of unknown length)."""
        self._awake_ns += end_ns - start_ns
        self._loop_ns += end_ns - (self._summed_to_ns or start_ns)
        self._summed_to_ns = self.sleep_ns = end_ns

    def lap(self, phase: int) -> None:
        """The input pass enters ``phase``: the time since the last lap
        goes to the phase that was running, and ``phase`` runs on until
        the next lap, the next callback or the tick's end. A callback's
        first lap (out of REST) reads no clock: its phase began with the
        callback; nor does a lap into the phase that runs. Only on the
        loop's thread (a pass on a fiber worker or a
        plucking joiner's, met while ``stamping`` is set, is not the
        loop's time)."""
        if phase == self._phase \
                or threading.get_ident() != self._loop_ident:
            return
        if self._phase != REST:
            now = time.monotonic_ns()
            self._phase_ns[self._phase] += now - self._lap_ns
            self._lap_ns = now
        self._phase = phase

    def stop(self):
        self._stop = True
        self._wakeup()


_global: Optional[EventDispatcher] = None
_glock = threading.Lock()


def global_dispatcher() -> EventDispatcher:
    global _global
    if _global is None:
        with _glock:
            if _global is None:
                _global = EventDispatcher()
    return _global


def peek_dispatcher() -> Optional[EventDispatcher]:
    """The global dispatcher if one exists — watchdogs must observe,
    never instantiate (a fresh dispatcher has nothing to stall)."""
    return _global


def dispatcher_ticks() -> int:
    """Wakeups of this process's event thread that fired at least one
    callback, since the dispatcher was made (syscall_stats.snapshot
    carries it; a level-triggered fd nobody pauses shows here)."""
    d = _global
    return d._tick_seq if d is not None else 0


def dispatcher_quiet_wakes() -> int:
    """select() timeouts the event thread took for a quiet duty's
    deadline (no fd event came first): the wakes the duties cost."""
    d = _global
    return d._quiet_wakes if d is not None else 0


def loop_sums() -> dict:
    """The event thread's wall time in us, summed only while spans
    record: ``dispatcher_loop_us`` (asleep + awake), ``dispatcher_awake_us``
    (each tick from its first reading to its last, and the duties that
    ran) and, of awake, what the input pass spent in ``_drain_readable``
    (read: from the callback's start), in the protocol's parse or scan
    (cut) and in ``process`` (from its start to the callback's end, or
    to where the pass cuts or reads on). Plain ints the loop alone
    writes."""
    d = _global
    if d is None:
        return dict.fromkeys(LOOP_SUMS, 0)
    phase = d._phase_ns
    return dict(zip(LOOP_SUMS, (
        d._loop_ns // 1000, d._awake_ns // 1000, phase[READ] // 1000,
        phase[CUT] // 1000, phase[PROCESS] // 1000)))


def _postfork_reset() -> None:
    """Fork hygiene: the dispatcher thread exists only in the parent,
    and the inherited epoll fd is the parent's kernel object — any
    EPOLL_CTL from the child would corrupt the parent's poll set.
    Abandon the instance (closing only the child's fd copies; close(2)
    never mutates the shared interest list) so the first post-fork
    consumer builds a private dispatcher with its own thread."""
    global _global, _glock, _stall_win, _stall_win_lock, stamping
    d, _global = _global, None
    stamping = None      # the parent's loop may have been inside a tick
    _glock = threading.Lock()
    _stall_win = None    # the Window rode the parent's sampler series
    _stall_win_lock = threading.Lock()
    if d is not None:
        d._stop = True
        try:
            d._selector.close()
        except Exception:
            pass
        for s in (d._wakeup_r, d._wakeup_w):
            try:
                s.close()
            except Exception:
                pass


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("transport.event_dispatcher", _postfork_reset)
