"""Flight recorder: continuous fiber-aware profiling + event-loop
stall watchdog on ONE dedicated sampler thread.

The reference's builtin layer keeps gperftools CPU/contention profilers
a URL away (/hotspots, hotspots_service.cpp); production incidents need
the profile of the LAST minute, not the next one. This module keeps a
low-rate sampling profiler always on:

  * a sampler thread (default 20 Hz, ``continuous_profiler_hz``) walks
    ``sys._current_frames()`` and attributes each sample to the RPC
    method the sampled thread's fiber is serving — via the scheduler's
    per-thread current-fiber cell (fiber/scheduler.py) and the serving
    controller's fiber-local (rpc/server_dispatch.py). Idle threads
    (parked workers, the selector wait) are classified by leaf frame
    and counted but not folded, so flamegraphs show WORK;
  * samples accumulate into a ring of windows (default 6 x 10 s,
    ``continuous_profiler_windows`` x ``continuous_profiler_window_s``)
    served by ``/hotspots?mode=continuous`` as folded stacks, SVG
    flamegraphs, or a per-method attribution table; ``diff=1`` shows
    what changed between the newest two windows. Shard groups merge the
    per-shard recorder states through the PR 5 dump/aggregator pattern;
  * the same thread is the event-loop WATCHDOG: the dispatcher stamps
    each callback batch (transport/event_dispatcher.py), the sampler
    flags a tick that overruns ``dispatcher_stall_ms`` — stall max into
    ``dispatcher_stall_ms_max_10s``, an annotation into the rpcz span
    of the request currently monopolizing the event thread;
  * ON-DEMAND profiles (/hotspots classic mode) run on this thread too:
    the HTTP handler fiber parks on an event instead of burning a
    worker for the sample window, and a second concurrent request is
    refused (503) instead of queueing.

Fork-safe: the postfork registry drops the recorder (the thread exists
only in the parent); a forked shard's ``Server.start`` calls
``global_recorder().ensure_running()`` and gets a private sampler with
empty windows.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional, Tuple

from brpc_tpu.butil.flags import define_flag, flag
# bound at module load, NOT inside the sampler's attribution path: an
# import there opens the module file ON THE SAMPLER THREAD at sample
# time — a transient fd that can appear/disappear mid-sample in
# fd-exhaustion scenarios (the EMFILE accept-backoff test lost its
# "no free descriptors" precondition to exactly that open/close)
from brpc_tpu.fiber import worker_module as _worker_module
# same rule for the device-lane label registry: the sampler reads
# device-thread labels (poller pump, PjRt waiter threads) through this
# binding — transport/device_stats has no import cycle with builtin,
# so it binds at load like worker_module
from brpc_tpu.transport import device_stats as _device_stats

# the remaining sampler-path collaborators are import-CYCLIC with this
# module at load time (scheduler/server_dispatch/event_dispatcher all
# reach back into builtin), so they are bound by _bind_sampler_imports
# from ensure_running — on the CALLER thread, before the sampler thread
# exists. Sampler-reachable code must only ever read these globals
# (enforced by the sampler-no-lazy-import graftlint rule).
_sched = None                  # brpc_tpu.fiber.scheduler
_thread_current_fiber = None   # scheduler.thread_current_fiber
_serving_cntl = None           # server_dispatch._serving_cntl
_ed = None                     # brpc_tpu.transport.event_dispatcher


def _bind_sampler_imports() -> None:
    """One-time import binding for everything the sampler thread
    touches; runs on the thread that STARTS the sampler."""
    global _sched, _thread_current_fiber, _serving_cntl, _ed
    if _ed is not None:
        return
    from brpc_tpu.fiber import scheduler as sched
    from brpc_tpu.fiber.scheduler import thread_current_fiber as tcf
    from brpc_tpu.rpc.server_dispatch import _serving_cntl as sc
    from brpc_tpu.transport import event_dispatcher as ed
    _sched, _thread_current_fiber, _serving_cntl, _ed = sched, tcf, sc, ed

define_flag("continuous_profiler_hz", 20,
            "continuous sampling profiler rate (samples/s across all "
            "threads); 0 disables the continuous profile only — "
            "on-demand /hotspots and the stall watchdog (50ms poll) "
            "keep working")
define_flag("continuous_profiler_window_s", 10,
            "seconds per continuous-profile window")
define_flag("continuous_profiler_windows", 6,
            "completed windows kept in the continuous-profile ring")
define_flag("dispatcher_stall_ms", 50.0,
            "an event-dispatcher callback batch holding the event "
            "thread longer than this is a stall: counted, and "
            "annotated into the rpcz span it is serving")

_MAX_STACK = 48

# frames whose ``self`` is the Socket being drained/processed: the
# connection-affinity attribution hook (see _attribute)
_SOCK_HINT_FRAMES = frozenset((
    "_drain_readable", "_process_input_entry", "_on_readable_event",
    "_drain_writes_inline", "_keep_write"))

# frames whose ``self`` is the IciConn doing device-lane work (pump /
# flush / descriptor staging / the pull itself): samples landing here
# with no serving context attribute to ``device:<peer>`` instead of
# vanishing into a thread-name leaf — /hotspots then shows the device
# lane's true CPU cost
_DEV_HINT_FRAMES = frozenset((
    "_pump", "_pump_locked", "_flush", "_stage_lane_frame",
    "take_device_payload", "write_device_payload"))

# frame-id strings are hot (every busy sample builds one per frame):
# cache keyed by the CODE OBJECT itself (hashable; holding it also
# pins its identity — an id()-keyed cache would serve a dead
# function's label after address reuse), bounded by the program's
# code locations
_frame_ids: Dict[tuple, str] = {}


def _frame_id(frame) -> str:
    code = frame.f_code
    key = (code, frame.f_lineno)
    s = _frame_ids.get(key)
    if s is None:
        if len(_frame_ids) > 65536:
            _frame_ids.clear()
        s = (f"{code.co_name} "
             f"({code.co_filename.rsplit('/', 1)[-1]}:{frame.f_lineno})")
        _frame_ids[key] = s
    return s


def _is_idle(frame) -> bool:
    """Leaf-frame idle classification: a thread parked in a condvar /
    event wait or the selector's poll is waiting, not working — its
    stack must not drown the flamegraph in parked workers."""
    code = frame.f_code
    name = code.co_name
    if name in ("wait", "_wait_for_tstate_lock", "select", "poll"):
        fn = code.co_filename
        return fn.endswith(("threading.py", "selectors.py"))
    return False


class _Window:
    """One continuous-profile window: folded busy stacks + per-label
    attribution counts."""

    __slots__ = ("start_mono", "end_mono", "nsamples", "nbusy",
                 "folded", "labels")

    def __init__(self, now: float):
        self.start_mono = now
        self.end_mono = 0.0
        self.nsamples = 0       # thread samples taken (busy + idle)
        self.nbusy = 0
        self.folded: Counter = Counter()
        self.labels: Counter = Counter()


class _Job:
    """One on-demand profile request, executed by the sampler thread."""

    __slots__ = ("deadline", "interval", "next_due", "on_done",
                 "leaves", "folded", "nsamples")

    def __init__(self, seconds: float, interval: float, on_done: Callable):
        now = time.monotonic()
        self.deadline = now + seconds
        self.interval = max(0.001, interval)
        self.next_due = now
        self.on_done = on_done
        self.leaves: Counter = Counter()
        self.folded: Counter = Counter()
        self.nsamples = 0


class FlightRecorder:
    def __init__(self):
        self._lock = threading.Lock()
        self._stop_ev = threading.Event()
        self._wake = threading.Event()      # nudges the loop off a sleep
        self._thread: Optional[threading.Thread] = None
        self._cur: Optional[_Window] = None
        self._done: deque = deque(maxlen=16)
        self._job: Optional[_Job] = None
        self._next_cont = 0.0
        self._counted_tick = -1     # the last tick counted as a stall
        self._annotated_tick = -1   # ... and the last whose span was told
        self.started_mono = time.monotonic()

    # ----------------------------------------------------------- lifecycle
    def ensure_running(self) -> None:
        _bind_sampler_imports()
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop_ev = threading.Event()
                self._wake = threading.Event()
                self._thread = threading.Thread(
                    target=self._loop, name="flight_recorder", daemon=True)
                self._thread.start()

    def stop(self) -> None:
        self._stop_ev.set()
        self._wake.set()

    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    # ----------------------------------------------------------- on-demand
    def request_profile(self, seconds: float, interval_s: float,
                        on_done: Callable) -> bool:
        """Schedule an on-demand profile on the sampler thread;
        ``on_done(leaves, folded, nsamples)`` fires from that thread at
        the deadline. False (caller answers 503) while another profile
        is running — on-demand profiling is one-at-a-time, like the
        reference's /hotspots."""
        with self._lock:
            if self._job is not None:
                return False
            self._job = _Job(seconds, interval_s, on_done)
        self.ensure_running()
        # nudge the loop off whatever sleep it is in (a low-hz
        # continuous sleep can be most of a second — the job's window
        # must not be spent waiting for it)
        self._wake.set()
        return True

    def profiling(self) -> bool:
        return self._job is not None

    # ------------------------------------------------------------ sampling
    def _sample_pass(self, include_cont: bool, job: Optional[_Job]) -> None:
        me = threading.get_ident()
        frames = sys._current_frames()
        # housekeeping piggybacked on the walk we already paid for
        if _sched is not None:
            _sched.prune_thread_registry(frames.keys())
        names = {t.ident: t.name for t in threading.enumerate()}
        # accumulate into pass-local counters and merge into the live
        # window under the lock ONCE: readers (merged(), shard dumps)
        # copy the window under the same lock, so neither side ever
        # iterates a dict the other is resizing
        loc_folded: Counter = Counter()
        loc_labels: Counter = Counter()
        nsamples = nbusy = 0
        for tid, frame in frames.items():
            if tid == me:
                continue
            nsamples += 1
            if job is not None:
                job.nsamples += 1
            if _is_idle(frame):
                continue
            stack: List[str] = []
            hint_frame = None
            dev_hint_frame = None
            f = frame
            while f is not None and len(stack) < _MAX_STACK:
                stack.append(_frame_id(f))
                if hint_frame is None and \
                        f.f_code.co_name in _SOCK_HINT_FRAMES and \
                        f.f_code.co_filename.endswith("socket.py"):
                    hint_frame = f
                if dev_hint_frame is None and \
                        f.f_code.co_name in _DEV_HINT_FRAMES and \
                        f.f_code.co_filename.endswith("ici.py"):
                    dev_hint_frame = f
                f = f.f_back
            if not stack:
                continue
            label = self._attribute(tid, names, hint_frame,
                                    dev_hint_frame)
            folded_key = label + ";" + ";".join(reversed(stack))
            nbusy += 1
            loc_folded[folded_key] += 1
            loc_labels[label] += 1
            if job is not None:
                # the job is touched only by this sampler thread until
                # its on_done handoff — no lock needed
                job.leaves[stack[0]] += 1
                job.folded[folded_key] += 1
        if include_cont:
            with self._lock:
                cur = self._cur
                if cur is not None:
                    cur.nsamples += nsamples
                    cur.nbusy += nbusy
                    cur.folded.update(loc_folded)
                    cur.labels.update(loc_labels)

    @staticmethod
    def _attribute(tid: int, names: Dict[int, str],
                   hint_frame=None, dev_hint_frame=None) -> str:
        """Sample attribution, most-specific first: the RPC method the
        thread's current fiber is serving (serving-controller fiber
        local, set by the classic dispatch path), the fiber's name (the
        turbo path names its fibers with the method key, so the native
        scan lane attributes for free), the device-thread label / ici
        pump-leg hint (device work outside any fiber attributes to
        ``device:<peer>``), the sampled connection's last-served method
        (transport legs — the dispatcher draining a conn's bytes is
        serving that conn's traffic), then the thread name."""
        if _thread_current_fiber is None:
            return f"thread:{names.get(tid, tid)}"
        fiber = _thread_current_fiber(tid)
        if fiber is not None:
            try:
                cntl = _serving_cntl.peek(fiber)
            except Exception:
                cntl = None
            if cntl is not None:
                svc = getattr(cntl, "_service_name", "") or ""
                meth = getattr(cntl, "_method_name", "") or ""
                if svc or meth:
                    return f"rpc:{svc}.{meth}"
            name = fiber.name
            if name:
                # turbo request fibers carry "Service.Method" directly
                if "." in name and " " not in name:
                    return f"rpc:{name}"
                return f"fiber:{name}"
            return "fiber:<anon>"
        # worker-module engine slices (serving decode steps) run on the
        # worker thread OUTSIDE any fiber: the module declares its label
        lbl = _worker_module.active_label(tid)
        if lbl:
            return f"rpc:{lbl}" if "." in lbl else f"module:{lbl}"
        # serving-lane threads (engine warm-up / decode slices with no
        # live module label) stamp ``serving:<what>`` in serving_stats;
        # resolved through sys.modules — NEVER an import on the sampler
        # thread, and the serving package (model -> jax) must not load
        # just because the recorder sampled a thread
        ss = sys.modules.get("brpc_tpu.serving.serving_stats")
        if ss is not None:
            srv_lbl = ss.serving_thread_label(tid)
            if srv_lbl:
                return srv_lbl
        dev_lbl = _device_stats.device_thread_label(tid)
        if dev_lbl:
            return dev_lbl
        if dev_hint_frame is not None:
            # f_locals on another thread's live frame builds a copy —
            # fine at sampling rate, never mutates the frame
            try:
                conn = dev_hint_frame.f_locals.get("self")
                rem = getattr(conn, "_remote", None)
                if rem is not None:
                    return f"device:{rem}"
            except Exception:
                pass
        if hint_frame is not None:
            # f_locals on another thread's live frame builds a copy —
            # fine at sampling rate, never mutates the frame
            try:
                sock = hint_frame.f_locals.get("self")
                lm = getattr(sock, "last_method", None)
                if lm:
                    return f"rpc:{lm}"
            except Exception:
                pass
        return f"thread:{names.get(tid, tid)}"

    # ------------------------------------------------------------ watchdog
    def _watchdog_pass(self, now_ns: int) -> None:
        ed = _ed
        if ed is None:
            return
        d = ed.peek_dispatcher()
        if d is None:
            return
        t0 = d._tick_start_ns
        if not t0:
            return
        stall_ms = (now_ns - t0) / 1e6
        if stall_ms <= 1.0:
            return
        ed.note_stall(stall_ms)
        if stall_ms < float(flag("dispatcher_stall_ms")):
            return
        seq = d._tick_seq
        if seq != self._counted_tick:
            self._counted_tick = seq    # an overrun counts once a tick
            ed.nstalls.add(1)
        if seq == self._annotated_tick:
            return                      # its span has been told already
        # name the culprit: the rpcz span of the request whose handler
        # is monopolizing the event thread right now (inline dispatch).
        # A tick that overran before its request had a span (a slow read
        # or parse, a first request's imports) is told on a later pass
        t = d._thread
        if t is None or t.ident is None or _thread_current_fiber is None:
            return
        fiber = _thread_current_fiber(t.ident)
        if fiber is None:
            return
        try:
            cntl = _serving_cntl.peek(fiber)
            span = cntl.__dict__.get("_span") if cntl is not None else None
            if span is not None and hasattr(span, "annotate"):
                span.annotate(f"dispatcher_stall {stall_ms:.1f}ms "
                              "(handler held the event thread)")
                self._annotated_tick = seq
        except Exception:
            pass

    # ---------------------------------------------------------------- loop
    def _sleep(self, seconds: float) -> None:
        """Interruptible sleep: request_profile/stop set _wake so a
        fresh job never waits out a long low-hz continuous sleep."""
        if self._wake.wait(max(0.001, seconds)):
            self._wake.clear()

    def _loop(self) -> None:
        stop = self._stop_ev
        while not stop.is_set():
            hz = flag("continuous_profiler_hz")
            with self._lock:
                job = self._job
            if hz <= 0 and job is None:
                # profiling parked — the STALL WATCHDOG stays on (it is
                # a separate feature behind dispatcher_stall_ms): a
                # 50ms poll reliably catches default-threshold stalls,
                # and the pass is a few attribute reads
                try:
                    self._watchdog_pass(time.monotonic_ns())
                except Exception:
                    pass
                self._sleep(0.05)
                continue
            period = 1.0 / max(0.5, float(hz)) if hz > 0 else 0.25
            now = time.monotonic()
            # window roll / lazy creation
            if hz > 0:
                win_s = max(1.0, float(flag("continuous_profiler_window_s")))
                with self._lock:
                    if self._cur is None:
                        self._cur = _Window(now)
                        self._next_cont = now
                    elif now - self._cur.start_mono >= win_s:
                        self._cur.end_mono = now
                        # the flag counts COMPLETED windows (floor 2 so
                        # window_diff always has a pair), the live one
                        # rides on top
                        keep = max(
                            2, int(flag("continuous_profiler_windows")))
                        if self._done.maxlen != keep:
                            self._done = deque(self._done, maxlen=keep)
                        self._done.append(self._cur)
                        self._cur = _Window(now)
            cont_due = hz > 0 and now >= self._next_cont
            job_due = job is not None and now >= job.next_due
            if cont_due or job_due:
                try:
                    self._sample_pass(cont_due, job if job_due else None)
                except Exception:
                    pass                # sampling must never die
                if cont_due:
                    self._next_cont = now + period
                if job_due:
                    job.next_due = now + job.interval
            try:
                self._watchdog_pass(time.monotonic_ns())
            except Exception:
                pass
            if job is not None and now >= job.deadline:
                with self._lock:
                    self._job = None
                try:
                    job.on_done(job.leaves, job.folded, job.nsamples)
                except Exception:
                    pass
                job = None
            # next due event decides the sleep — capped at 50ms so the
            # stall watchdog's resolution never degrades below the
            # default dispatcher_stall_ms threshold, whatever hz is
            waits = [0.05]
            if hz > 0:
                waits.append(self._next_cont - time.monotonic())
            if job is not None:
                waits.append(job.next_due - time.monotonic())
            self._sleep(min(waits))

    # ------------------------------------------------------------- reading
    def windows(self) -> List[_Window]:
        """Completed windows oldest-first, plus a SNAPSHOT of the
        in-progress one (completed windows are immutable after the
        roll; the live one is copied under the lock the sampler merges
        under, so readers never iterate a mutating Counter)."""
        with self._lock:
            out = list(self._done)
            cur = self._cur
            if cur is not None:
                snap = _Window(cur.start_mono)
                snap.nsamples = cur.nsamples
                snap.nbusy = cur.nbusy
                snap.folded = Counter(cur.folded)
                snap.labels = Counter(cur.labels)
                out.append(snap)
        return out

    def merged(self, windows: Optional[List[_Window]] = None) -> dict:
        wins = self.windows() if windows is None else windows
        folded: Counter = Counter()
        labels: Counter = Counter()
        nsamples = nbusy = 0
        for w in wins:
            folded.update(w.folded)
            labels.update(w.labels)
            nsamples += w.nsamples
            nbusy += w.nbusy
        span_s = 0.0
        if wins:
            end = wins[-1].end_mono or time.monotonic()
            span_s = max(0.0, end - wins[0].start_mono)
        return {"nsamples": nsamples, "nbusy": nbusy,
                "windows": len(wins), "span_s": round(span_s, 1),
                "folded": folded, "labels": labels}

    def window_diff(self) -> dict:
        """What changed between the two most recent COMPLETED windows:
        positive deltas = stacks heating up, negative = cooling down.
        The in-progress window is excluded — comparing a partial
        window against a full one would show everything 'cooling' at a
        steady load."""
        with self._lock:
            done = list(self._done)
        if len(done) < 2:
            return {"ok": False, "reason":
                    "need two completed windows (profiler just "
                    "started? window_s too long for this wait?)"}
        prev, cur = done[-2], done[-1]
        delta: Counter = Counter(cur.folded)
        delta.subtract(prev.folded)
        return {"ok": True,
                "cur_samples": cur.nbusy, "prev_samples": prev.nbusy,
                "delta": {k: v for k, v in delta.items() if v},
                "labels_cur": dict(cur.labels),
                "labels_prev": dict(prev.labels)}

    def dump_state(self, top: int = 150) -> dict:
        """JSON-ready snapshot for shard dumps: bounded folded stacks +
        attribution so the supervisor can merge an N-shard profile by
        summing counters (the PR 5 aggregator discipline: counters sum,
        maxima max — sample counts are counters)."""
        m = self.merged()
        from brpc_tpu.transport.event_dispatcher import stall_ms_max_10s
        return {
            "nsamples": m["nsamples"], "nbusy": m["nbusy"],
            "windows": m["windows"], "span_s": m["span_s"],
            "folded": dict(m["folded"].most_common(top)),
            "labels": dict(m["labels"].most_common(50)),
            "stall_ms_max_10s": stall_ms_max_10s(),
        }

    def note_incident(self, text: str) -> None:
        """Anomaly-watchdog stamp (bvar/anomaly.py): mark the LIVE
        continuous-profile window's label counts so the window
        covering a statistical break reads as such on
        /hotspots?mode=continuous and in merged shard profiles (labels
        already ride dump_state). No live window (profiler parked, hz
        0) means nothing to mark — the incident ring on /timeline is
        the durable record either way."""
        with self._lock:
            cur = self._cur
            if cur is not None:
                cur.labels[f"incident:{text}"] += 1

    def clear(self) -> None:
        with self._lock:
            self._done.clear()
            self._cur = None


def merge_dump_states(states: List[dict]) -> dict:
    """Merge per-shard dump_state payloads (counters sum, stall maxes)."""
    folded: Counter = Counter()
    labels: Counter = Counter()
    out = {"nsamples": 0, "nbusy": 0, "windows": 0, "span_s": 0.0,
           "stall_ms_max_10s": 0.0, "shards_reporting": len(states)}
    for st in states:
        folded.update({k: int(v) for k, v in st.get("folded", {}).items()})
        labels.update({k: int(v) for k, v in st.get("labels", {}).items()})
        out["nsamples"] += int(st.get("nsamples", 0) or 0)
        out["nbusy"] += int(st.get("nbusy", 0) or 0)
        out["windows"] = max(out["windows"], int(st.get("windows", 0) or 0))
        out["span_s"] = max(out["span_s"],
                            float(st.get("span_s", 0.0) or 0.0))
        out["stall_ms_max_10s"] = max(
            out["stall_ms_max_10s"],
            float(st.get("stall_ms_max_10s", 0.0) or 0.0))
    out["folded"] = folded
    out["labels"] = labels
    return out


# ---------------------------------------------------------------- render

def render_continuous_text(m: dict, top: int = 40) -> str:
    """Attribution-first text view of a merged continuous profile."""
    labels: Counter = m["labels"] if isinstance(m["labels"], Counter) \
        else Counter(m["labels"])
    nbusy = m["nbusy"] or 0
    lines = [f"continuous profile: {m['nsamples']} samples over "
             f"~{m.get('span_s', 0)}s in {m.get('windows', 0)} window(s); "
             f"{nbusy} busy\n"]
    if m.get("stall_ms_max_10s") is not None:
        lines.append(
            f"dispatcher_stall_ms_max_10s: {m['stall_ms_max_10s']}\n")
    lines.append("\nbusy samples by attribution:\n")
    for label, n in labels.most_common(top):
        pct = 100.0 * n / nbusy if nbusy else 0.0
        lines.append(f"{n:8d} {pct:5.1f}%  {label}\n")
    lines.append("\ntop stacks (folded):\n")
    folded: Counter = m["folded"] if isinstance(m["folded"], Counter) \
        else Counter(m["folded"])
    for stack, n in folded.most_common(top):
        lines.append(f"{n:8d}  {stack}\n")
    return "".join(lines)


def render_diff_text(d: dict, top: int = 40) -> str:
    if not d.get("ok"):
        return f"window diff unavailable: {d.get('reason')}\n"
    lines = [f"window diff (newest {d['cur_samples']} busy samples vs "
             f"previous {d['prev_samples']}):\n"]
    items = sorted(d["delta"].items(), key=lambda kv: -abs(kv[1]))
    for stack, dv in items[:top]:
        lines.append(f"{dv:+8d}  {stack}\n")
    if not items:
        lines.append("(no change)\n")
    return "".join(lines)


# ---------------------------------------------------------------- global

_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def global_recorder() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
    return _recorder


def _postfork_reset() -> None:
    """Fork hygiene: the sampler thread exists only in the parent, the
    windows profile the parent's RPCs, and the lock may be mid-hold.
    Drop the recorder — the shard's Server.start calls ensure_running()
    and builds a private sampler with empty windows."""
    global _recorder, _recorder_lock
    _recorder = None
    _recorder_lock = threading.Lock()


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the singleton it resets)

postfork.register("builtin.flight_recorder", _postfork_reset)
