"""Continuous batcher: iteration-level scheduling over fixed KV slots
(the Orca idea — admit between decode steps, never between requests).

A classic batcher collects a batch, decodes it to completion, then
admits the next batch; a request arriving one step late waits a whole
batch. Here the unit of scheduling is ONE decode step:

  * **admission** — new requests join the running batch at the top of
    the next step whenever a KV slot is free (a slot = one sequence's
    fixed-capacity cache in the model's [max_batch, cache_len, dim]
    arrays). The wait queue behind the slots is bounded
    (``max_waiting``): a submit past that SHEDS immediately — better a
    fast failure the client can retry elsewhere than an unbounded queue
    every entry of which will miss its deadline anyway;
  * **eviction** — every admitted request carries its serving
    Controller, and each step starts by sweeping
    ``cntl.deadline_expired()``: a sequence whose client budget ran out
    mid-generation is retired with ``ERPCTIMEDOUT`` and its slot freed
    for the queue — generation for a caller who stopped waiting is pure
    waste (the serving twin of PR 2's pre-handler shed gates);
  * **retirement** — a sequence hitting its token budget (or stop
    token, or client disconnect) leaves at the END of the step it
    finished in; survivors never notice.

Device work: one step of lookahead. The decode a ``step()`` collects
was dispatched by the step before it; a call runs the model's prefill
program for each request admitted in it (the donated cache orders it
behind the decode in flight), then ONE ``device_get`` brings the
in-flight decode's tokens and counters and the prefills' first tokens
to the host. From those alone it decides, in numpy over the slot table,
which slots continue (budget, stop token, cache room) and at which
lengths, and dispatches the NEXT decode at once; only then does it hand
out this step's tokens, retire the finished and fire the callbacks,
while the device runs the decode just queued. Slots that run with no
decode in flight (behind a step that raised before it dispatched one)
decode before the fetch, as a plain synchronous step. Both programs take
the cache donated and write it in place; nothing else crosses.

Thread model: ``step()`` is called from fiber-worker threads through
the engine's WorkerModule hook (serving/engine.py) and is serialized by
the engine's decode lock; THIS lock only guards the queues/slots, so
``submit``/``cancel`` from handler fibers stay cheap. The jitted calls
and the fetch run OUTSIDE the lock (jax releases the GIL; a submit must
not wait out a whole step), and user callbacks (``on_token``/
``on_finish``) fire outside it too — they write to sockets whose
failure paths call straight back into ``cancel``. A slot swept (cancel,
deadline) after its row was dispatched keeps that row on the device
and out of every stream: a row counts only while its slot's epoch is
the one it was dispatched under.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import Counter, deque, namedtuple
from typing import Callable, List, Optional, Tuple

import numpy as np

from brpc_tpu.bvar.reducer import Adder, PassiveStatus
from brpc_tpu.bvar.window import PerSecond
from brpc_tpu.rpc import errno_codes as berr

from . import serving_stats as _sstats
from .model import TinyDecoder


def _flat(counts):
    """A counter array from the device as a flat tuple of ints (None
    where the model counts nothing)."""
    if counts is None:
        return None
    return tuple(int(c) for c in np.ravel(counts))


# a decode dispatched and not yet collected: its device outputs, and the
# lengths and slot epochs it was dispatched with
_Flight = namedtuple("_Flight", "tokens counts lengths epoch")


def _sum_counts(arrays):
    """The element-wise sum of the prefills' counter arrays (None where
    there are none, or the model counts nothing)."""
    arrays = [a for a in arrays if a is not None]
    if not arrays:
        return None
    return np.sum(arrays, axis=0)

# request states
WAITING = "waiting"
RUNNING = "running"
COMPLETED = "completed"
EVICTED = "evicted"        # deadline expired mid-flight -> ERPCTIMEDOUT
SHED = "shed"              # wait queue full at submit
CANCELED = "canceled"      # client gone (stream/conn closed)

_TERMINAL = frozenset((COMPLETED, EVICTED, SHED, CANCELED))

# process-wide counters (the /vars surface; per-batcher figures live in
# stats_snapshot). Exposed by expose_serving_vars from Server.start —
# the unexpose_all-surviving lifecycle every subsystem here uses.
nsubmitted = Adder()
ncompleted = Adder()
nevicted = Adder()
nshed = Adder()
ncanceled = Adder()
ntokens = Adder()
ndecode_overlapped = Adder()  # steps that queued the next decode early
_tokens_ps = None           # PerSecond over ntokens, built on expose
_live_batchers: "weakref.WeakSet[ContinuousBatcher]" = weakref.WeakSet()


def _sum_live(attr: str) -> float:
    return sum(getattr(b, attr)() for b in list(_live_batchers))


def expose_serving_vars() -> None:
    global _tokens_ps
    nsubmitted.expose("serving_requests")
    ncompleted.expose("serving_completed")
    nevicted.expose("serving_evicted")
    nshed.expose("serving_shed")
    ncanceled.expose("serving_canceled")
    ntokens.expose("serving_tokens")
    ndecode_overlapped.expose("serving_decode_overlapped")
    if _tokens_ps is None:
        _tokens_ps = PerSecond(ntokens, 10)
    _tokens_ps.expose("serving_tokens_per_second_10s")
    PassiveStatus(lambda: int(_sum_live("running_count"))).expose(
        "serving_running")
    PassiveStatus(lambda: int(_sum_live("waiting_count"))).expose(
        "serving_waiting")
    PassiveStatus(lambda: round(_sum_live("kv_occupancy"), 4)).expose(
        "serving_kv_occupancy")
    # the flight-deck family (per-method cells, TTFT/TPOT recorders,
    # serving_ttft_p99_us) shares the serving lane's expose lifecycle
    _sstats.expose_serving_stats_vars()


def _postfork_reset() -> None:
    """A forked shard inherits the parent's batcher objects through the
    weakset; its counters restart with its private bvar store."""
    global _live_batchers, _tokens_ps
    _live_batchers = weakref.WeakSet()
    _tokens_ps = None


from brpc_tpu.butil import postfork  # noqa: E402  (registration ships
#                                      with the registry it resets)

postfork.register("serving.batcher", _postfork_reset)


class RequestTooLong(ValueError):
    """Prompt alone would overflow the KV slot — unservable, distinct
    from shed (retrying elsewhere cannot help)."""


class GenRequest:
    """One generation request riding the batch: the prompt, the token
    budget, the serving controller whose deadline drives eviction, and
    the emit callbacks (called OUTSIDE batcher locks, on the engine's
    worker thread)."""

    _seq = 0
    _seq_lock = threading.Lock()

    def __init__(self, prompt_tokens: List[int], max_new_tokens: int,
                 cntl=None,
                 on_token: Optional[Callable[["GenRequest", int], None]] = None,
                 on_finish: Optional[Callable[["GenRequest", str], None]] = None,
                 stop_token: Optional[int] = None):
        with GenRequest._seq_lock:
            GenRequest._seq += 1
            self.req_id = GenRequest._seq
        self.prompt = list(prompt_tokens)
        self.max_new_tokens = int(max_new_tokens)
        self.cntl = cntl
        self.on_token = on_token
        self.on_finish = on_finish
        self.stop_token = stop_token
        self.state = WAITING
        self.slot: Optional[int] = None
        self.tokens: List[int] = []
        self.created_ns = time.monotonic_ns()
        self.admitted_ns = 0
        self.first_token_ns = 0
        self.finished_ns = 0
        self.error_code = 0          # berr.* for evicted/shed
        self._cancel = False         # set by cancel(); swept by step()
        # flight-deck stage tracker (serving_stats.GenTracker), attached
        # by the service when serving_stats is enabled; None costs the
        # batcher one attribute check per waypoint
        self.tracker = None

    @property
    def ntokens(self) -> int:
        return len(self.tokens)

    def ttft_ms(self) -> Optional[float]:
        if not self.first_token_ns:
            return None
        return (self.first_token_ns - self.created_ns) / 1e6


class ContinuousBatcher:
    def __init__(self, model=None, max_batch: int = 8,
                 max_waiting: int = 32, wake=None):
        self.model = model or TinyDecoder()
        # worker wake-up hook (TaskControl.parking_lot.signal): a submit
        # landing while every fiber worker is parked must not wait out
        # the 0.5s park timeout — TTFT is a headline number here. Once a
        # worker is stepping it keeps polling until the batch drains, so
        # only the idle->busy edge needs the kick.
        self._wake = wake
        self.max_batch = int(max_batch)
        self.max_waiting = int(max_waiting)
        self.cache_len = int(self.model.cache_len)
        self._lock = threading.Lock()
        # the model's cache lives on the device; only step() touches it
        # (steps are serialized by the engine's decode lock). stop()
        # lets it go, or leaves that to the step in flight (_stepping)
        self._stepping = False
        self._cache = self.model.new_cache(self.max_batch, self.cache_len)
        self._lens = np.zeros((self.max_batch,), np.int64)  # rows cached
        self._last = np.zeros((self.max_batch,), np.int32)  # pending token
        # what the lookahead decides on, a slot each: the epoch (bumped
        # whenever the slot is let go), tokens emitted, the budget and
        # the stop token (-1: none)
        self._epoch = np.zeros((self.max_batch,), np.int64)
        self._ntok = np.zeros((self.max_batch,), np.int64)
        self._budget = np.zeros((self.max_batch,), np.int64)
        self._stop = np.full((self.max_batch,), -1, np.int64)
        self._flight: Optional[_Flight] = None
        self._slots: List[Optional[GenRequest]] = [None] * self.max_batch
        self._free = list(range(self.max_batch))
        self._waiting: deque = deque()
        self._nrunning = 0           # racy-read counter for has_work
        self.stopped = False
        # per-batcher observability (module Adders carry the /vars view)
        self.batch_hist: Counter = Counter()     # batch size -> steps
        self.steps_by_group: Counter = Counter()  # worker group -> steps
        self.decode_steps = 0
        self.completed = 0
        self.evicted = 0
        self.shed = 0
        self.canceled = 0
        self.tokens_out = 0
        _live_batchers.add(self)

    def warm_up(self) -> None:
        """Compile every program the model will run (before the first
        request, so its TTFT measures scheduling, not XLA)."""
        self._cache = self.model.warm_up(self._cache)

    # ------------------------------------------------------------- intake
    def submit(self, req: GenRequest) -> bool:
        """Queue a request for admission at the next step boundary.
        False = shed (bounded queue full, or batcher stopped); raises
        RequestTooLong when the prompt cannot fit a KV slot at all."""
        if not req.prompt:
            raise ValueError("empty prompt")
        if len(req.prompt) + 1 > self.cache_len \
                or len(req.prompt) > self.model.max_prompt:
            raise RequestTooLong(
                f"prompt of {len(req.prompt)} tokens cannot fit a "
                f"{self.cache_len}-token KV slot (prompts up to "
                f"{self.model.max_prompt})")
        # clamp the budget to the slot: a request asking for more than
        # fits generates what fits (the response says how many it got)
        req.max_new_tokens = min(req.max_new_tokens,
                                 self.cache_len - len(req.prompt))
        with self._lock:
            if self.stopped or len(self._waiting) >= self.max_waiting:
                req.state = SHED
                req.error_code = berr.ELIMIT
                req.finished_ns = time.monotonic_ns()
                self.shed += 1
                nshed.add(1)
                return False
            nsubmitted.add(1)
            self._waiting.append(req)
        if self._wake is not None:
            try:
                self._wake(1)
            except Exception:
                pass
        return True

    def cancel(self, req: GenRequest) -> None:
        """Client gone (stream closed, connection dropped): flag the
        request; the next step retires it and frees its KV slot. Safe
        from any thread, including socket-failure callbacks."""
        req._cancel = True

    # ------------------------------------------------------------ queries
    def has_work(self) -> bool:
        """Lock-free peek for the worker loops' has_task poll."""
        return (self._nrunning > 0 or bool(self._waiting)) \
            and not self.stopped

    def running_count(self) -> int:
        return self._nrunning

    def waiting_count(self) -> int:
        return len(self._waiting)

    def cache_bytes(self) -> int:
        """Bytes the cache pins: the model's device arrays (none once
        stopped) and the slot table's host arrays."""
        nbytes = self._lens.nbytes + self._last.nbytes
        cache = self._cache
        if cache is not None:
            import jax
            nbytes += sum(a.nbytes for a in jax.tree.leaves(cache))
        return nbytes

    def kv_occupancy(self) -> float:
        """Fraction of the KV budget (all slots x cache_len) holding
        live sequence state."""
        with self._lock:
            used = sum(int(self._lens[i])
                       for i, r in enumerate(self._slots) if r is not None)
        return used / float(self.max_batch * self.cache_len)

    # ------------------------------------------------------------ stepping
    def _retire_locked(self, req: GenRequest, state: str,
                       done: List[Tuple[GenRequest, str]]) -> None:
        req.state = state
        req.finished_ns = time.monotonic_ns()
        if state == EVICTED:
            req.error_code = berr.ERPCTIMEDOUT
            self.evicted += 1
            nevicted.add(1)
        elif state == COMPLETED:
            self.completed += 1
            ncompleted.add(1)
        elif state == CANCELED:
            self.canceled += 1
            ncanceled.add(1)
        if req.slot is not None:
            i = req.slot
            self._slots[i] = None
            self._lens[i] = 0
            self._epoch[i] += 1
            self._free.append(i)
            self._nrunning -= 1
            req.slot = None
        done.append((req, state))

    def step(self, group_index: Optional[int] = None) -> bool:
        """One scheduling iteration: sweep evictions/cancels, admit from
        the queue into free slots, collect the decode in flight (or run
        one), dispatch the next, emit tokens, retire finished sequences.
        Returns False when there was nothing to do (the caller's worker
        may park). Callers serialize steps (engine decode lock); this
        lock only covers slot/queue state."""
        with self._lock:
            if self._cache is None:         # stopped: the cache is gone
                return False
            self._stepping = True
        try:
            return self._step(group_index)
        finally:
            with self._lock:
                self._stepping = False
                if self.stopped:
                    self._cache = self._flight = None

    def _step(self, group_index: Optional[int]) -> bool:
        """The step itself (``step`` guards the cache around it)."""
        emits: List[Tuple[GenRequest, int]] = []
        done: List[Tuple[GenRequest, str]] = []
        admitted: List[GenRequest] = []
        # flight-deck iteration telemetry: one flag check per step; the
        # waypoint stamps below are attribute writes gated on the
        # request's tracker, and the step record lands in the bounded
        # ring AFTER the callbacks (never under this lock)
        stats_on = _sstats.enabled()
        t0 = time.monotonic_ns() if stats_on else 0
        t_sweep = t_admit = 0
        n_evicted = n_canceled = 0
        waiting_after = free_after = kv_used = 0
        with self._lock:
            # 1. sweep the running batch: client-gone and deadline-dead
            # sequences leave BEFORE we spend a step on them (a row
            # already in flight for one is dropped at collection)
            for req in [r for r in self._slots if r is not None]:
                if req._cancel:
                    self._retire_locked(req, CANCELED, done)
                elif req.cntl is not None and req.cntl.deadline_expired():
                    self._retire_locked(req, EVICTED, done)
            # ...and the WAIT QUEUE: a dead entry must not sit there
            # pinning max_waiting capacity (shedding live traffic) for
            # the whole duration of a full batch — it gets its verdict
            # NOW, not at its eventual admission turn
            if self._waiting:
                survivors = deque()
                for req in self._waiting:
                    if req._cancel:
                        self._retire_locked(req, CANCELED, done)
                    elif req.cntl is not None \
                            and req.cntl.deadline_expired():
                        self._retire_locked(req, EVICTED, done)
                    else:
                        survivors.append(req)
                self._waiting = survivors
            if stats_on:
                t_sweep = time.monotonic_ns()
                n_evicted = sum(1 for _, s in done if s == EVICTED)
                n_canceled = len(done) - n_evicted
            # 2. iteration-level admission: free slots pull from the
            # bounded queue between steps — never waiting for drain.
            # Slot assignment here; the prefill compute below, outside
            # the lock (submit/cancel/occupancy must stay cheap)
            while self._free and self._waiting:
                req = self._waiting.popleft()
                i = self._free.pop()
                self._slots[i] = req
                req.slot = i
                req.state = RUNNING
                req.admitted_ns = time.monotonic_ns()
                if req.tracker is not None:
                    req.tracker.gen_admitted(req.admitted_ns)
                self._nrunning += 1
                self._lens[i] = self._ntok[i] = 0
                self._budget[i] = req.max_new_tokens
                self._stop[i] = (-1 if req.stop_token is None
                                 else req.stop_token)
                admitted.append(req)
            active = [(i, r) for i, r in enumerate(self._slots)
                      if r is not None]
            if active:
                self.decode_steps += 1
                self.batch_hist[len(active)] += 1
                if group_index is not None:
                    self.steps_by_group[group_index] += 1
        flight, self._flight = self._flight, None
        if not active:
            # nothing runs: a decode still in flight holds rows of
            # swept slots alone, and is dropped
            self._fire(emits, done)
            if stats_on and done:
                self._record_step(t0, t_sweep, t_sweep, t_sweep,
                                  group_index, 0, len(admitted),
                                  n_evicted, n_canceled, 0,
                                  len(self._waiting), len(self._free), 0,
                                  0, None, None, 0)
            return bool(done)
        # the device work, outside the lock: the cache, lens and last
        # tokens are only written by step(), and steps are serialized by
        # the engine's decode lock, so only the slot TABLE needed it.
        # Admitted requests prefill (their first token comes from it)
        firsts, pre_counts = [], []
        for req in admitted:
            self._cache, tok, counts, _aux = self.model.prefill(
                self._cache, req.slot, req.prompt)
            firsts.append(tok)
            pre_counts.append(counts)
        t_admit = time.monotonic_ns() if stats_on else 0
        if flight is None:
            # nothing in flight (a step raised before it dispatched
            # one): the slots running before this step decode now
            fresh = set(id(r) for r in admitted)
            lengths = np.full((self.max_batch,), -1, np.int32)
            for i, req in active:
                if id(req) not in fresh:
                    lengths[i] = self._lens[i]
            if (lengths >= 0).any():
                flight = self._dispatch(self._last, lengths,
                                        self._epoch.copy())
        # one wait for the device: the tokens and the counters only
        import jax
        firsts, pre_counts, nxt, dec_counts = jax.device_get(
            (firsts, pre_counts, None if flight is None else flight.tokens,
             None if flight is None else flight.counts))
        t_decode = time.monotonic_ns() if stats_on else 0
        with self._lock:
            # 3. decide from the fetched tokens alone which slots go on,
            # at which lengths: a row counts while its slot's epoch is
            # the one it was dispatched under
            got = np.zeros((self.max_batch,), bool)
            toks = np.zeros((self.max_batch,), np.int64)
            lens = self._lens.copy()
            if flight is not None:
                rows = (flight.lengths >= 0) & (flight.epoch == self._epoch)
                got |= rows
                toks[rows] = nxt[rows]
                lens[rows] += 1
            for req, first in zip(admitted, firsts):
                if req.slot is not None:    # not retired by stop()
                    got[req.slot] = True
                    toks[req.slot] = first
                    lens[req.slot] = len(req.prompt)
            finish = got & ((toks == self._stop)
                            | (self._ntok + 1 >= self._budget)
                            | (lens + 1 >= self.cache_len))
            going = got & ~finish & (not self.stopped)
            slots = np.flatnonzero(got)
            owners = [self._slots[i] for i in slots]
            epoch = self._epoch.copy()
        # 4. the next decode, queued before this step's host work
        overlapped = 0
        if going.any():
            self._flight = self._dispatch(
                np.where(going, toks, 0).astype(np.int32),
                np.where(going, lens, -1).astype(np.int32), epoch)
            overlapped = 1
            ndecode_overlapped.add(1)
        # 5. hand out the tokens and retire, while the device decodes
        with self._lock:
            for i, req in zip(slots, owners):
                if self._slots[i] is not req:
                    continue        # retired by stop() during the step
                now = time.monotonic_ns()
                if not req.tokens and req.tracker is not None:
                    req.tracker.gen_prefilled(now)
                tok = int(toks[i])
                self._lens[i] = lens[i]
                self._last[i] = tok
                self._ntok[i] += 1
                req.tokens.append(tok)
                self.tokens_out += 1
                ntokens.add(1)
                if not req.first_token_ns:
                    req.first_token_ns = now
                if req.tracker is not None:
                    req.tracker.gen_token(now)
                emits.append((req, tok))
                if finish[i]:
                    self._retire_locked(req, COMPLETED, done)
            if stats_on:
                waiting_after = len(self._waiting)
                free_after = len(self._free)
                kv_used = sum(int(self._lens[i])
                              for i, r in enumerate(self._slots)
                              if r is not None)
        self._fire(emits, done)
        if stats_on:
            self._record_step(t0, t_sweep, t_admit, t_decode,
                              group_index, len(active), len(admitted),
                              n_evicted, n_canceled, len(emits),
                              waiting_after, free_after, kv_used,
                              sum(len(r.prompt) for r in admitted),
                              _flat(dec_counts), _flat(_sum_counts(
                                  pre_counts)), overlapped)
        return True

    def _dispatch(self, last, lengths, epoch) -> _Flight:
        """Queue one decode on the device (the cache donated) and keep
        its outputs for the step that collects them."""
        self._cache, nxt, counts, _aux = self.model.decode(
            self._cache, last, lengths)
        return _Flight(nxt, counts, lengths, epoch)

    def _record_step(self, t0: int, t_sweep: int, t_admit: int,
                     t_decode: int, group_index, batch: int,
                     admitted: int, evicted: int, canceled: int,
                     tokens: int, waiting: int, free_slots: int,
                     kv_used: int, prefill_tokens: int, routed,
                     prefill_routed, overlapped: int) -> None:
        """One bounded iteration record into the flight deck's step
        ring (leaf lock, outside every batcher lock): the Orca view —
        what THIS step did and where its microseconds went."""
        t_end = time.monotonic_ns()
        # a positional tuple in STEP_FIELDS order, integer microseconds:
        # this runs once per engine iteration from cold caches, where a
        # keyed dict build + float round()s measured ~3x the cost of
        # the whole record (step_records() re-keys at read time)
        reg = _sstats._registry
        if reg is None:
            reg = _sstats.global_serving_stats()
        reg.note_step_record((
            time.time_ns() // 1_000_000,
            group_index,
            batch,
            admitted,
            evicted,
            canceled,
            tokens,
            waiting,
            free_slots,
            round(kv_used / float(self.max_batch * self.cache_len), 4),
            max(0, t_sweep - t0) // 1000,
            max(0, t_admit - t_sweep) // 1000,
            max(0, t_decode - t_admit) // 1000,
            max(0, t_end - t_decode) // 1000,
            max(0, t_end - t0) // 1000,
            prefill_tokens,
            kv_used,
            routed,
            prefill_routed,
            overlapped,
        ))

    @staticmethod
    def _fire(emits, done) -> None:
        """User callbacks, outside every batcher lock: they write to
        streams/attachments whose failure paths call back into
        cancel()."""
        for req, tok in emits:
            if req.on_token is not None:
                try:
                    req.on_token(req, tok)
                except Exception:
                    import logging
                    logging.getLogger("brpc_tpu.serving").exception(
                        "on_token failed")
        for req, state in done:
            if req.on_finish is not None:
                try:
                    req.on_finish(req, state)
                except Exception:
                    import logging
                    logging.getLogger("brpc_tpu.serving").exception(
                        "on_finish failed")
            # settle the flight-deck tracker AFTER the finish callback:
            # emit_us then covers the delivery path (the sender pushing
            # the verdict frame), and the span's end stamp is the
            # moment the client could know its outcome
            if req.tracker is not None:
                cause = None
                if state == EVICTED:
                    cause = "deadline_expired"
                elif state == CANCELED:
                    cause = "client_gone"
                req.tracker.gen_settled(
                    state, cause=cause, finished_ns=req.finished_ns,
                    error_code=req.error_code)

    # ----------------------------------------------------------- shutdown
    def stop(self) -> List[GenRequest]:
        """Refuse new work, retire everything in flight (CANCELED) and
        release the device cache, and the decode in flight with it, once
        no step is running. Returns the retired requests (the service
        fails their calls)."""
        done: List[Tuple[GenRequest, str]] = []
        with self._lock:
            self.stopped = True
            victims = [r for r in self._slots if r is not None]
            victims += list(self._waiting)
            self._waiting.clear()
            for r in victims:
                if r.state not in _TERMINAL:
                    self._retire_locked(r, CANCELED, done)
            if not self._stepping:
                self._cache = self._flight = None
        self._fire([], done)
        return [r for r, _ in done]

    # ------------------------------------------------------ observability
    def stats_snapshot(self) -> dict:
        with self._lock:
            running = [{
                "req_id": r.req_id,
                "tokens": r.ntokens,
                "budget": r.max_new_tokens,
                "remaining_ms": (None if r.cntl is None
                                 else r.cntl.remaining_ms()),
            } for r in self._slots if r is not None]
            now = time.monotonic_ns()
            waiting_detail = [{
                "req_id": r.req_id,
                "age_ms": round((now - r.created_ns) / 1e6, 1),
                "remaining_ms": (None if r.cntl is None
                                 else r.cntl.remaining_ms()),
            } for r in list(self._waiting)[:32]]
            waiting = len(self._waiting)
            hist = dict(sorted(self.batch_hist.items()))
            groups = dict(sorted(self.steps_by_group.items()))
            used = sum(int(self._lens[i])
                       for i, r in enumerate(self._slots) if r is not None)
        return {
            "max_batch": self.max_batch,
            "cache_len": self.cache_len,
            "max_waiting": self.max_waiting,
            "running": running,
            "waiting": waiting,
            "waiting_detail": waiting_detail,
            "completed": self.completed,
            "evicted": self.evicted,
            "shed": self.shed,
            "canceled": self.canceled,
            "tokens_out": self.tokens_out,
            "decode_steps": self.decode_steps,
            "batch_size_hist": hist,
            "steps_by_worker_group": groups,
            "kv_occupancy": round(
                used / float(self.max_batch * self.cache_len), 4),
            "stopped": self.stopped,
        }
