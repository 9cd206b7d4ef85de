"""Ring lane: the batched-syscall event dispatcher (io_uring-style).

The fork's headline transport addition (src/bthread/ring_listener.*,
PAPER.md §layer 3) re-expressed for this stack: instead of a selector
tick that fires one Python callback per ready fd — each callback then
paying its own recv/send Python→libc round trip with a GIL
release/reacquire — the RingDispatcher tick is ONE GIL-released native
call (native/src/ring.cc) that polls the interest set AND executes the
whole ready-set's I/O: recv bursts, accept loops, one-shot writability.
Python drains the returned completion ring in bulk, and every response
written while draining is deferred onto a flush list that leaves as a
second single native call — a pipelined burst's responses depart as one
gather writev per connection instead of one send per RPC.

Selection is per-dispatcher: ``global_dispatcher()`` builds a
RingDispatcher when the ``event_ring_lane`` flag is on (env:
``BRPC_TPU_FLAG_EVENT_RING_LANE=1``) and the native extension is
available; the selector EventDispatcher stays the fallback lane and the
default. Conns that cannot hand their fd to the ring (ssl above-fd
buffering, chaos-wrapped conns whose write side must cross the fault
script) register poll-only: the ring reports readiness and their
classic callbacks run unchanged, so the chaos lane keeps observing
every byte it injects.

Completion-drain discipline (the graftlint-enforced contract, same as
the selector lane's event callbacks): everything this module runs on
the ring thread must be cheap — schedule fibers, feed portals, never
block. The scan lane's judge-or-defer posture carries over wholesale
because completions enter the SAME Socket machinery
(``Socket.ring_input`` → the classic parse/dispatch cycle).
"""

from __future__ import annotations

import errno
import logging
import os
import socket as pysocket
import threading
import time
from typing import Dict, Optional

from brpc_tpu.butil.flags import define_flag
from brpc_tpu.bvar.reducer import Adder
from brpc_tpu.transport import event_dispatcher as _evd

define_flag("event_ring_lane", False,
            "route the global event dispatcher through the ring lane "
            "(batched-syscall submission/completion ticks, native "
            "ring.cc); off = the selector lane. Per-dispatcher: "
            "existing dispatchers keep their lane")

# completion ops (must match native/src/ring.cc)
OP_RECV = 0
OP_ACCEPT = 1
OP_WRITEV = 2
OP_WRITABLE = 3
OP_READABLE = 4

_KIND_DATA = 0
_KIND_ACCEPT = 1
_KIND_POLL = 2

# handler slots (one list per fd, the EventDispatcher idiom)
_H_READ = 0      # classic on_readable (poll-only delivery)
_H_WRITE = 1     # one-shot on_writable
_H_ARMED = 2
_H_ONESHOT = 3
_H_KIND = 4
_H_SINK = 5      # ring_recv(data, eof, err) | ring_accept(fd_or_negerrno)

# ring-lane health at /vars: ticks, completion volume, and how much the
# write half batches (flushed_frames / flush_batches = frames per
# gather — the syscalls the lane removed vs one-send-per-frame)
nticks = Adder().expose("ring_ticks")
ncompletions = Adder().expose("ring_completions")
nflush_batches = Adder().expose("ring_flush_batches")
nflush_frames = Adder().expose("ring_flushed_frames")

# Current in-tick dispatcher for THIS thread: Socket._submit consults it
# (via try_defer_write) to route response frames into the end-of-tick
# flush instead of paying an inline send per frame. Only the ring
# thread ever sees a non-None value.
_tick_local = threading.local()


def try_defer_write(sock) -> bool:
    """True when ``sock``'s queued frames were handed to the current
    ring tick's write flush (the caller just claimed writership via its
    MPSC push; the flush settles it). False = no ring tick on this
    thread — the caller writes inline as usual."""
    d = getattr(_tick_local, "disp", None)
    if d is None:
        return False
    return d._defer_write(sock)


def ring_available() -> bool:
    from brpc_tpu.native import fastcore
    fc = fastcore.get()
    return fc is not None and hasattr(fc, "Ring")


class RingDispatcher:
    """EventDispatcher-compatible readiness engine over a native Ring.

    The public surface (add_consumer / pause_read / resume_read /
    request_writable / remove_consumer / stop) matches the selector
    dispatcher so conns wire up unchanged; data conns additionally pass
    ``ring_recv=`` (bytes flow natively) and listeners ``ring_accept=``
    (accepted fds arrive pre-made)."""

    ring_native = True

    def __init__(self, name: str = "ring_dispatcher"):
        from brpc_tpu.native import fastcore
        fc = fastcore.get()
        if fc is None or not hasattr(fc, "Ring"):
            raise RuntimeError("ring lane needs the fastcore extension")
        self._ring = fc.Ring()
        self.backend = self._ring.backend_name()
        self._lock = threading.Lock()
        self._barrier_cv = threading.Condition(self._lock)
        self._handlers: Dict[int, list] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._name = name
        # tick-barrier state: _tick_busy spans wait()+drain+flush;
        # consumers that must not overlap an in-flight native pass
        # (pluck claims, fd closes) kick the wakeup pipe and wait for
        # the CURRENT tick to settle (read_barrier)
        self._tick_busy = False
        self._tick_gen = 0
        # fds removed mid-tick: later completions of the SAME tick may
        # still name them (or a recycled fd number) — skip those
        self._tick_dead: set = set()
        # sockets whose writes this tick deferred (flush at tick end)
        self._flush: list = []
        # uring deferred gather writes awaiting their OP_WRITEV
        # completion: fd -> (socket, views, marks, total)
        self._pending_writes: Dict[int, tuple] = {}
        # stall-watchdog surface (flight recorder reads these off the
        # global dispatcher regardless of lane)
        self._tick_start_ns = 0
        self._tick_seq = 0
        self._wakeup_r, self._wakeup_w = pysocket.socketpair()
        self._wakeup_r.setblocking(False)
        wfd = self._wakeup_r.fileno()
        self._handlers[wfd] = [self._drain_wakeup, None, True, False,
                               _KIND_POLL, None]
        self._ring.register_fd(wfd, _KIND_POLL)

    # ------------------------------------------------------ registration
    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(target=self._run,
                                            name=self._name, daemon=True)
            self._thread.start()

    def _wakeup(self):
        if threading.current_thread() is self._thread:
            return
        try:
            self._wakeup_w.send(b"x")
        except (BlockingIOError, OSError):
            pass

    def _drain_wakeup(self):
        try:
            while self._wakeup_r.recv(4096):
                pass
        except (BlockingIOError, OSError):
            pass

    def add_consumer(self, fd: int, on_readable, oneshot_read: bool = False,
                     ring_recv=None, ring_accept=None) -> None:
        """Register read interest. ``ring_recv(data, eof, err)`` makes
        the fd ring-native (the tick recvs it and delivers bytes);
        ``ring_accept(fd_or_negerrno)`` marks a listener. Neither =
        poll-only: readiness fires the classic ``on_readable``."""
        if ring_recv is not None:
            kind, sink = _KIND_DATA, ring_recv
        elif ring_accept is not None:
            kind, sink = _KIND_ACCEPT, ring_accept
        else:
            kind, sink = _KIND_POLL, None
        with self._lock:
            self._handlers[fd] = [on_readable, None, True, oneshot_read,
                                  kind, sink]
            self._ring.register_fd(fd, kind)
            self._ensure_thread()
        self._wakeup()

    def pause_read(self, fd: int) -> None:
        with self._lock:
            h = self._handlers.get(fd)
            if h is None or not h[_H_ARMED]:
                return
            h[_H_ARMED] = False
            self._ring.set_read(fd, False)
        # no wakeup: an in-flight tick may still observe the fd once —
        # consumers that need a hard cutoff follow with read_barrier()

    def resume_read(self, fd: int) -> None:
        with self._lock:
            h = self._handlers.get(fd)
            if h is None or h[_H_ARMED]:
                return
            h[_H_ARMED] = True
            self._ring.set_read(fd, True)
        # the in-flight native pass snapshotted its interest set at
        # entry: kick it so pending bytes are seen now, not at the next
        # 500ms boundary
        self._wakeup()

    def request_writable(self, fd: int, on_writable) -> None:
        with self._lock:
            h = self._handlers.get(fd)
            if h is None:
                self._handlers[fd] = [None, on_writable, False, False,
                                      _KIND_POLL, None]
                self._ring.register_fd(fd, _KIND_POLL)
                self._ring.set_read(fd, False)   # write interest only
            else:
                h[_H_WRITE] = on_writable
            self._ring.request_writable(fd)
            self._ensure_thread()
        self._wakeup()

    def remove_consumer(self, fd: int) -> None:
        with self._lock:
            self._handlers.pop(fd, None)
            self._ring.unregister_fd(fd)
            self._tick_dead.add(fd)
            # graftlint: disable=guarded-by -- _pending_writes is
            # ring-thread owned (defer/settle on the tick); this one
            # teardown pop from another thread holds _lock while the
            # native generation guard stales any in-flight CQE for fd.
            pend = self._pending_writes.pop(fd, None)
        if pend is not None:
            # a deferred uring gather was still in flight: its CQE is
            # now stale (suppressed by the native generation guard) —
            # settle the parked frames here so their done callbacks
            # fire with the failure instead of hanging to the deadline.
            # Outside the lock: settle fires user callbacks.
            sock, views, marks, total = pend
            sock.ring_settle_write(0, errno.EPIPE, views, marks, total)
        self._wakeup()
        # the caller closes the fd next (TcpConn.close): an in-flight
        # native pass still holding it in its poll/recv set would then
        # race a recycled fd NUMBER — wait the tick out (microseconds
        # once kicked; skipped on the ring thread itself, where being
        # in Python IS proof the native pass isn't running)
        self.read_barrier()

    def read_barrier(self) -> None:
        """Block until the in-flight tick (native pass + completion
        drain + write flush) settles. The pluck lane calls this after
        pausing read interest and BEFORE sending its request: past the
        barrier, the ring can no longer consume response bytes the
        plucker is about to read itself."""
        if threading.current_thread() is self._thread:
            return
        self._wakeup()
        with self._lock:
            gen = self._tick_gen
            while self._tick_busy and self._tick_gen == gen:
                self._barrier_cv.wait(0.05)

    # ------------------------------------------------------- write flush
    def _defer_write(self, sock) -> bool:
        # ring-thread only (the thread-local gate in try_defer_write);
        # the socket's push already claimed writership, which the tick
        # flush now owns until settle
        # graftlint: disable=guarded-by -- _flush is ring-thread
        # confined: the thread-local gate admits only the tick thread,
        # a single writer that needs no lock.
        self._flush.append(sock)
        return True

    def _flush_writes(self) -> None:
        socks, self._flush = self._flush, []
        batch = []
        metas = []
        for sock in socks:
            try:
                if sock.failed:
                    # fail-drain + retire through the classic writer
                    # (its failed branch fires every callback with the
                    # reason)
                    sock._drain_writes_inline()
                    continue
                views, marks, total = sock.ring_collect_writes()
                if not marks:
                    sock._drain_writes_inline()   # raced empty: retire
                    continue
                fd = -1
                pfd = getattr(sock.conn, "stream_fd", None)
                if pfd is not None:
                    try:
                        fd = pfd()
                    except OSError:
                        fd = -1
                if fd < 0:
                    # no usable fd (failed mid-tick): park everything
                    # via the classic handoff — its writable
                    # continuation (or set_failed's cleanup) settles
                    # the frames
                    sock.ring_settle_write(0, 0, views, marks, total)
                    continue
                batch.append((fd, views))
                metas.append((sock, views, marks, total))
            except Exception:
                # one socket must not strand the rest of the round: an
                # escaping collect/settle (MemoryError, a broken conn
                # attr) fails THIS conn — set_failed + the classic
                # fail-drain retire everything still queued with the
                # reason — and the loop moves on, so the remaining
                # sockets' claimed writership still flushes
                logging.getLogger("brpc_tpu.transport").exception(
                    "ring flush collect failed; failing the conn")
                try:
                    sock.set_failed(
                        ConnectionError("ring flush collect failed"))
                    sock._drain_writes_inline()
                except Exception:
                    pass
        if not batch:
            return
        nflush_batches.add(len(batch))
        nflush_frames.add(sum(len(m[2]) for m in metas))
        try:
            results = self._ring.flush_writes(batch)
        except Exception:
            logging.getLogger("brpc_tpu.transport").exception(
                "ring write flush failed; parking batches")
            for sock, views, marks, total in metas:
                sock.ring_settle_write(0, 0, views, marks, total)
            return
        for (sock, views, marks, total), (fd, res, err) in zip(metas,
                                                               results):
            try:
                if res < 0 and err == 0:
                    # uring pending marker: the OP_WRITEV completion
                    # settles
                    self._pending_writes[fd] = (sock, views, marks,
                                                total)
                    continue
                sock.ring_settle_write(res, err, views, marks, total)
            except Exception:
                # same containment as the collect half: a raising
                # settle fails its own conn, the rest of the batch
                # still settles
                logging.getLogger("brpc_tpu.transport").exception(
                    "ring write settle failed; failing the conn")
                try:
                    sock.set_failed(
                        ConnectionError("ring write settle failed"))
                    sock._drain_writes_inline()
                except Exception:
                    pass

    # ---------------------------------------------------------- the loop
    def _run(self):
        _tick_local.disp = self
        log = logging.getLogger("brpc_tpu.transport")
        while not self._stop:
            with self._lock:
                self._tick_busy = True
                self._tick_dead.clear()
            try:
                try:
                    comps = self._ring.wait(500)
                except OSError:
                    continue
                except ValueError:      # ring closed under us (postfork)
                    return
                if not comps:
                    continue
                nticks.add(1)
                ncompletions.add(len(comps))
                self._tick_seq += 1
                self._tick_start_ns = time.monotonic_ns()
                try:
                    for comp in comps:
                        try:
                            self._dispatch_completion(comp)
                        except Exception:
                            log.exception(
                                "ring completion failed for fd %d", comp[0])
                finally:
                    # flush settles callbacks that may defer MORE
                    # writes (a completed response re-issues a call):
                    # loop until drained, bounded — a pathological
                    # re-issue chain falls back to inline writes
                    rounds = 0
                    while self._flush and rounds < 8:
                        rounds += 1
                        try:
                            self._flush_writes()
                        except Exception:
                            log.exception("ring flush round failed")
                            break
                    for sock in self._flush:
                        try:
                            sock._drain_writes_inline()
                        except Exception:
                            log.exception("ring flush fallback failed")
                    self._flush = []
                    dur_ms = (time.monotonic_ns() -
                              self._tick_start_ns) / 1e6
                    self._tick_start_ns = 0
                    if dur_ms > 1.0:
                        _evd._tick_ms_max.update(dur_ms)
            finally:
                with self._lock:
                    self._tick_busy = False
                    self._tick_gen += 1
                    self._barrier_cv.notify_all()

    def _dispatch_completion(self, comp) -> None:
        fd, op, res, payload = comp
        if op == OP_WRITEV:
            # settle FIRST, dead or alive: the parked frames' done
            # callbacks must fire exactly like the classic writer's
            # fail-drain (a removed consumer's entry would otherwise
            # leak and hang any waiter on a write ack until its RPC
            # deadline; ring_settle_write routes a failed socket's
            # frames through its failure machinery)
            pend = self._pending_writes.pop(fd, None)
            if pend is not None:
                sock, views, marks, total = pend
                if res >= 0:
                    sock.ring_settle_write(res, 0, views, marks, total)
                else:
                    sock.ring_settle_write(0, -res, views, marks, total)
            return
        with self._lock:
            if fd in self._tick_dead:
                # removed mid-tick (possibly re-registered on a
                # recycled fd number): this completion describes the
                # OLD consumer — drop it
                if op == OP_ACCEPT and res >= 0:
                    os.close(res)        # never leak an accepted fd
                return
            h = self._handlers.get(fd)
            cb = None
            if h is not None:
                if op == OP_WRITABLE:
                    cb, h[_H_WRITE] = h[_H_WRITE], None
                    if h[_H_READ] is None and h[_H_SINK] is None:
                        # write-only registration fully consumed
                        del self._handlers[fd]
                        self._ring.unregister_fd(fd)
                elif op == OP_READABLE and h[_H_ONESHOT]:
                    # one-shot read semantics for poll-only conns (ssl):
                    # disarm until resume_read, like the selector lane
                    h[_H_ARMED] = False
                    self._ring.set_read(fd, False)
        if h is None:
            if op == OP_ACCEPT and res >= 0:
                os.close(res)
            return
        # callbacks run OUTSIDE the registry lock (they re-enter the
        # dispatcher: pause/resume, remove on failure)
        if op == OP_RECV:
            sink = h[_H_SINK]
            if sink is not None:
                sink(payload if res > 0 else None,
                     res == 0, -res if res < 0 else 0)
            elif h[_H_READ] is not None:
                h[_H_READ]()
        elif op == OP_ACCEPT:
            sink = h[_H_SINK]
            if sink is not None:
                sink(res)
            elif res >= 0:
                os.close(res)
        elif op == OP_WRITABLE:
            if cb is not None:
                cb()
        elif op == OP_READABLE:
            if h[_H_READ] is not None:
                h[_H_READ]()

    def stop(self):
        self._stop = True
        self._wakeup()

    def _postfork_abandon(self):
        """Fork hygiene (called by event_dispatcher's postfork reset on
        the CHILD's copy): the ring thread exists only in the parent;
        close the child's copies of the wakeup pair and the native ring
        (batch: frees; uring: unmaps the rings and closes the ring fd —
        close(2) never disturbs the parent's kernel object)."""
        self._stop = True
        for s in (self._wakeup_r, self._wakeup_w):
            try:
                s.close()
            except Exception:
                pass
        try:
            self._ring.close()
        except Exception:
            pass
