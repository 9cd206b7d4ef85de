"""The client of the ``remote_caller`` deployment: a process of its own
that never loads an accelerator runtime (no ``jax``: payloads are numpy
arrays in host memory), driven by the process that holds the chip over
a pipe.

    python benchmark/drivers/remote_child.py      (stdin/stdout: the pipe)

One JSON object a line each way. The parent sends ``{"cmd": ...}``, the
child answers every command with one line (``{"ok": true, ...}`` or
``{"ok": false, "error": ...}``). Commands, in the order of a run:

``load``    requests, expectations and the program's own outputs of
            set-up from a file of host bytes (made on the chip from the
            seed by the parent: nothing is drawn twice on two backends);
            dials the one ``Channel``; answers the lane kind
``clock``   this process's ``time.monotonic_ns()``
``warm``    every (input, layer) pair once, synchronously, each held to
            the float32 reference by the tolerance AND to the program's
            own bytes; then one ``Hold``
``window``  the closed loop for ``seconds``: ``depth`` calls in flight on
            the one connection through ``done=`` chains; the ``done=``
            callback (the fabric's thread) stamps the call, ONE issuing
            thread stamps and issues the next; every response goes to a
            verifier thread, off the timed path. Spans record from
            ``spans_from_s`` on (the flag ``rpcz_enabled``: no profile
            runs here). Answers stamps, failures, mismatches and this
            process's CPU
``report``  after the window: ``"jax" in sys.modules``, the
            ``/device`` cells (balanced or not), counters, client spans

The child exits when its pipe closes (its parent is gone or has closed
it) at any moment: a thread of its own reads the pipe. The parent's
``close()`` kills it."""

from __future__ import annotations

import json
import os
import queue
import struct
import sys
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))

SERVICE = "Perf"
_TAG = struct.Struct("<Q")
now_ns = time.monotonic_ns      # CLOCK_MONOTONIC: one clock a host
SPAN_KEYS = ("trace_id", "span_id", "parent_span_id", "method",
             "error_code", "start_us", "write_done_us", "first_byte_us",
             "end_us")
SETTLE_S = 5.0


def load_arrays(path: str) -> dict:
    """The file the parent wrote: a JSON header line, then raw bytes.
    bfloat16 needs ml_dtypes, which needs no jax."""
    import numpy as np

    out: dict = {}
    with open(path, "rb") as f:
        header = json.loads(f.readline())
        for name, dtype, shape, nbytes in header["arrays"]:
            if dtype == "bfloat16":
                import ml_dtypes
                dt = np.dtype(ml_dtypes.bfloat16)
            else:
                dt = np.dtype(dtype)
            out[name] = np.frombuffer(f.read(nbytes), dtype=dt).reshape(shape)
    return out


class Verifier(threading.Thread):
    """Holds every response to the reference off the timed path: bit
    exact against the bytes the timed program itself produced for that
    (input, layer) pair in set-up; a mismatch falls through to the
    float32 reference within the tolerance before it fails the call
    (what passed by the tolerance alone is told to the parent, which
    holds it against the program run again)."""

    def __init__(self, client):
        super().__init__(name="bench-verifier", daemon=True)
        self.client = client
        self.q: queue.SimpleQueue = queue.SimpleQueue()
        self.cpu_s = 0.0            # this thread's CPU, as of its last item
        self.handed = 0             # by the issuing thread alone
        self.checked = 0
        self.fast = 0               # settled by the bit-exact path
        self.soft: list = []        # seq: passed by the tolerance alone
        self.bad: list = []         # (seq, reason)

    def put(self, seq, arr) -> None:
        self.handed += 1
        self.q.put((seq, arr))

    def run(self) -> None:
        import numpy as np

        c = self.client
        while True:
            seq, arr = self.q.get()
            try:
                i = c.index_of(seq)
                if arr.dtype == c.produced[i].dtype and \
                        arr.shape == c.produced[i].shape and \
                        np.array_equal(arr.view(np.uint16),
                                       c.produced[i].view(np.uint16)):
                    self.fast += 1
                elif c.reference.within(arr, c.expected[i], c.atol):
                    self.soft.append(seq)
                else:
                    self.bad.append((seq, "differs from the float32 "
                                     "reference by more than the tolerance"))
            except Exception as e:  # noqa: BLE001 - a bad response, counted
                self.bad.append((seq, f"{type(e).__name__}: {e}"[:200]))
            self.cpu_s = time.thread_time()
            self.checked += 1

    def drain(self, timeout_s: float) -> bool:
        deadline = time.monotonic() + timeout_s
        while self.checked < self.handed:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.002)
        return True


class Client:
    def __init__(self):
        self.channel = None
        self.verifier = Verifier(self)
        self.before: dict = {}      # counters at the window's start

    # ------------------------------------------------------------ set-up
    def load(self, cmd: dict) -> dict:
        from benchmark.lib.loader import load_module
        from brpc_tpu.butil.flags import set_flag
        from brpc_tpu.rpc import Channel, ChannelOptions

        self.reference = load_module("reference", "remote_caller")
        data = load_arrays(cmd["path"])
        self.pool, self.layers = int(cmd["pool"]), int(cmd["layers"])
        self.period = int(cmd["period"])
        self.atol = float(cmd["atol"])
        self.xs = [data[f"x{i}"] for i in range(self.pool)]
        self.expected = [data[f"e{i}"] for i in range(self.period)]
        self.produced = [data[f"y{i}"] for i in range(self.period)]
        self.method = cmd["method"]
        set_flag("device_stats_enabled", True)
        self.channel = Channel(cmd["dial"],
                               ChannelOptions(**cmd["channel_options"]))
        self.verifier.start()
        return {"lane": self.channel.device_lane_kind(), "pid": os.getpid()}

    def index_of(self, seq: int) -> int:
        """Which of the parent's (input, layer) pairs call ``seq`` is: it
        numbered them by the reference's rule over one period (the lcm
        of pool and layers), which the rule repeats with."""
        return seq % self.period

    def _response_array(self, seq: int, cntl):
        """The reply's array after the host-side checks: the call did
        not fail, its own tag came back, one numpy array in this
        process's memory."""
        import numpy as np

        if cntl.failed():
            raise RuntimeError(f"call failed: {cntl.error_code} "
                               f"{cntl.error_text}")
        if cntl.response_payload.to_bytes() != _TAG.pack(seq):
            raise AssertionError("response carries another request's tag")
        arrs = cntl.response_device_arrays
        if not arrs or len(arrs) != 1:
            raise AssertionError(f"response has {len(arrs or ())} arrays")
        if not isinstance(arrs[0], np.ndarray):
            raise AssertionError(f"the reply is a {type(arrs[0]).__name__}, "
                                 "not a numpy array in host memory")
        return arrs[0]

    def warm(self, _cmd: dict) -> dict:
        import numpy as np

        for seq in range(self.period):
            cntl = self.channel.call_sync(
                SERVICE, self.method, _TAG.pack(seq),
                request_device_arrays=[self.xs[seq % self.pool]])
            arr = self._response_array(seq, cntl)
            i = self.index_of(seq)
            if not self.reference.within(arr, self.expected[i], self.atol):
                raise AssertionError(f"warm-up response {seq} differs from "
                                     "the float32 reference")
            if not np.array_equal(arr.view(np.uint16),
                                  self.produced[i].view(np.uint16)):
                raise AssertionError(
                    f"warm-up response {seq} is not the bytes the program "
                    "produced for this pair in set-up")
        cntl = self.channel.call_sync(SERVICE, "Hold", _TAG.pack(0),
                                      request_device_arrays=[self.xs[0]])
        if cntl.failed():
            raise RuntimeError(f"Hold failed: {cntl.error_text}")
        return {"calls": self.period + 1}

    # ------------------------------------------------------------ window
    def window(self, cmd: dict) -> dict:
        from benchmark.lib import counters
        from brpc_tpu.butil.flags import set_flag

        seconds, depth = float(cmd["seconds"]), int(cmd["depth"])
        first_seq = int(cmd["first_seq"])
        spans_from = cmd.get("spans_from_s")
        channel, xs, pool, method = (self.channel, self.xs, self.pool,
                                     self.method)
        done_q: queue.SimpleQueue = queue.SimpleQueue()
        calls, failures = [], []
        verifier = self.verifier
        verifier.bad.clear()
        verifier.soft.clear()
        verifier.handed = verifier.checked = verifier.fast = 0

        def issue(seq: int) -> None:
            t_issue = now_ns()

            def on_done(cntl) -> None:
                # the fabric's thread: the reply's bytes are in host
                # memory when this runs; stamp and hand over
                done_q.put((seq, t_issue, cntl, now_ns()))
            channel.call(SERVICE, method, _TAG.pack(seq), done=on_done,
                         request_device_arrays=[xs[seq % pool]])

        self.before = counters.snapshot()
        verify0 = self.verifier.cpu_s
        c0 = time.thread_time()
        next_seq = first_seq
        start_ns = now_ns()
        t_end = start_ns + int(seconds * 1e9)
        t_spans = start_ns + int(spans_from * 1e9) \
            if spans_from is not None else None
        live = 0
        for _ in range(depth):
            issue(next_seq)
            next_seq += 1
            live += 1
        if t_spans is not None and t_spans <= start_ns:
            set_flag("rpcz_enabled", True)
            t_spans = None
        while live:
            seq, t_issue, cntl, t_done = done_q.get()
            try:
                arr = self._response_array(seq, cntl)
                calls.append((seq, t_issue, t_done))
                self.verifier.put(seq, arr)
            except Exception as e:  # noqa: BLE001 - a failed call, counted
                failures.append((seq, f"{type(e).__name__}: {e}"[:300]))
            now = now_ns()
            if t_spans is not None and now >= t_spans:
                set_flag("rpcz_enabled", True)
                t_spans = None
            if now >= t_end:
                live -= 1
                continue
            issue(next_seq)
            next_seq += 1
        issue_cpu_s = time.thread_time() - c0
        set_flag("rpcz_enabled", False)
        drained = self.verifier.drain(30.0)
        cpu_s = counters.snapshot()["cpu_s"] - self.before["cpu_s"]
        if not drained:
            failures.append((next_seq, "the verifier did not finish within "
                             "30 s of the window's end"))
        return {
            "start_ns": start_ns, "end_ns": t_end,
            "attempted": next_seq - first_seq,
            "calls": calls, "failures": failures,
            "bad": verifier.bad[:], "checked": verifier.checked,
            "fast_path": verifier.fast,
            "tolerance_only": verifier.soft[:],
            "cpu_s": cpu_s,
            "verify_cpu_s": self.verifier.cpu_s - verify0,
            "issue_cpu_s": issue_cpu_s,
            "lane": self.channel.device_lane_kind(),
        }

    def report(self, _cmd: dict) -> dict:
        from benchmark.lib import counters

        # this process's /device cells, held to what the parent's are
        unbalanced = counters.settle_and_check(self.before, SETTLE_S)
        delta = counters.delta(self.before, counters.snapshot())
        return {
            "jax_loaded": "jax" in sys.modules,
            "cells": delta["cells"], "unbalanced": unbalanced,
            "tpud_put_fallbacks":
                delta["syscalls"].get("tpud_put_fallbacks"),
            "counters": delta["syscalls"],
            "spans": _client_spans(self.method),
        }


def _client_spans(method: str) -> list:
    """This process's client spans of the window's method, the stamps
    the parent's readers join with its server spans (ids as the spans'
    own ``to_dict`` writes them)."""
    from brpc_tpu.rpc.span import global_collector

    out = []
    for s in global_collector.recent(1 << 30):
        if s.side == "client" and s.method == method:
            d = s.to_dict()
            out.append({k: d[k] for k in SPAN_KEYS})
    return out


def main() -> int:
    # the pipe is this process's stdin and stdout; whatever else prints
    # goes to stderr, which is the parent's
    pipe_out = os.fdopen(os.dup(sys.stdout.fileno()), "w")
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    sys.stdout = sys.stderr
    cmds: queue.SimpleQueue = queue.SimpleQueue()

    def reader() -> None:
        for line in sys.stdin:
            if line.strip():
                cmds.put(json.loads(line))
        os._exit(0)         # the pipe closed: the parent is gone

    threading.Thread(target=reader, name="bench-pipe", daemon=True).start()
    client = Client()
    while True:
        cmd = cmds.get()
        name = cmd["cmd"]
        try:
            if name == "clock":
                reply = {"monotonic_ns": time.monotonic_ns()}
            else:
                reply = getattr(client, name)(cmd)
            reply["ok"] = True
        except Exception as e:  # noqa: BLE001 - the parent ends the run
            import traceback
            traceback.print_exc()
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        pipe_out.write(json.dumps(reply) + "\n")
        pipe_out.flush()


if __name__ == "__main__":
    sys.exit(main())
