"""The ``remote_caller`` deployment: upstream's rdma_performance as the
two processes it is. This process holds the chip and runs the server:
``Perf.Step`` and ``Perf.Hold`` exactly as ``services/perf.py`` has them
(its handlers, its widths, its resident layers, the program
``jit_perf_step``) behind one ``Server`` on ``tpud://``. The client is a
CHILD process (``benchmark/drivers/remote_child.py``) that never loads
an accelerator runtime: its requests are numpy arrays born in host
memory, they cross loopback TCP, the server's conn puts them on chip 0,
and the reply goes device -> host -> TCP -> the child's host memory.

Data from the seed, made once, on the chip, here: the 8 requests, the
float32 references (``benchmark/reference/remote_caller.py``) and the
bytes the timed program itself produces for each (input, layer) pair
(held to the reference within ``STEP_ATOL`` on the chip before they are
handed on). Their host bytes go to the child in a file under
``benchmark_out/``; nothing is drawn twice on two backends.

What ``correct`` holds a run to, beside ``tpu_performance``'s (tag back,
``Step`` within 2^-4 of the float32 reference, no retry and no backup, a
timeout is a failed call, the ``/device`` cells of BOTH processes
balance, the lane kind on both ends):
- ``client_off_chip``: the child says ``"jax" not in sys.modules`` after
  the window;
- ``placement``: the handler wrapper sees every request as a
  ``jax.Array`` committed to chip 0 (counted, 0 violations); the child
  takes only a numpy array as a reply;
- ``no_silent_staging``: ``tpud_put_fallbacks`` reads 0 in both
  processes;
- ``every_call_verified``: the child holds every response of the window
  to the reference, off the timed path (bit exact against the program's
  own bytes of set-up, a mismatch then held to the tolerance; responses
  that pass by the tolerance alone while the program, run again, still
  gives its bytes of set-up were altered on the way: not ``correct``).
The tolerance is ``services/perf.py``'s ``STEP_ATOL`` = 2^-4: bf16 carries
8 significant bits, one ulp is 2^-5 for 4 <= |y| < 8, the largest outputs
these widths produce; two ulps cover the bf16 rounding of the hidden layer
that feeds the second matmul (PR 21's tolerance).

Every wait on the child is bounded: it has ``REPLY_S`` to answer a
set-up command and the call timeout beyond a window's length to answer
a window; a child that died is seen within ``POLL_S``. ``close()`` kills
it; it exits by itself when its pipe closes."""

from __future__ import annotations

import json
import os
import select
import subprocess
import sys
import time

from benchmark.lib.loader import BENCH_DIR, REPO_ROOT, load_module
from benchmark.services.perf import STEP_ATOL, PerfDeployment

REPLY_S = 120.0         # a set-up command (the child's imports, a dial)
POLL_S = 0.02
# what the readers of benchmark/layer_metrics/ reach: the measured
# window's reports of the child, and whether the two clocks agreed
LAST: dict = {}


def build(ctx):
    return RemoteCallerDeployment(ctx)


class ChildError(RuntimeError):
    pass


class Child:
    """The client process and the pipe to it."""

    def __init__(self):
        env = dict(os.environ)
        # an environment that cannot reach the chip: the child loads no
        # jax at all (the run is not ``correct`` if it did), and if
        # something in it ever did, it would get the CPU
        env["JAX_PLATFORMS"] = "cpu"
        for name in list(env):
            if name.startswith(("TPU_", "PJRT_", "LIBTPU")):
                del env[name]
        env["PYTHONUNBUFFERED"] = "1"
        self.proc = subprocess.Popen(
            [sys.executable,
             os.path.join(BENCH_DIR, "drivers", "remote_child.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=REPO_ROOT)
        self._buf = b""

    def send(self, **cmd) -> None:
        try:
            self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError) as e:
            raise ChildError(f"the client process is gone: {e}") from e

    def reply(self, timeout_s: float, while_waiting=None) -> dict:
        """The child's next line, within ``timeout_s``; ``while_waiting``
        is called between polls (the window's ``at_offsets``). A child
        that died, said nothing in time or answered ``ok: false`` ends
        the run."""
        fd = self.proc.stdout.fileno()
        deadline = time.monotonic() + timeout_s
        while b"\n" not in self._buf:
            if while_waiting is not None:
                while_waiting()
            ready, _, _ = select.select([fd], [], [], POLL_S)
            if ready:
                chunk = os.read(fd, 1 << 20)
                if not chunk:
                    raise ChildError(
                        "the client process closed its pipe (exit code "
                        f"{self.proc.poll()})")
                self._buf += chunk
            elif self.proc.poll() is not None:
                raise ChildError("the client process died (exit code "
                                 f"{self.proc.returncode})")
            elif time.monotonic() >= deadline:
                raise ChildError("the client process said nothing for "
                                 f"{timeout_s:.0f} s")
        line, _, self._buf = self._buf.partition(b"\n")
        out = json.loads(line)
        if not out.get("ok"):
            raise ChildError(f"the client process failed: "
                             f"{out.get('error')}")
        return out

    def ask(self, timeout_s: float = REPLY_S, **cmd) -> dict:
        self.send(**cmd)
        return self.reply(timeout_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            pass
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass


class RemoteCallerDeployment(PerfDeployment):
    """``PerfDeployment``'s draw, program, handlers and sequence
    numbers; the references the client holds a response to, the server
    and the client are this deployment's own."""

    def __init__(self, ctx):
        super().__init__(ctx)
        if self.method != "Step":
            raise ValueError("remote_caller's traffic is Step")
        self.layout = ctx.cell.config["layout"]
        self.reference = load_module("reference", "remote_caller")
        self.server = None
        self.child = None
        self.child_lane = None
        self.data_path = None
        self.placement_violations = 0
        self.bad: list = []         # (seq, reason), every window's
        self.window_reply: dict = {}
        LAST.clear()

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        """``PerfDeployment``'s set-up (requests, weights and the program
        ``jit_perf_step``, made on the device from the seed: ONE draw,
        so a seed gives ``step_2mb_d8``'s data), then this deployment's
        own: the float32 references of its own reference file, the
        program's own output for each (input, layer) pair held to them
        on the chip, and the client's share written out as host bytes."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        super().prepare()
        pool, layers = self.pool, self.layers
        ref = jax.jit(self.reference.step_reference)
        worst = jax.jit(lambda y, e: jnp.max(jnp.abs(
            y.astype(jnp.float32) - e)))
        arrays = [(f"x{i}", self.xs[i]) for i in range(pool)]
        self.produced = []
        for i in range(self.period):
            xi, li = self.reference.expectation_of(i, pool, layers)
            expected = ref(self.xs[xi], self.w_in[li], self.w_out[li])
            produced = self.step(self.xs[xi], self.w_in[li], self.w_out[li])
            err = float(worst(produced, expected))
            if not err <= STEP_ATOL:
                raise AssertionError(
                    f"the program's own output for pair {i} is {err} from "
                    f"the float32 reference, over {STEP_ATOL}")
            self.produced.append(np.asarray(produced))
            arrays += [(f"e{i}", expected), (f"y{i}", self.produced[i])]

        out_dir = os.path.join(REPO_ROOT, "benchmark_out", "remote_caller")
        os.makedirs(out_dir, exist_ok=True)
        self.data_path = os.path.join(out_dir, f"data.{os.getpid()}.bin")
        header, blobs = [], []
        for name, arr in arrays:
            host = np.asarray(arr)
            raw = host.tobytes()
            header.append([name, str(host.dtype), list(host.shape),
                           len(raw)])
            blobs.append(raw)
        with open(self.data_path, "wb") as f:
            f.write((json.dumps({"arrays": header}) + "\n").encode())
            for raw in blobs:
                f.write(raw)

    def _placed(self, fn):
        """The handler wrapper's placement check: every request payload
        a ``jax.Array`` committed to this deployment's chip."""
        import jax

        want = {self.device}

        def handler(cntl, request):
            for a in cntl.request_device_arrays or ():
                if not isinstance(a, jax.Array) or a.devices() != want:
                    self.placement_violations += 1
            return fn(cntl, request)
        return handler

    def start(self) -> None:
        from brpc_tpu.rpc import Server, ServerOptions, Service

        svc = Service("Perf")
        svc.register_method("Step", self.stamps.wrap_handler(
            self._placed(self._step)))
        svc.register_method("Hold", self._placed(self._hold))
        # default ServerOptions, as benchmark/lib/fabric.py builds them
        self.server = Server(ServerOptions(enable_builtin_services=False))
        self.server.add_service(svc)
        ep = self.server.start(self.layout["servers"][0])
        self.child = Child()
        hello = self.child.ask(
            cmd="load", path=self.data_path, pool=self.pool,
            layers=self.layers, period=self.period, atol=STEP_ATOL,
            method=self.method,
            dial=self.layout["dial"].format(port=ep.port),
            channel_options=self.layout["channel_options"])
        self.child_lane = hello["lane"]
        self.child_pid = hello["pid"]
        # one clock reading each way: both processes read
        # CLOCK_MONOTONIC of one host, so the child's lies between the
        # parent's two; where it does not, spans of the two processes
        # are not compared
        t0 = time.monotonic_ns()
        theirs = self.child.ask(cmd="clock")["monotonic_ns"]
        t1 = time.monotonic_ns()
        LAST["clocks_agree"] = t0 <= theirs <= t1
        LAST["clock_exchange_ns"] = [t0, theirs, t1]
        if not LAST["clocks_agree"]:
            print(json.dumps({"info": {
                "clocks_disagree": LAST["clock_exchange_ns"],
                "note": "the client's clock reading lies outside the "
                        "parent's round trip: remote_*_wire_us are left "
                        "out"}}), flush=True)

    # ------------------------------------------------------------ client
    def warm(self) -> int:
        return int(self.child.ask(cmd="warm")["calls"])

    def run_window(self, seconds: float, depth: int, spans_from_s,
                   while_waiting) -> dict:
        """One window through the child; the driver's half."""
        timeout_s = seconds + \
            self.layout["channel_options"]["timeout_ms"] / 1e3 + 40.0
        self.child.send(cmd="window", seconds=seconds, depth=depth,
                        first_seq=self.first_seq, spans_from_s=spans_from_s)
        reply = self.child.reply(timeout_s, while_waiting)
        self.bad += reply["bad"]
        self.window_reply = reply
        self.child_lane = reply["lane"]
        return reply

    def finish(self) -> int:
        """The child's report of the measured window. What breaks a
        guarantee is a failed call with its reason; returns the
        responses that differ from the reference."""
        from brpc_tpu.transport import syscall_stats

        rep = self.child.ask(cmd="report")
        win = self.window_reply
        seq = self.first_seq
        fail = self.stamps.fail
        if rep["jax_loaded"]:
            fail(seq, "client_off_chip: the client process has loaded jax")
        if self.placement_violations:
            fail(seq, f"placement: {self.placement_violations} request "
                 "payloads reached the handler off chip 0 or as host "
                 "memory")
        mine = syscall_stats.snapshot().get("tpud_put_fallbacks")
        if rep["tpud_put_fallbacks"] or mine:
            fail(seq, f"no_silent_staging: tpud_put_fallbacks server "
                 f"{mine}, client {rep['tpud_put_fallbacks']}")
        for problem in rep["unbalanced"]:
            fail(seq, f"device_cells_balance, the client's: {problem}")
        if win.get("checked") != len(win.get("calls", ())):
            fail(seq, f"every_call_verified: {win.get('checked')} of "
                 f"{len(win.get('calls', ()))} responses were verified")
        # a response that is not the program's own bytes of set-up passed
        # by the tolerance alone. The program is run again: where it still
        # gives those bytes, the response was altered on its way, and a
        # payload that is only NEAR the reference is not the guarantee
        soft = win.get("tolerance_only") or []
        if soft and self._program_repeats_itself():
            fail(seq, f"step: {len(soft)} responses (first: call "
                 f"{soft[0]}) are within the tolerance of the reference "
                 "but are not the bytes the program produces, and the "
                 "program repeats its own bit for bit: altered on the way")
        # the warm window's too, as a DeviceVerifier's finish() counts
        bad = self.bad
        verified = max(1, len(win["calls"]) - len(win["bad"]))
        LAST.update(window=win, report=rep)
        print(json.dumps({"info": {"remote_client": {
            "pid": self.child_pid, "jax_loaded": rep["jax_loaded"],
            "lane": self.child_lane, "cells": rep["cells"],
            "verified": win.get("checked"),
            "verified_bit_exact": win.get("fast_path"),
            "verified_by_tolerance_alone": len(soft),
            "bad": bad[:3],
            "cpu_s": win["cpu_s"], "verify_cpu_s": win["verify_cpu_s"],
            "issue_thread_cpu_s": win["issue_cpu_s"],
            "verify_cpu_us_per_call": win["verify_cpu_s"] * 1e6 / verified,
            "placement_violations": self.placement_violations,
            "tpud_put_fallbacks": {"server": mine,
                                   "client": rep["tpud_put_fallbacks"]},
            "client_spans": len(rep["spans"]),
            "counters": {k: v for k, v in rep["counters"].items()
                         if k.startswith(("tpud_", "write_", "cpu_us_",
                                          "dispatcher_ticks", "recv",
                                          "writev"))},
        }}}), flush=True)
        return len(bad)

    def _program_repeats_itself(self) -> bool:
        """Whether ``jit_perf_step`` gives, for every (input, layer)
        pair, the bytes it gave in set-up (no new program: the same
        compiled one, the comparison on the host)."""
        import numpy as np

        for i, first in enumerate(self.produced):
            xi, li = self.reference.expectation_of(i, self.pool, self.layers)
            again = np.asarray(self.step(self.xs[xi], self.w_in[li],
                                         self.w_out[li]))
            if again.tobytes() != first.tobytes():
                return False
        return True

    def describe(self) -> dict:
        # the client has the server's hello by now; the accept that sent
        # it may still be filing the connection
        deadline = time.monotonic() + 2.0
        while not self.server.connections() and time.monotonic() < deadline:
            time.sleep(0.001)
        server_lanes = [s.conn.lane_kind for s in self.server.connections()]
        lanes = [self.child_lane] + server_lanes
        want = self.layout["lane"]
        if len(server_lanes) != 1 or any(k != want for k in lanes):
            raise AssertionError(
                f"device lanes are {lanes} (the client's, then the "
                f"server's connections), the configuration says one "
                f"connection of {want!r}")
        return {"lanes": lanes, "method": self.method, "pool": self.pool,
                "client_pid": self.child_pid}

    def close(self) -> None:
        if self.child is not None:
            self.child.kill()
        if self.server is not None:
            self.server.stop()
            self.server.join(5)
        if self.data_path is not None:
            try:
                os.remove(self.data_path)
            except OSError:
                pass
