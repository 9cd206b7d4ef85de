"""Dedicated device-lane probe with hang forensics.

ONE bring-up attempt in a CHILD process, instrumented so a hang produces
evidence instead of an error string. The reference's flagship
fast-fabric benchmark prints QPS + latency percentiles from the runtime
(/root/reference/example/rdma_performance/client.cpp:261); this is the
ici:// analog, plus forensics for a backend that does not come up.

Forensic design (why parent/child):

* a hang inside the PJRT client (C land) can be observed by a
  same-process watchdog but never interrupted — the CHILD owns the
  backend attempt, the PARENT owns the clock. The child is also the one
  process that holds the chip: the parent never imports jax;
* the child arms ``faulthandler.register(SIGUSR1, all_threads=True)``:
  faulthandler dumps from the C signal handler, so it reports every
  thread's Python stack even while the main thread is parked inside a
  C call (exactly the frame we need to name);
* the parent snapshots the child's /proc state on a timeline — per-task
  ``wchan`` (the blocking syscall), process state, thread count, RSS;
* everything is written INCREMENTALLY to ``--out`` (atomic replace), so
  a harness kill of the whole bench still leaves the evidence on disk.

On successful bring-up the child runs the device lane: link floors,
then a 4B-4MB echo sweep over ``ici://`` with GB/s + p50/p99 per point
(lane_kind and the platform reported so the number can't silently
measure nothing). A lane failure is an error: the child exits 1 and
``main`` exits 1.

Usage: ``python tools/device_probe.py [--budget 150] [--out FILE]``
(bench.py calls ``run_probe()``). The platform is JAX's own choice
(``JAX_PLATFORMS``); the tests run the identical machinery on the CPU.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)



# --------------------------------------------------------------------------
# parent-side /proc forensics
# --------------------------------------------------------------------------

def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def _task_wchans(pid: int) -> List[dict]:
    """Per-thread (comm, state, wchan) — wchan names the kernel symbol
    the thread is blocked in, i.e. the exact syscall site."""
    out: List[dict] = []
    base = f"/proc/{pid}/task"
    try:
        tids = sorted(int(t) for t in os.listdir(base) if t.isdigit())
    except OSError:
        return out
    for tid in tids:
        comm = _read(f"{base}/{tid}/comm")
        wchan = _read(f"{base}/{tid}/wchan")
        state = ""
        stat = _read(f"{base}/{tid}/stat")
        if stat:
            # state is field 3, after the parenthesised comm
            rp = stat.rfind(")")
            if rp != -1:
                fields = stat[rp + 1:].split()
                if fields:
                    state = fields[0]
        out.append({"tid": tid, "comm": comm, "state": state,
                    "wchan": wchan or "0"})
    return out


def _snapshot(pid: int, t0: float) -> dict:
    return {
        "elapsed_s": round(time.monotonic() - t0, 1),
        "tasks": _task_wchans(pid),
        "vm_rss": next((ln.split()[1] + " kB" for ln in
                        _read(f"/proc/{pid}/status").splitlines()
                        if ln.startswith("VmRSS")), ""),
    }


def _write_out(out_path: Optional[str], doc: dict) -> None:
    if not out_path:
        return
    try:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        os.replace(tmp, out_path)
    except OSError:
        pass


def _last_dump(trace_text: str) -> str:
    """The final faulthandler dump in an append-only trace file (the
    periodic dump_traceback_later dumps accumulate; attribution must
    judge the LAST state, not a stale early dump)."""
    marker = "Timeout ("
    i = trace_text.rfind(marker)
    if i == -1:
        return trace_text
    tail = trace_text[i:]
    # a file ending mid-timeout-dump (no full SIGUSR1 dump after it)
    # still contains that dump's threads; shorter than ~2 lines means
    # the dump was cut off — fall back to the whole text
    return tail if tail.count("\n") > 2 else trace_text


def _attribute_hang(hang: dict) -> str:
    """Name the blocker from the captured evidence — the runtime's
    client creation vs this repo — so the artifact carries a
    conclusion, not just raw snapshots. Factual pattern matches only,
    judged against the FINAL stack dump."""
    stacks = _last_dump(hang.get("python_stacks", ""))
    tasks = hang.get("final_snapshot", {}).get("tasks", [])
    wchans = sorted({t.get("wchan") for t in tasks})
    repo_on_stack = "brpc_tpu" in stacks
    if "make_c_api_client" in stacks or "make_tpu_client" in stacks:
        where = "inside PJRT client creation (jaxlib -> libtpu)"
        if repo_on_stack:
            return (f"MIXED: blocked {where}, with repo frames also on "
                    f"the stack — see python_stacks")
        return (f"EXTERNAL: blocked {where} — another live process "
                f"holding the chip is the usual cause (preflight's "
                f"chip_holders); wchans {wchans}")
    if repo_on_stack:
        return ("REPO: a brpc_tpu frame is on the blocked stack — "
                "see python_stacks")
    return "UNATTRIBUTED: see python_stacks/timeline"


# --------------------------------------------------------------------------
# parent: spawn + monitor + forensics
# --------------------------------------------------------------------------

def run_probe(budget_s: float = 150.0, out_path: Optional[str] = None,
              progress=None) -> dict:
    """Spawn the child probe, monitor it, return the device_lane dict.

    The returned dict either carries real numbers (``headline_GBps``,
    ``sweep``, ``lane_kind``…) or a ``hang`` report naming the blocking
    frames and syscalls at the moment of death.
    """
    def note(obj):
        if progress:
            progress(obj)

    lane: dict = {"probe": {"budget_s": budget_s}}
    _write_out(out_path, lane)
    note({"progress": "device_probe_start", **lane["probe"]})

    trace_path = os.path.join(REPO_ROOT, ".pids", "device_probe_trace.txt")
    os.makedirs(os.path.dirname(trace_path), exist_ok=True)
    try:
        os.unlink(trace_path)
    except OSError:
        pass

    env = dict(os.environ)
    env["BRPC_TPU_PROBE_TRACE"] = trace_path
    env["BRPC_TPU_PROBE_BUDGET_S"] = str(budget_s)
    try:
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
    except OSError as e:
        lane["error"] = f"spawn failed: {type(e).__name__}: {e}"[:200]
        _write_out(out_path, lane)
        return lane

    os.set_blocking(child.stdout.fileno(), False)
    os.set_blocking(child.stderr.fileno(), False)
    t0 = time.monotonic()
    timeline: List[dict] = []
    phases: List[dict] = []
    backend_seen = [False]              # mutated inside drain()
    raw_stderr: List[str] = []          # non-JSON child output (tracebacks)
    stdout_buf = b""
    stderr_buf = b""
    last_snap = 0.0
    result_line: Optional[str] = None

    def drain():
        nonlocal stdout_buf, stderr_buf, result_line
        try:
            chunk = child.stdout.read()
            if chunk:
                stdout_buf += chunk
        except OSError:
            pass
        try:
            chunk = child.stderr.read()
            if chunk:
                stderr_buf += chunk
        except OSError:
            pass
        while b"\n" in stderr_buf:
            ln, _, stderr_buf = stderr_buf.partition(b"\n")
            try:
                rec = json.loads(ln)
                if not isinstance(rec, dict):
                    raise TypeError
                phases.append(rec)
                if rec.get("phase") == "backend_up":
                    backend_seen[0] = True
                note({"progress": "device_probe_phase", **rec})
            except (ValueError, TypeError):
                # keep runtime chatter / crash tracebacks as evidence
                raw_stderr.append(ln.decode("utf-8", "replace"))
                del raw_stderr[:-40]
        while b"\n" in stdout_buf:
            ln, _, stdout_buf = stdout_buf.partition(b"\n")
            if ln.startswith(b"RESULT "):
                result_line = ln[7:].decode("utf-8", "replace")

    # the child budgets ITSELF to finish within budget_s; the parent's
    # clock gets grace on top so a legitimate near-budget run is never
    # killed mid-final-batch and mislabeled as a hang. Bring-up gets a
    # SHORTER leash: a healthy backend arrives in seconds and a wedge's
    # signature is fully formed soon after (stable stacks) — burning the
    # whole sweep budget on a diagnosed hang would just delay the rest
    # of the bench behind it.
    parent_deadline_s = budget_s + min(20.0, max(3.0, budget_s * 0.15))
    # 45s at the default budget: the r4 bench's probe window, known to
    # fit the driver's outer clock, and a wedge's stacks are static
    # long before it
    bringup_deadline_s = min(parent_deadline_s, max(20.0, budget_s * 0.3))
    hung = False
    while True:
        drain()
        if result_line is not None or child.poll() is not None:
            break
        now = time.monotonic()
        limit = parent_deadline_s if backend_seen[0] else bringup_deadline_s
        if now - t0 > limit:
            hung = True
            tripped_limit = limit
            break
        if now - last_snap >= 5.0:
            last_snap = now
            timeline.append(_snapshot(child.pid, t0))
            if len(timeline) > 40:           # bound the artifact
                del timeline[1:3]            # keep first, thin the middle
            lane["probe"]["phases"] = phases[-12:]
            lane["probe"]["timeline"] = timeline[-8:]
            _write_out(out_path, lane)
        time.sleep(0.2)

    if hung:
        # name the blocker: python stacks (faulthandler via SIGUSR1,
        # dumped from the C signal handler even mid-C-call), kernel
        # wchan per thread — then kill.
        final_snap = _snapshot(child.pid, t0)
        try:
            child.send_signal(signal.SIGUSR1)
            time.sleep(2.0)
        except OSError:
            pass
        drain()
        py_stacks = _read(trace_path)
        try:
            child.kill()
            child.wait(10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        last_phase = phases[-1] if phases else {}
        ph = last_phase.get("phase", "?")
        # name the stage honestly: a hang after backend_up is a lane
        # stall, not a bring-up failure
        stage = ("backend bring-up" if ph in ("?", "import_jax",
                                              "jax_devices",
                                              "selftest_hang")
                 else f"device lane (after {ph})")
        lane["error"] = (
            f"{stage} hung > {tripped_limit:.0f}s "
            f"(last phase: {ph})")
        lane["hang"] = {
            "last_phase": last_phase,
            # the FINAL dump (faulthandler appends; early periodic dumps
            # are stale states) — main thread prints first within a dump
            "python_stacks": _last_dump(py_stacks)[:6000],
            "final_snapshot": final_snap,
            "timeline": timeline,
            "stderr_tail": raw_stderr[-10:],
        }
        lane["hang"]["attribution"] = _attribute_hang(lane["hang"])
        note({"progress": "device_probe_hang",
              "last_phase": last_phase.get("phase", "?"),
              "attribution": lane["hang"]["attribution"],
              "wchans": [t["wchan"] for t in final_snap["tasks"]][:8]})
    else:
        # the child may have printed RESULT between our last drain and
        # its exit — drain once more before judging
        drain()
    if not hung:
        if result_line is not None:
            try:
                child_result = json.loads(result_line)
                lane.update(child_result)
            except ValueError:
                lane["error"] = \
                    f"unparseable child result: {result_line[:200]}"
            try:
                child.wait(15)
            except subprocess.TimeoutExpired:
                child.kill()
        else:
            tail = raw_stderr[-10:]
            if stderr_buf:
                tail.append(stderr_buf[-200:].decode("utf-8", "replace"))
            lane["error"] = (
                f"probe child exited rc={child.returncode} without a "
                f"result; stderr tail: {' | '.join(tail)[-600:]}")
            if phases:
                lane["probe"]["last_phase"] = phases[-1]

    lane["probe"]["phases"] = phases[-12:]
    lane["probe"]["wall_s"] = round(time.monotonic() - t0, 1)
    _write_out(out_path, lane)
    return lane


# --------------------------------------------------------------------------
# child: the actual backend attempt + device-lane sweep
# --------------------------------------------------------------------------

def _child_note(obj: dict) -> None:
    print(json.dumps(obj), file=sys.stderr, flush=True)


def _child_main() -> None:
    import faulthandler

    budget_s = float(os.environ.get("BRPC_TPU_PROBE_BUDGET_S", "150"))
    t_start = time.monotonic()
    trace_path = os.environ.get("BRPC_TPU_PROBE_TRACE")
    trace_f = open(trace_path, "w") if trace_path else sys.stderr
    faulthandler.enable(file=trace_f)
    faulthandler.register(signal.SIGUSR1, file=trace_f, all_threads=True)
    # belt-and-braces: periodic dumps mean even a SIGKILL'd child leaves
    # the last stack on disk
    faulthandler.dump_traceback_later(15.0, repeat=True, file=trace_f)

    result: dict = {}

    if os.environ.get("BRPC_TPU_PROBE_SELFTEST_HANG"):
        # exercises the parent's whole forensic path (SIGUSR1 stack
        # dump, /proc timeline, kill) without touching a backend
        _child_note({"phase": "selftest_hang", "t": 0.0})
        time.sleep(10 ** 6)

    _child_note({"phase": "import_jax", "t": 0.0})
    import jax  # noqa: PLC0415 — the probe IS the import site

    _child_note({"phase": "jax_devices",
                 "t": round(time.monotonic() - t_start, 1)})
    # retry on EXCEPTION only (a transient UNAVAILABLE must not kill
    # the run); a HANG is the parent's department — it watches the
    # whole child with forensics armed, so no thread-timeout dance here
    t0 = time.perf_counter()
    devs = None
    for attempt, backoff in enumerate((0.0, 3.0, 8.0)):
        time.sleep(backoff)
        try:
            devs = jax.devices()
            break
        except Exception as e:  # noqa: BLE001 - retried bring-up
            _child_note({"phase": "jax_devices_retry", "attempt": attempt + 1,
                         "error": f"{type(e).__name__}: {e}"[:300]})
    if devs is None:
        raise RuntimeError("backend raised on every bring-up attempt "
                           "(see jax_devices_retry phases)")
    init_s = time.perf_counter() - t0
    faulthandler.cancel_dump_traceback_later()
    result["bringup"] = {
        "init_s": round(init_s, 2),
        "devices": [str(d) for d in devs],
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
    }
    _child_note({"phase": "backend_up", **result["bringup"],
                 "t": round(time.monotonic() - t_start, 1)})

    try:
        _child_lane(result, devs, budget_s, t_start)
    except BaseException as e:  # noqa: BLE001 - partial evidence > none
        # a lane failure must not discard the bring-up evidence the
        # probe exists to capture — and must stay localizable, so the
        # traceback rides along (the old crash path got it for free
        # via the parent's stderr capture)
        import traceback
        result["lane_error"] = f"{type(e).__name__}: {e}"[:400]
        result["lane_error_traceback"] = traceback.format_exc()[-1500:]
        _child_note({"phase": "lane_error", "error": result["lane_error"]})
    print("RESULT " + json.dumps(result), flush=True)
    # PjRt teardown from live threads can abort the interpreter;
    # everything is flushed, skip teardown (bench.py's own convention).
    # A lane that failed is a failed probe.
    os._exit(1 if "lane_error" in result else 0)


def _child_lane(result: dict, devs, budget_s: float,
                t_start: float) -> None:
    """Link floors + the ici:// echo sweep (runs only after a healthy
    bring-up; any failure here is reported as lane_error next to the
    bring-up data)."""
    if os.environ.get("BRPC_TPU_PROBE_SELFTEST_LANE_FAIL"):
        raise RuntimeError("selftest lane failure")
    import jax
    import numpy as np

    # link floors: what one H2D / D2H crossing costs on this fabric —
    # context for every sweep number
    probe = np.ones((1,), np.float32)
    x = jax.device_put(probe, devs[0])
    x.block_until_ready()
    np.asarray(x)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.device_put(probe, devs[0]).block_until_ready()
    result["link_floor_us"] = round((time.perf_counter() - t0) / 3 * 1e6, 1)
    t0 = time.perf_counter()
    for _ in range(3):
        np.asarray(jax.device_put(probe, devs[0]))
    result["d2h_floor_us"] = round((time.perf_counter() - t0) / 3 * 1e6, 1)
    _child_note({"phase": "link_floor",
                 "link_floor_us": result["link_floor_us"],
                 "d2h_floor_us": result["d2h_floor_us"]})

    # device lane: echo over ici:// with REAL byte movement per call
    # (request H2D-staged, response materialized D2H), the
    # rdma_performance sweep shape
    from brpc_tpu.bvar.latency_recorder import LatencyRecorder
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                              ServerOptions, Service)

    two_dev = len(devs) >= 2
    server_dev = 1 if two_dev else 0
    result["moved"] = (
        "request H2D-staged from a host buffer + response materialized "
        "D2H per call (host<->HBM link crossed twice)" if not two_dev else
        "request staged to dev0 then copied dev0->dev1 at the server, "
        "response copied back dev1->dev0, plus D2H per call")

    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("Bench")

    @svc.method()
    def Echo(cntl, request):
        if cntl.request_device_arrays:
            cntl.response_device_arrays = cntl.request_device_arrays
        return bytes(request)

    server.add_service(svc)
    ep = server.start(f"ici://127.0.0.1:0#device={server_dev}")
    ch = Channel(f"ici://127.0.0.1:{ep.port}#reply_device=0",
                 ChannelOptions(timeout_ms=120000))

    from pipeline_runner import run_pipelined

    def run_batch(iters: int, inflight: int, rec, device_buf) -> float:
        """Pipelined echo batch over the shared async-client core."""
        expect = device_buf.nbytes

        def issue(on_done):
            t_call = time.perf_counter_ns()

            def _done(cntl):
                try:
                    if cntl.failed():
                        raise RuntimeError(cntl.error_text)
                    out = np.asarray(cntl.response_device_arrays[0])
                    if out.nbytes != expect:
                        raise RuntimeError("size mismatch")
                    if rec is not None:
                        rec.record((time.perf_counter_ns() - t_call) / 1e3)
                except BaseException as e:  # noqa: BLE001
                    on_done(e)
                else:
                    on_done(None)

            ch.call("Bench", "Echo", b"", done=_done,
                    request_device_arrays=[device_buf])

        return run_pipelined(iters, inflight, issue, max(30.0, budget_s))

    def budget_left() -> float:
        return budget_s - (time.monotonic() - t_start)

    # headline: 1MB
    host_buf = np.ones(((1 << 20) // 4,), np.float32)
    warm_dt = run_batch(4, 16, None, host_buf)
    per_call = warm_dt / 4
    result["lane_kind"] = ch._get_socket().conn.lane_kind
    _child_note({"phase": "ici_warm",
                 "per_call_ms": round(per_call * 1e3, 1),
                 "lane_kind": result["lane_kind"]})
    iters = int(max(8, min(100, budget_left() * 0.35 / max(per_call, 1e-6))))
    rec = LatencyRecorder()
    dt = run_batch(iters, 16, rec, host_buf)
    result["headline_GBps"] = round(iters * (1 << 20) * 2 / dt / 1e9, 4)
    result["p50_us"] = round(rec.latency_percentile(0.5), 1)
    result["p99_us"] = round(rec.latency_percentile(0.99), 1)
    _child_note({"phase": "ici_headline", "iters": iters,
                 "GBps": result["headline_GBps"],
                 "p99_us": result["p99_us"]})

    # 4B-4MB sweep (rdma_performance's range)
    result["sweep"] = {}
    sizes = []
    size = 4
    while size <= 4 << 20:
        sizes.append(size)
        size *= 4
    for idx, sz in enumerate(sizes):
        if budget_left() < 5.0:
            result["sweep"][str(sz)] = {"skipped": "probe budget"}
            continue
        buf = np.ones((max(1, sz // 4),), np.float32)
        rec = LatencyRecorder()
        warm = run_batch(2, 8, None, buf)
        point_budget = max(1.0, budget_left() * 0.8 / max(1, len(sizes) - idx))
        it = int(max(4, min(16, point_budget / max(warm / 2, 1e-6))))
        dt = run_batch(it, 8, rec, buf)
        pt = {"GBps": round(it * buf.nbytes * 2 / dt / 1e9, 4),
              "avg_us": round(rec.latency(), 1),
              "p99_us": round(rec.latency_percentile(0.99), 1),
              "iters": it}
        result["sweep"][str(sz)] = pt
        _child_note({"phase": "sweep_point", "size": sz, **pt})

    # device observatory: what the per-lane cells COST
    # (device_stats_overhead_pct, alternating best-of on/off windows —
    # single pairs drift on shared sandboxes) and what the stage spans
    # ACCOUNT FOR per phase (stage/wire/ack µs per size class +
    # ici_stage_attribution_pct) — the honesty floor under the numbers
    # above; failures degrade to obs_error, never discard the sweep
    try:
        _obs_phase(result, run_batch, budget_left, np)
    except BaseException as e:  # noqa: BLE001 - evidence over crash
        result["obs_error"] = f"{type(e).__name__}: {e}"[:300]

    ch.close()


def _obs_phase(result: dict, run_batch, budget_left, np) -> None:
    """The observatory phase of the probe (see _child_lane)."""
    from brpc_tpu.butil.flags import set_flag
    from brpc_tpu.rpc.span import global_collector

    if budget_left() > 8.0:
        buf = np.ones(((256 << 10) // 4,), np.float32)
        # ORDER-BALANCED (off, on) pairs, MEDIAN over the per-pair
        # ratios (the device_obs_smoke estimator): always measuring
        # one arm second turns any warm-up or load ramp into fake
        # overhead, and cross-run minima drift more than the cells
        # cost on a shared box
        from brpc_tpu.bvar.latency_recorder import LatencyRecorder
        pair_pcts: List[float] = []
        for k in range(3):      # 3 pairs: a 2-pair "median" is the max
            t = {}
            for arm in ((False, True) if k % 2 == 0
                        else (True, False)):
                set_flag("device_stats_enabled", arm)
                rec = LatencyRecorder()
                run_batch(16, 8, rec, buf)
                # per-call MEDIAN, not window wall: jax/gc outliers
                # land on a few calls and wall time swallows them whole
                t[arm] = rec.latency_percentile(0.5)
            if t[False] > 0:
                pair_pcts.append(
                    (t[True] - t[False]) / t[False] * 100.0)
        set_flag("device_stats_enabled", True)
        if pair_pcts:
            s = sorted(pair_pcts)
            result["device_stats_overhead_pct"] = round(
                max(0.0, s[len(s) // 2]), 2)
        else:
            result["device_stats_overhead_pct"] = None
        _child_note({"phase": "device_stats_overhead",
                     "pct": result["device_stats_overhead_pct"]})

    # stage-resolved breakdown per phase (rpcz device spans)
    set_flag("rpcz_enabled", True)
    breakdown: dict = {}
    ratios: List[float] = []
    try:
        for sz in (4096, 256 << 10, 1 << 20):
            if budget_left() < 4.0:
                break
            global_collector.clear()
            buf = np.ones((max(1, sz // 4),), np.float32)
            run_batch(4, 4, None, buf)
            sends = [s for s in global_collector.recent(400)
                     if s.side == "device" and
                     (s.write_done_us or s.first_byte_us)]
            if not sends:
                continue
            ds = [s.to_dict() for s in sends]
            n = len(ds)
            breakdown[str(sz)] = {
                "n": n,
                "stage_us": round(sum(d["stage_us"] for d in ds) / n, 1),
                "wire_us": round(sum(d["wire_us"] for d in ds) / n, 1),
                "ack_us": round(sum(d["ack_us"] for d in ds) / n, 1),
                "lane": ds[0]["method"],
            }
            ratios.extend(
                (d["stage_us"] + d["wire_us"] + d["ack_us"])
                / d["latency_us"] for d in ds if d["latency_us"] > 0)
    finally:
        set_flag("rpcz_enabled", False)
    if breakdown:
        result["stage_breakdown"] = breakdown
    if ratios:
        result["ici_stage_attribution_pct"] = round(
            100.0 * sum(ratios) / len(ratios), 1)
        _child_note({"phase": "stage_breakdown", **breakdown,
                     "attribution_pct":
                     result["ici_stage_attribution_pct"]})


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--budget", type=float, default=float(
        os.environ.get("BRPC_TPU_DEVICE_BUDGET_S", "150")))
    ap.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                  "DEVICE_PROBE.json"))
    args = ap.parse_args()
    if args.child:
        _child_main()
        return
    lane = run_probe(args.budget, args.out,
                     progress=lambda o: print(json.dumps(o),
                                              file=sys.stderr, flush=True))
    print(json.dumps(lane), flush=True)
    if "error" in lane or "lane_error" in lane:
        sys.exit(1)


if __name__ == "__main__":
    main()
