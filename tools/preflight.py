"""Bench preflight: one process holds the chip.

A chip belongs to one process at a time: a stray jax-capable process
(an orphaned example server, a wedged smoke run) makes the next
client's backend bring-up fail or hang. Before the bench's probe child
touches the backend this:

1. scans /proc for OTHER live processes with libtpu mapped — a process
   maps it only when it has opened (or is trying to open) the TPU
   backend — and names them in the artifact, so a hung bring-up is
   attributable instead of mysterious;
2. kills leftovers the repo itself spawned, via the pidfile convention
   (.pids/<name>.pid written by Server.run_until_asked_to_quit and the
   tool servers) — only pids whose cmdline still points into this repo
   are signalled, so an unrelated recycled pid is never killed.

Returns a JSON-ready report either way; scanning failures degrade to
empty lists, never to a crash (the bench must run).
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

from brpc_tpu.butil.pidfile import (PID_DIR, cmdline,  # noqa: E402,F401
                                    remove_pidfile, write_pidfile)

_cmdline = cmdline   # single normalization authority: pidfile.cmdline


def scan_chip_holders() -> List[dict]:
    """Processes (other than us) with libtpu mapped: each holds the
    chip or is waiting for it."""
    me = os.getpid()
    out: List[dict] = []
    try:
        pids = [int(p) for p in os.listdir("/proc") if p.isdigit()]
    except OSError:
        return out
    for pid in pids:
        if pid == me:
            continue
        try:
            with open(f"/proc/{pid}/maps", "rb") as f:
                maps = f.read()
        except OSError:
            continue
        if b"libtpu" in maps:
            out.append({"pid": pid, "cmdline": _cmdline(pid)[:200]})
    return out


def kill_stale_repo_servers(grace_s: float = 2.0) -> List[dict]:
    """SIGTERM (then SIGKILL) every pidfile-recorded process whose
    LIVE cmdline still matches the cmdline recorded at pidfile-write
    time (a recycled pid never matches, so an unrelated process is
    never killed; a relative-path launch matches itself exactly). Reap
    pidfiles of dead/recycled pids; keep the file when a matching
    process somehow survives the SIGKILL, so the evidence remains."""
    actions: List[dict] = []
    try:
        entries = os.listdir(PID_DIR)
    except OSError:
        return actions
    victims = []
    for name in entries:
        path = os.path.join(PID_DIR, name)
        try:
            with open(path) as f:
                lines = f.read().splitlines()
            pid = int(lines[0].strip() or "0")
            recorded_cmd = lines[1].strip() if len(lines) > 1 else ""
        except (OSError, ValueError, IndexError):
            pid, recorded_cmd = 0, ""
        live_cmd = _cmdline(pid) if pid else ""
        if pid and live_cmd and recorded_cmd and live_cmd == recorded_cmd:
            try:
                os.kill(pid, signal.SIGTERM)
                victims.append((pid, path))
                actions.append({"pid": pid, "pidfile": name,
                                "cmdline": live_cmd[:200], "signal": "TERM"})
            except OSError as e:
                # kill failed (EPERM?) on a LIVE matching stray: keep
                # the pidfile — the evidence must survive for the next
                # preflight/operator
                actions.append({"pid": pid, "pidfile": name,
                                "cmdline": live_cmd[:200],
                                "error": f"{type(e).__name__}: {e}"[:120]})
            continue   # never unlink a live match here
        try:
            os.unlink(path)   # dead or recycled pid: stale record
        except OSError:
            pass
    if victims:
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline and any(
                os.path.exists(f"/proc/{p}") for p, _ in victims):
            time.sleep(0.1)
        for p, path in victims:
            if os.path.exists(f"/proc/{p}"):
                try:
                    os.kill(p, signal.SIGKILL)
                    actions.append({"pid": p, "signal": "KILL"})
                except OSError:
                    pass
            if not os.path.exists(f"/proc/{p}"):
                try:
                    os.unlink(path)   # confirmed dead: reap the record
                except OSError:
                    pass
    return actions


def run_preflight() -> dict:
    """The bench's first act: kill repo strays, then name anything else
    still holding the chip."""
    report: dict = {}
    try:
        report["killed"] = kill_stale_repo_servers()
    except Exception as e:  # noqa: BLE001 - evidence, not control flow
        report["killed_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        report["chip_holders"] = scan_chip_holders()
    except Exception as e:  # noqa: BLE001
        report["scan_error"] = f"{type(e).__name__}: {e}"[:200]
    return report


# --------------------------------------------------------------- gates
#
# `python tools/preflight.py --gate` is the correctness gate every PR
# runs for free: graftlint over the whole package (unwaived findings
# fail), a sanitizer smoke-build of both native artifacts (the cheap
# half of the tier-2 lane — the instrumented fuzz RUN lives in
# tests/test_sanitizer_lane.py), a seeded chaos smoke (one fault
# storm over mem://, tools/chaos.py), and a trace smoke (loopback
# multi-hop rpcz burst assembled + Perfetto-validated,
# tools/trace.py). docs/invariants.md, docs/robustness.md and
# docs/observability.md document them.

GATE_SANITIZERS = ("address", "undefined")


def gate_graftlint() -> dict:
    """Run graftlint over brpc_tpu/; ok iff no unwaived finding."""
    proc = subprocess.run(
        [sys.executable, "-m", "brpc_tpu.analysis", "brpc_tpu", "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout)
        out["active"] = len(report["active"])
        out["waived"] = len(report["waived"])
        if report["active"]:
            out["findings"] = [
                f"{f['path']}:{f['line']}: [{f['rule']}] {f['message']}"
                for f in report["active"]]
    except (ValueError, KeyError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_locklint() -> dict:
    """graftlint v2's lock lane, gated standalone: the full-tree lock
    rules (lock-cycle / callback-under-lock / blocking-under-lock plus
    the learned-invariant pack) must report zero unwaived findings, AND
    a mutation smoke must prove the rules still bite — stripping the
    real guards (moving the batcher's callback fire inside its lock,
    dropping ici's memoryview release) must make the rules fire. A
    silent rule is worse than no rule."""
    lock_rules = ("lock-cycle,callback-under-lock,blocking-under-lock,"
                  "sampler-no-lazy-import,event-wait-not-sleep,"
                  "memoryview-release")
    proc = subprocess.run(
        [sys.executable, "-m", "brpc_tpu.analysis", "brpc_tpu",
         "--rules", lock_rules, "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout)
        out["active"] = len(report["active"])
        out["waived"] = len(report["waived"])
        if report["active"]:
            out["findings"] = [
                f"{f['path']}:{f['line']}: [{f['rule']}] {f['message']}"
                for f in report["active"][:10]]
    except (ValueError, KeyError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
        return out
    # mutation smoke, in-process over mutated SourceFiles: the real
    # modules with their real guards stripped must trip the rules
    try:
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.lock_graph import (
            CallbackUnderLockRule,
        )
        from brpc_tpu.analysis.rules.memoryview_release import (
            MemoryviewReleaseRule,
        )
        muts = []
        # 1. batcher: fire callbacks INSIDE the lock (the PR 8 bug)
        bpath = os.path.join(REPO_ROOT, "brpc_tpu", "serving",
                             "batcher.py")
        bsrc = open(bpath).read()
        mutated = bsrc.replace(
            "        self._fire(emits, done)\n        if stats_on:",
            "            self._fire(emits, done)\n        if stats_on:")
        assert mutated != bsrc
        sf = SourceFile(bpath, "brpc_tpu/serving/batcher.py", mutated)
        found = list(CallbackUnderLockRule().finalize(
            _fresh_ctx([sf])))
        muts.append(("callback-under-lock",
                     any(f.rule == "callback-under-lock"
                         for f in found)))
        # 2. ici: drop the finally: mv.release() (the PR 6 BufferError)
        ipath = os.path.join(REPO_ROOT, "brpc_tpu", "transport",
                             "ici.py")
        isrc = open(ipath).read()
        mutated = isrc.replace(
            "                    finally:\n"
            "                        mv.release()\n", "")
        assert mutated != isrc
        sf = SourceFile(ipath, "brpc_tpu/transport/ici.py", mutated)
        found = list(MemoryviewReleaseRule().check(sf, _fresh_ctx([sf])))
        muts.append(("memoryview-release",
                     any(f.rule == "memoryview-release"
                         for f in found)))
        out["mutations"] = {name: fired for name, fired in muts}
        if not all(fired for _, fired in muts):
            out["ok"] = False
            out["error"] = "mutation smoke: a stripped guard went unseen"
    except Exception as e:  # noqa: BLE001 - gate must report, not die
        out["ok"] = False
        out["error"] = f"mutation smoke failed: {type(e).__name__}: {e}"
    return out


def _fresh_ctx(files):
    from brpc_tpu.analysis.core import Context
    return Context(files)


def gate_guard_lint() -> dict:
    """The guarded-by lane: zero unwaivered CONFIRMED findings on the
    full tree (PLAUSIBLE rows are ranked advice, not gate failures),
    plus a mutation smoke proving the rule still bites — re-stripping
    the two lock holds ISSUE 16 added (Recorder._write_batch's counter
    block, TaskControl.stop_and_join's pool teardown) must re-surface
    their cross-role CONFIRMED findings. BRPC_TPU_GUARD_LINT=0
    skips."""
    if os.environ.get("BRPC_TPU_GUARD_LINT", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_GUARD_LINT=0"}
    proc = subprocess.run(
        [sys.executable, "-m", "brpc_tpu.analysis", "brpc_tpu",
         "--rules", "guarded-by", "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {}
    try:
        report = json.loads(proc.stdout)
        confirmed = [f for f in report["active"]
                     if "[CONFIRMED]" in f["message"]]
        out["ok"] = not confirmed
        out["confirmed"] = len(confirmed)
        out["plausible"] = len(report["active"]) - len(confirmed)
        out["waived"] = len(report["waived"])
        if confirmed:
            out["findings"] = [
                f"{f['path']}:{f['line']}: {f['message']}"
                for f in confirmed[:10]]
    except (ValueError, KeyError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
        return out
    # mutation smoke: the real tree with this PR's own fixes reverted
    # must re-flag the races they closed
    try:
        from brpc_tpu.analysis.core import SourceFile, iter_source_files
        from brpc_tpu.analysis.rules.guarded_by import GuardedByRule
        muts = []
        for relpath, field, old, new in (
            ("brpc_tpu/traffic/capture.py", "Recorder.written",
             "        w.flush()\n        with self._lock:\n",
             "        w.flush()\n        if True:\n"),
            ("brpc_tpu/fiber/scheduler.py", "TaskControl._threads",
             "        with self._start_lock:\n"
             "            # claim the pool under the same lock",
             "        if True:\n"
             "            # claim the pool under the same lock"),
        ):
            files = iter_source_files(
                [os.path.join(REPO_ROOT, "brpc_tpu")])
            path = os.path.join(REPO_ROOT, relpath)
            src = open(path).read()
            mutated = src.replace(old, new)
            assert mutated != src, relpath
            files = [SourceFile(path, relpath, mutated)
                     if sf.relpath == relpath else sf for sf in files]
            found = list(GuardedByRule().finalize(_fresh_ctx(files)))
            muts.append((field, any(
                f.path == relpath and field in f.message
                and "[CONFIRMED]" in f.message for f in found)))
        out["mutations"] = {name: fired for name, fired in muts}
        if not all(fired for _, fired in muts):
            out["ok"] = False
            out["error"] = "mutation smoke: a stripped guard went unseen"
    except Exception as e:  # noqa: BLE001 - gate must report, not die
        out["ok"] = False
        out["error"] = f"mutation smoke failed: {type(e).__name__}: {e}"
    return out


def gate_racelane() -> dict:
    """The racelane seeded-interleaving smoke (python -m
    brpc_tpu.analysis.racelane --smoke under BRPC_TPU_LOCK_DEBUG=1): a
    seeded AB/BA inversion must be detected deterministically (same
    first violation, two runs) and the real batcher must run a
    submit/step/cancel storm clean under perturbation.
    BRPC_TPU_RACELANE_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_RACELANE_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_RACELANE_SMOKE=0"}
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "BRPC_TPU_LOCK_DEBUG": "1",
                "BRPC_TPU_LOCK_SEED": env.get("BRPC_TPU_LOCK_SEED",
                                              "42")})
    proc = subprocess.run(
        [sys.executable, "-m", "brpc_tpu.analysis.racelane", "--smoke"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout)
        for k in ("inversion_detected", "inversion_deterministic",
                  "real_code_clean"):
            out[k] = report.get(k)
        out["stats"] = report.get("real_code", {}).get("stats")
        fr = report.get("field_races", {})
        out["field_races"] = {
            name: {"expect_race": p.get("expect_race"),
                   "raced": p.get("raced"),
                   "evidence": p.get("evidence", [])[:2]}
            for name, p in fr.get("pairs", {}).items()}
        out["field_races_ok"] = fr.get("ok")
    except ValueError:
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_sanitizer_smoke() -> dict:
    """Build both native artifacts under ASan/UBSan (separate .san.so
    cache — the plain lane is untouched). A missing sanitizer
    toolchain SKIPS (ok) with the reason named; a build failure under
    instrumentation FAILS the gate."""
    from brpc_tpu.native.build import (build, build_fastcore,
                                       sanitizer_toolchain_missing)
    missing = sanitizer_toolchain_missing(GATE_SANITIZERS)
    if missing:
        return {"ok": True, "skipped": f"toolchain lacks {missing}"}
    try:
        lib = build(sanitize=GATE_SANITIZERS)
        fast = build_fastcore(sanitize=GATE_SANITIZERS)
    except RuntimeError as e:
        return {"ok": False, "error": str(e)[-800:]}
    return {"ok": True, "artifacts": [os.path.basename(lib),
                                      os.path.basename(fast)]}


def gate_trace_smoke() -> dict:
    """Loopback multi-hop burst with rpcz_dir set (tools/trace.py
    --smoke): spans persist, assemble into per-call trace chains, and
    the Perfetto export loads with every event well-formed. A
    subprocess so a wedged burst cannot hang the gate."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "trace.py"),
         "--smoke"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout)
        if proc.returncode == 0:
            out["spans"] = report["spans"]
            out["chains"] = report["chains"]
            out["perfetto_slices"] = report["perfetto_slices"]
        else:
            out["invariant"] = report.get("invariant")
    except (ValueError, KeyError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_shard_smoke() -> dict:
    """One 2-shard reuseport group (tools/shard_server.py --smoke):
    connections spread, a SIGKILLed shard restarts within the backoff
    budget with zero errors on surviving shards' channels, retried
    calls on the victim's connections succeed, and the merged /vars
    counters equal the sum of the per-shard dumps. A subprocess so a
    wedged group cannot hang the gate."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "shard_server.py"), "--smoke"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        if proc.returncode == 0:
            out["elapsed_s"] = report["smoke"]["elapsed_s"]
            out["restart_s"] = report["smoke"]["restart_s"]
            out["survivor_calls"] = report["smoke"]["survivor_calls"]
        else:
            out["invariant"] = report.get("invariant")
    except (ValueError, KeyError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_chaos_smoke() -> dict:
    """One seeded fault storm over mem:// (tools/chaos.py --smoke,
    ~10s budget): deadline shedding >= 99%, every call reaches a
    verdict, flapped peer isolated-then-revived, zero leaks. A
    subprocess so a wedged storm cannot hang the gate."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "chaos.py"),
         "--smoke"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout)
        if proc.returncode == 0:
            out["elapsed_s"] = report["smoke"]["elapsed_s"]
            out["shed_ratio"] = \
                report["smoke"]["deadline"]["expired_shed_ratio"]
        else:
            out["invariant"] = report.get("invariant")
    except (ValueError, KeyError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


# Machine-relative perf floors (tools/perf_smoke.py measures the
# ratios; absolute QPS/GB/s do NOT transfer across harnesses). The
# reference points are the BENCH_r05-era capture re-expressed as
# ratios on this codebase at ISSUE-4 time, times the 30%-regression
# allowance:
#   mb_eff    r05 efficiency_vs_stream_raw 0.654  -> floor 0.654*0.7
#   qps_ratio sync-RPC qps / raw ping-pong qps, ~0.45 measured at
#             ISSUE-4 close                        -> floor 0.45*0.7*0.8
# (the extra 0.8 on qps_ratio absorbs scheduler-noise variance seen on
# shared sandboxes; a real hot-path regression blows through 30%+20%).
# Overrides for slow/weird machines: BRPC_TPU_PERF_SMOKE=0 skips the
# gate entirely; BRPC_TPU_PERF_FLOOR_SCALE scales both floors.
PERF_FLOORS = {"mb_eff": 0.458, "qps_ratio": 0.25}

# Device-lane floors (tools/device_perf_smoke.py), machine-relative by
# the same discipline: ratios against a host-payload RPC burst in the
# same process. ISSUE-19-close calibration on cpu-dryrun loopback:
#   headline_ratio        2.86-3.42 measured -> floor 2.9 * 0.7
#   small_latency_ratio   1.6-2.33 measured (lower is better) ->
#                         ceiling 2.33 * 1.5 (30% + sandbox noise)
# BRPC_TPU_PERF_SMOKE=0 skips; BRPC_TPU_PERF_FLOOR_SCALE scales the
# floor down / the ceiling up for slow machines.
DEVICE_PERF_FLOOR_HEADLINE_RATIO = 2.0
DEVICE_PERF_CEIL_SMALL_RATIO = 3.5


def gate_flight_smoke() -> dict:
    """Flight-recorder smoke (tools/flight_smoke.py): a loopback PyEcho
    burst under continuous profiling must capture PyEcho frames with
    >=80% busy-sample attribution, profiler-on qps must stay within 5%
    of profiler-off, and /census totals must equal the sum of the
    per-connection rows. A subprocess so a wedged burst cannot hang the
    gate. BRPC_TPU_FLIGHT_SMOKE=0 skips; BRPC_TPU_PERF_SMOKE=0 skips
    only the overhead criterion (capture + census still run)."""
    if os.environ.get("BRPC_TPU_FLIGHT_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_FLIGHT_SMOKE=0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "flight_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        for k in ("profiler_overhead_pct", "attribution_ratio",
                  "pyecho_in_folded", "census_ok", "qps_on", "qps_off"):
            if k in report:
                out[k] = report[k]
        if proc.returncode != 0:
            out["invariant"] = report.get("invariant", report.get("error"))
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_cluster_top() -> dict:
    """Cluster-observatory smoke (tools/cluster_top.py --smoke): a
    cluster-channel burst at two spawned backends must land 100% of
    attempts on backend stat-cell rows, the HTTP-scraped /backends
    totals must equal the in-process channel bvar sums, the cross-node
    merge math must reproduce them, and the cells must cost <= 5% qps
    on vs off (BRPC_TPU_PERF_SMOKE=0 skips just that criterion). A
    subprocess so a wedged burst cannot hang the gate;
    BRPC_TPU_CLUSTER_SMOKE=0 skips the lane."""
    if os.environ.get("BRPC_TPU_CLUSTER_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_CLUSTER_SMOKE=0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "cluster_top.py"), "--smoke"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        for k in ("backends", "attempts", "scrape_matches_bvars",
                  "attributed", "merge_matches",
                  "backend_stats_overhead_pct", "qps_on", "qps_off"):
            if k in report:
                out[k] = report[k]
        if proc.returncode != 0:
            out["invariant"] = report.get("invariant", report.get("error"))
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_serving_smoke() -> dict:
    """Serving-lane smoke (tools/serving_smoke.py --smoke): a 2-shard
    GenerateService under a mixed stream/HTTP/evict/overflow client set
    — every request must end in exactly one of completed/evicted/shed,
    TTFT must sit measurably below full-generation latency (streaming
    is incremental, not buffered), and the supervisor's merged /serving
    must account for the whole set. A subprocess so a wedged engine
    cannot hang the gate; BRPC_TPU_SERVING_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_SERVING_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_SERVING_SMOKE=0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "serving_smoke.py"), "--smoke"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        if proc.returncode == 0:
            smoke = report["smoke"]
            out["outcomes"] = smoke["outcomes"]
            out["ttft_p50_ms"] = smoke["ttft_p50_ms"]
            out["full_gen_p50_ms"] = smoke["full_gen_p50_ms"]
            out["elapsed_s"] = smoke["elapsed_s"]
        else:
            out["invariant"] = report.get("invariant")
    except (ValueError, KeyError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_fabric_smoke() -> dict:
    """Overload-control fabric storm (tools/fabric_smoke.py --smoke
    --shards 2 --corpus auto, ~15s): three 2-shard nodes behind
    budget-hedging ClusterChannels — one node SIGKILLed mid-burst +
    one stalled must leave the non-shed survivor error rate 0 with
    goodput >= 0.7x fault-free, a full-outage window must keep WIRE
    retry amplification <= 1.2x (retry token bucket), no hedge may be
    armed past budget (rpcz attempt-span evidence), and the cluster
    must recover after the nodes respawn. The corpus-fed press tail
    (ISSUE 14) then drives >= 2x capacity: highest-priority goodput
    >= 0.9 once thresholds converge, per-priority goodput ordered by
    class, and >= 50% of doomed low-priority sends shed CLIENT-side
    via the piggybacked admission threshold. BRPC_TPU_PERF_SMOKE=1
    (default) also prices the calm-path admission layer:
    admission_overhead_pct <= 5% with no priorities/weights
    configured (pair-median alternating windows). A subprocess so a
    wedged storm cannot hang the gate; ONE retry round absorbs the
    shared sandbox's worst scheduling jitter (a real regression fails
    both). BRPC_TPU_FABRIC_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_FABRIC_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_FABRIC_SMOKE=0"}
    out: dict = {}
    for attempt in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "fabric_smoke.py"), "--smoke",
             "--shards", "2", "--corpus", "auto"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=180)
        out = {"ok": proc.returncode == 0, "attempt": attempt + 1}
        try:
            report = json.loads(proc.stdout.strip().splitlines()[-1])
            for k in ("fault_goodput_ratio", "fault_p99_ms",
                      "outage_amplification", "hedges_armed",
                      "hedges_past_budget", "revived",
                      "priority_goodput_hi_ratio",
                      "press_client_shed_frac", "press_priority_sheds"):
                out[k] = report.get(k)
            if proc.returncode != 0:
                out["problems"] = report.get("problems")
        except (ValueError, IndexError):
            out["ok"] = False
            out["error"] = (proc.stdout + proc.stderr)[-500:]
        if out["ok"]:
            break
    if out.get("ok") and os.environ.get("BRPC_TPU_PERF_SMOKE",
                                        "1") != "0":
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "fabric_smoke.py"),
             "--overhead"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=180)
        try:
            rep = json.loads(proc.stdout.strip().splitlines()[-1])
            out["admission_overhead_pct"] = rep.get(
                "admission_overhead_pct")
            if proc.returncode != 0:
                out["ok"] = False
                out["problems"] = (out.get("problems") or []) + [
                    f"admission overhead "
                    f"{rep.get('admission_overhead_pct')}% > 5%"]
        except (ValueError, IndexError):
            out["ok"] = False
            out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_device_obs() -> dict:
    """Device-observatory smoke (tools/device_obs_smoke.py, cpu-dryrun
    lane, ~3s): an ici:// loopback transfer burst must produce
    stage-resolved device spans accounting for >= 90% of transfer wall
    time (child spans of the owning RPC spans), cells must balance
    after close (transfers == completed + failed, bytes == corpus),
    the /device HTTP page + supervisor merge must agree with the
    in-process builder, and the cells must cost <= 5% on-vs-off on
    pipelined pair-median windows (BRPC_TPU_PERF_SMOKE=0 skips just
    that criterion). A subprocess so a wedged lane cannot hang the
    gate; ONE retry round absorbs the shared sandbox's sustained load
    bursts (the fabric-gate precedent — a real overhead regression
    fails both); BRPC_TPU_DEVICE_OBS_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_DEVICE_OBS_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_DEVICE_OBS_SMOKE=0"}
    out: dict = {}
    for attempt in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "device_obs_smoke.py")],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        out = {"ok": proc.returncode == 0, "attempt": attempt + 1}
        try:
            report = json.loads(proc.stdout.strip().splitlines()[-1])
            for k in ("device_spans", "ici_stage_attribution_pct",
                      "device_stats_overhead_pct", "transfer_lane",
                      "elapsed_s"):
                if k in report:
                    out[k] = report[k]
            if proc.returncode != 0:
                out["problems"] = report.get("problems",
                                             report.get("error"))
        except (ValueError, IndexError):
            out["ok"] = False
            out["error"] = (proc.stdout + proc.stderr)[-500:]
        if out["ok"]:
            break
    return out


def gate_serving_obs() -> dict:
    """Serving-observatory smoke (tools/serving_obs_smoke.py, cpu-dryrun
    lane, ~3s): a mixed-length generate burst must produce serving
    spans whose queue/prefill/decode/emit stages account for >= 90% of
    each generation's stream latency (children of the owning RPC
    spans), the /serving HTTP page + supervisor merge must agree with
    the in-process pane on the per-method counters, the step ring must
    carry the burst's iterations, and the flight deck must cost <= 5%
    on-vs-off on per-step pair-median windows (BRPC_TPU_PERF_SMOKE=0
    skips just that criterion). A subprocess so a wedged engine cannot
    hang the gate; ONE retry round absorbs the shared sandbox's
    sustained load bursts (a real overhead regression fails both);
    BRPC_TPU_SERVING_OBS_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_SERVING_OBS_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_SERVING_OBS_SMOKE=0"}
    out: dict = {}
    for attempt in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "serving_obs_smoke.py")],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        out = {"ok": proc.returncode == 0, "attempt": attempt + 1}
        try:
            report = json.loads(proc.stdout.strip().splitlines()[-1])
            for k in ("serving_spans", "serving_stage_attribution_pct",
                      "serving_stats_overhead_pct", "elapsed_s"):
                if k in report:
                    out[k] = report[k]
            if proc.returncode != 0:
                out["problems"] = report.get("problems",
                                             report.get("error"))
        except (ValueError, IndexError):
            out["ok"] = False
            out["error"] = (proc.stdout + proc.stderr)[-500:]
        if out["ok"]:
            break
    return out


def gate_traffic_smoke() -> dict:
    """Traffic-engine smoke (tools/traffic_smoke.py, ~4s): record a
    paced mixed-size/mixed-priority burst through the live capture
    path, assert the corpus reproduces per-method counts EXACTLY (and
    leaks nothing in the recorder), then replay it at 2x time-warp and
    assert replayed counts match with the wall time landing near half
    the recorded span (interarrival error in tolerance) and schedule
    fidelity >= 85. A subprocess so a wedged replay cannot hang the
    gate; BRPC_TPU_TRAFFIC_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_TRAFFIC_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_TRAFFIC_SMOKE=0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "traffic_smoke.py"), "--smoke"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        for k in ("recorded", "replayed", "replay_fidelity_pct",
                  "replay_elapsed_s", "recorded_span_s", "elapsed_s"):
            if k in report:
                out[k] = report[k]
        if proc.returncode != 0:
            out["problems"] = report.get("problems")
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_timeline_smoke() -> dict:
    """Telemetry-time-machine smoke (tools/timeline_smoke.py, ~3s
    plus overhead windows): a paced burst's 1s series buckets must
    equal the counter deltas EXACTLY, an injected fault must open
    exactly one incident that names the implicated vars and annotates
    an in-window rpcz span, HTTP /timeline must equal the builtin twin
    structurally, the supervisor merge must reproduce the per-bucket
    shard-dump sum (p99 per-bucket MAX, never the average), and the
    series engine must cost <= 5% on order-balanced pair-median
    windows (the PR 12 estimator; BRPC_TPU_PERF_SMOKE=0 skips just
    that criterion). A subprocess so a wedged burst cannot hang the
    gate; BRPC_TPU_TIMELINE_SMOKE=0 skips the lane."""
    if os.environ.get("BRPC_TPU_TIMELINE_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_TIMELINE_SMOKE=0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "timeline_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        for k in ("bucket_exact", "incidents_opened", "incident_ok",
                  "twin_parity", "merged_ok", "series_overhead_pct",
                  "elapsed_s"):
            if k in report:
                out[k] = report[k]
        if proc.returncode != 0:
            out["invariant"] = report.get("invariant",
                                          report.get("error"))
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_incident_smoke() -> dict:
    """Incident-time-machine smoke (tools/incident_smoke.py): a
    concurrency-press wave must open an incident, arm a bounded
    capture window and bundle ONE size-capped .brpcinc artifact naming
    the trigger key; HTTP /incidents must equal the builtin twin and
    serve only ledgered downloads; replay_incident must re-fire the
    watchdog on the same key while the fix-forward run stays green;
    the supervisor merge must sum/tag the shard sections; and arming
    must cost <= 5% on order-balanced pair-median windows
    (BRPC_TPU_PERF_SMOKE=0 skips just that criterion). A subprocess so
    a wedged replay cannot hang the gate; BRPC_TPU_INCIDENT_SMOKE=0
    skips the lane."""
    if os.environ.get("BRPC_TPU_INCIDENT_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_INCIDENT_SMOKE=0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "incident_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        for k in ("press_sheds", "e2e_ok", "artifacts",
                  "corpus_records", "twin_parity", "status_line_ok",
                  "download_ok", "replay_refired", "fix_forward_quiet",
                  "merged_ok", "arm_overhead_pct", "elapsed_s"):
            if k in report:
                out[k] = report[k]
        if proc.returncode != 0:
            out["invariant"] = report.get("invariant",
                                          report.get("error"))
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
    return out


def gate_perf_smoke() -> dict:
    """Fast hot-path perf gate: raw-socket-normalized small-RPC and
    1MB-echo ratios must stay within 30% of the BENCH_r05-era floors.
    A subprocess so a wedged bench cannot hang the gate."""
    if os.environ.get("BRPC_TPU_PERF_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_PERF_SMOKE=0"}
    try:
        scale = float(os.environ.get("BRPC_TPU_PERF_FLOOR_SCALE", "1.0"))
    except ValueError:
        scale = 1.0
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "perf_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
        return out
    out.update(report)
    if not out["ok"]:
        return out
    for key, floor in PERF_FLOORS.items():
        floor *= scale
        got = report.get(key)
        if got is None:
            # calibration failed (raw echo didn't run): report, don't
            # fail — an absent ratio is a measurement problem, not a
            # perf regression
            out[f"{key}_floor"] = round(floor, 3)
            out[f"{key}_missing"] = True
            continue
        out[f"{key}_floor"] = round(floor, 3)
        if got < floor:
            out["ok"] = False
            out["regression"] = f"{key} {got} < floor {round(floor, 3)}"
    # shard scaling is MACHINE-RELATIVE by construction: the shard
    # count derives from the core count inside perf_smoke (skipped
    # below 4 cores), and the floor scales with it — 0.4x per shard
    # tolerates sandbox scheduling noise while a real serialization
    # regression (scaling ~1) still fails by a wide margin.
    if "shard_scaling" in report:
        sfloor = 0.4 * report.get("shard_count", 0) * scale
        out["shard_scaling_floor"] = round(sfloor, 2)
        if report["shard_scaling"] < sfloor:
            out["ok"] = False
            out["regression"] = (f"shard_scaling {report['shard_scaling']}"
                                 f" < floor {round(sfloor, 2)}")
    elif "shard_skipped" not in report and \
            "shard_error" not in report and \
            os.cpu_count() and os.cpu_count() >= 4:
        out["ok"] = False
        out["regression"] = "shard_scaling missing from perf smoke"
    return out


def gate_device_perf() -> dict:
    """Device-lane perf gate (tools/device_perf_smoke.py): the ici://
    loopback's 1MB headline must stay >= 2x a host-payload burst on
    the same box (floor = calibration * 0.7) and the 4B-16KB
    small-batch latency must stay within 3.5x of the host small-RPC
    burst — the pair the pipelined-window + coalescing work moves. A
    subprocess so a wedged lane cannot hang the gate;
    BRPC_TPU_PERF_SMOKE=0 skips."""
    if os.environ.get("BRPC_TPU_PERF_SMOKE", "1") == "0":
        return {"ok": True, "skipped": "BRPC_TPU_PERF_SMOKE=0"}
    try:
        scale = float(os.environ.get("BRPC_TPU_PERF_FLOOR_SCALE", "1.0"))
    except ValueError:
        scale = 1.0
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "device_perf_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=420)
    out: dict = {"ok": proc.returncode == 0}
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        out["ok"] = False
        out["error"] = (proc.stdout + proc.stderr)[-500:]
        return out
    out.update(report)
    if not out["ok"]:
        return out
    floor = DEVICE_PERF_FLOOR_HEADLINE_RATIO * scale
    ceil = DEVICE_PERF_CEIL_SMALL_RATIO / max(scale, 1e-9)
    out["headline_ratio_floor"] = round(floor, 2)
    out["small_latency_ratio_ceil"] = round(ceil, 2)
    got = report.get("headline_ratio")
    if got is None:
        out["headline_ratio_missing"] = True
    elif got < floor:
        out["ok"] = False
        out["regression"] = (f"headline_ratio {got} < floor "
                             f"{round(floor, 2)}")
    got = report.get("small_latency_ratio")
    if got is None:
        out["small_latency_ratio_missing"] = True
    elif got > ceil:
        out["ok"] = False
        out["regression"] = (f"small_latency_ratio {got} > ceiling "
                             f"{round(ceil, 2)}")
    return out


def run_gate() -> int:
    report = {}
    for name, fn in (("graftlint", gate_graftlint),
                     ("locklint", gate_locklint),
                     ("guard_lint", gate_guard_lint),
                     ("racelane", gate_racelane),
                     ("sanitizer_smoke", gate_sanitizer_smoke),
                     ("chaos_smoke", gate_chaos_smoke),
                     ("trace_smoke", gate_trace_smoke),
                     ("shard_smoke", gate_shard_smoke),
                     ("flight_smoke", gate_flight_smoke),
                     ("cluster_top", gate_cluster_top),
                     ("serving_smoke", gate_serving_smoke),
                     ("fabric_smoke", gate_fabric_smoke),
                     ("traffic_smoke", gate_traffic_smoke),
                     ("device_obs", gate_device_obs),
                     ("serving_obs", gate_serving_obs),
                     ("timeline_smoke", gate_timeline_smoke),
                     ("incident_smoke", gate_incident_smoke),
                     ("device_perf", gate_device_perf),
                     ("perf_smoke", gate_perf_smoke)):
        try:
            report[name] = fn()
        except Exception as e:  # noqa: BLE001 - a hung/crashed gate
            # must still yield the structured report, not a traceback
            report[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:800]}
    ok = all(g.get("ok") for g in report.values())
    report["ok"] = ok
    print(json.dumps(report, indent=2))
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        description="bench preflight (default) or the per-PR "
                    "correctness gate (--gate)")
    p.add_argument("--gate", action="store_true",
                   help="run graftlint + sanitizer smoke-build; exit 1 "
                        "on any unwaived finding or build failure")
    args = p.parse_args(argv)
    if args.gate:
        return run_gate()
    print(json.dumps(run_preflight(), indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
