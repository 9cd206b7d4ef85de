"""One cell of the benchmark, one process, one result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process is the only one that touches JAX: it holds the chip(s), runs
server and client of the cell, makes requests and weights on the device
from ``--seed``, warms the cell's own shapes (set-up), measures for
``--seconds`` and prints, as the LAST line of stdout, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics. Everything else
goes to earlier lines (JSON objects under the key ``info``) or to stderr.

A platform other than ``tpu``, or fewer chips than the cell asks for, is
exit 2 with no result: nothing falls back to the CPU. ``--rehearse``
(CPU, tiny sizes, four virtual devices) only debugs the command; its
numbers are no measurements and an earlier line says so.

Which cells, metrics, traffic mixes, services and drivers exist is data:
``BENCHMARK.json`` and the files it names under ``benchmark/``. This file
lists none of them.
"""

from __future__ import annotations

import argparse
import faulthandler
import glob
import json
import os
import shutil
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

WALL_LIMIT_S = 1100          # the first run of a cell may take 1200 s
TRACE_SECONDS = 2.0          # the profiler records the window's last part
WARM_SECONDS = 0.5           # the driver's own path once before the window
EXIT_LIMIT_S = 150
OUT_DIR = os.path.join(_ROOT, "benchmark_out")   # inside the checkout
INJECTIONS = ("corrupt_response", "device_imbalance")


def process_age_s() -> float:
    """Seconds since this process started (set-up counts from there)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


def info(**kw) -> None:
    print(json.dumps({"info": kw}, default=str), flush=True)


def log(msg: str) -> None:
    print(f"[benchmark +{process_age_s():6.1f}s] {msg}", file=sys.stderr,
          flush=True)


class Context:
    """What a service file gets to build its deployment from."""

    def __init__(self, cell, seed, devices, stamps, inject):
        self.cell = cell
        self.seed = seed
        self.devices = devices
        self.stamps = stamps
        self.inject = inject


class RunData:
    """What a metric reader gets: the window's samples, counter deltas
    and, in a traced run, the reduced trace."""

    def __init__(self, cell, stamps, win, counters, setup_s, first_seq,
                 bad_responses, trace, devices, device_kind):
        self.cell = cell
        self.setup_s = setup_s
        self.window_s = (win.end_ns - win.start_ns) / 1e9
        self._win_start_ns = win.start_ns
        self.handovers = [h for h in stamps.handovers if h[0] >= first_seq]
        self.calls = [c for c in stamps.calls if c[0] >= first_seq]
        self.latencies_us = [(r - i) / 1e3 for _s, i, r in self.calls]
        self.in_window = sum(1 for _s, _i, r in self.calls
                             if r <= win.end_ns)
        self.verified_calls = max(0, len(self.calls) - bad_responses)
        self.handlers: dict = {}
        for seq, shard, t0, t1 in stamps.handlers:
            if seq >= first_seq:
                self.handlers.setdefault(seq, []).append((shard, t0, t1))
        self.counters = counters
        self.trace = trace
        self.trace_devices = list(range(cell.chips))
        self.devices = devices
        self.device_kind = device_kind

    def per_second(self) -> list:
        """[completions, median call time in us] in each whole second of
        the window."""
        from benchmark.lib.stats import median
        bins: dict = {}
        for _s, i, r in self.calls:
            bins.setdefault(int((r - self._win_start_ns) // 10**9),
                            []).append((r - i) / 1e3)
        return [[len(bins.get(k, ())),
                 median(bins[k]) if k in bins else None]
                for k in range(int(self.window_s))]

    def generator_wait_us(self) -> dict:
        from benchmark.lib.stats import median, tail
        waits = [(p - d) / 1e3 for _s, d, p, _r in self.handovers]
        if not waits:
            return {}
        return {"p50": median(waits), "p99": tail(waits, 0.99),
                "not_ready_at_done": sum(1 for h in self.handovers
                                         if not h[3])}

    def peaks(self) -> dict:
        from benchmark.lib.peaks import peaks_for
        return peaks_for(self.device_kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, four virtual devices: debugs "
                         "the command, measures nothing")
    ap.add_argument("--inject", default="", choices=("",) + INJECTIONS,
                    help="rehearsal only: break the run on purpose, to "
                         "prove that `correct` follows")
    args = ap.parse_args(argv)
    if args.inject and not args.rehearse:
        ap.error("--inject needs --rehearse")

    faulthandler.enable()
    faulthandler.dump_traceback_later(WALL_LIMIT_S, exit=True)

    from benchmark.lib import counters as counters_mod
    from benchmark.lib.loader import Cell, load_module
    from benchmark.lib.stamps import Stamps

    cell = Cell(args.workload, rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()

    import jax

    from brpc_tpu.butil.jax_runtime import ensure_compile_cache

    if args.rehearse:
        info(rehearsal=True, note="CPU rehearsal at tiny sizes: no number "
             "below is a measurement")
    cache_dir = ensure_compile_cache()
    compiles: list = []          # monotonic time of every program built
    cache = {"dir": cache_dir, "hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    def on_duration(name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.monotonic())
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    all_devices = jax.devices()
    device = {"platform": all_devices[0].platform,
              "kind": all_devices[0].device_kind, "count": len(all_devices)}
    log(f"device: {device}")
    if not args.rehearse and device["platform"] != "tpu":
        print(f"benchmark: JAX found platform {device['platform']!r}, not "
              "a TPU; nothing here falls back to the CPU (--rehearse is "
              "the explicit CPU run)", file=sys.stderr)
        return 2
    if device["count"] < cell.chips:
        print(f"benchmark: {device['count']} device(s), the cell "
              f"{cell.name} needs {cell.chips}", file=sys.stderr)
        return 2
    devices = all_devices[:cell.chips]

    from brpc_tpu import native
    from brpc_tpu.butil.flags import set_flag
    from brpc_tpu.native import fastcore

    set_flag("device_stats_enabled", True)
    stamps = Stamps(trace=bool(args.trace))
    ctx = Context(cell, args.seed, devices, stamps, args.inject)
    dep = load_module("services", cell.config["service"]).build(ctx)
    driver = load_module("drivers", cell.traffic["driver"])
    problems: list = []
    trace = None
    try:
        log("imports done")
        dep.prepare()
        log("requests, weights and references made on the device")
        dep.start()
        lanes = dep.describe()["lanes"]
        log(f"servers and channels up, lanes {lanes}")
        warm_calls = dep.warm()
        # the driver's own path once, short: threads, callbacks, batches.
        # A call that fails here ends the run with no result: nothing is
        # dialled again or retried, in set-up or in the window
        warm_win = driver.run(dep, cell.traffic, WARM_SECONDS, stamps)
        dep.first_seq += warm_win.attempted
        if stamps.failures:
            raise RuntimeError(f"warm-up calls failed: {stamps.failures[:3]}")
        first_seq = dep.first_seq
        log(f"warm: {warm_calls} + {warm_win.attempted} calls, lanes {lanes}")

        at_offsets = []
        trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # spans, not every Python call
            opts.host_tracer_level = 2
            at_offsets.append((
                max(0.0, args.seconds - TRACE_SECONDS),
                lambda: jax.profiler.start_trace(trace_dir,
                                                 profiler_options=opts)))

        before = counters_mod.snapshot()
        setup_s = process_age_s()
        t_window = time.monotonic()
        win = driver.run(dep, cell.traffic, args.seconds, stamps, at_offsets)
        t_window_end = time.monotonic()
        after = counters_mod.snapshot()
        if args.trace:
            jax.profiler.stop_trace()
        log(f"window: {win.attempted} calls attempted in "
            f"{(win.end_ns - win.start_ns) / 1e9:.2f} s")

        bad = dep.finish()
        if bad:
            problems.append(f"{bad} responses differ from the reference")
        if args.inject == "device_imbalance":
            from brpc_tpu.transport import device_stats
            device_stats.open_transfer("bench-injected", "local-d2d", 4096)
        problems += counters_mod.settle_and_check(before)
        in_window = [t for t in compiles if t_window <= t <= t_window_end]
        if in_window:
            problems.append(f"{len(in_window)} programs were compiled or "
                            "fetched inside the measured window")
        if dep.describe()["lanes"] != lanes:
            problems.append("a connection changed its lane kind")
        if not (native.available() and fastcore.available()):
            problems.append("the native core did not load")

        if args.trace:
            from benchmark.lib.trace_reduce import Trace
            found = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(f"the profiler wrote no trace under "
                                   f"{trace_dir}")
            trace = Trace.from_file(max(found, key=os.path.getmtime))

        run = RunData(cell, stamps, win, counters_mod.delta(before, after),
                      setup_s, first_seq, bad, trace, devices,
                      device["kind"])
        failed = len([f for f in stamps.failures if f[0] >= first_seq]) + bad
        if failed:
            problems.append(f"{failed} of {win.attempted} calls failed: "
                            f"{stamps.failures[:3]}")

        metrics = {}
        kind, wanted = (("layer_metrics", cell.per_layer) if args.trace
                        else ("end_to_end", cell.end_to_end))
        for m in wanted:
            value = load_module(kind, m["name"]).read(run)
            if value is not None:   # a reader that finds nothing: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        from benchmark.lib.stats import median, tail
        info(workload=cell.name, seed=args.seed, seconds=args.seconds,
             trace=args.trace, **dep.describe())
        info(samples=len(run.latencies_us),
             completed_in_window=run.in_window,
             call_p50_us=(median(run.latencies_us)
                          if run.latencies_us else None),
             call_p99_us=tail(run.latencies_us, 0.99),
             window_s=run.window_s, setup_s=setup_s,
             # drift inside a run, to set beside the spread between runs
             per_second=run.per_second(),
             # done= callbacks waiting for the benchmark's completion
             # thread: the generator's own time, not in any call time
             generator_wait_us=run.generator_wait_us())
        info(compile_cache=cache, programs_built=len(compiles),
             programs_built_in_window=len(in_window),
             versions=_versions(), host_cores=os.cpu_count(),
             host_cores_usable=len(os.sched_getaffinity(0)))
        shares = [v / run.window_s for v in win.thread_cpu_s.values()]
        info(benchmark_thread_cpu_share={
            "threads": len(shares), "sum": sum(shares),
            "max": max(shares, default=0.0)},
            process_cpu_share=run.counters["cpu_s"] / run.window_s,
            lane=run.counters["lane"], syscalls=run.counters["syscalls"])
        info(connections=run.counters["conns"])
        if problems:
            info(problems=problems)

        peak = 0
        for d in devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        device["memory_peak_bytes"] = peak
        result = {"correct": not problems, "attempted": win.attempted,
                  "failed": min(failed, win.attempted), "metrics": metrics,
                  "device": device}
        if trace is not None:
            tw = trace.window()
            device["busy_s"] = trace.busy_s(run.trace_devices)
            device["window_s"] = (tw[1] - tw[0]) / 1e9 if tw else 0.0
            result["breakdown"] = {
                "device_ops": trace.top_ops(run.trace_devices),
                "idle_gaps": trace.idle_gaps(run.trace_devices)}
    finally:
        dep.close()
    print(json.dumps(result), flush=True)
    # what is left is the interpreter's and the TPU client's teardown
    # (some 20 s on four chips): a hang there must still end the process
    faulthandler.dump_traceback_later(EXIT_LIMIT_S, exit=True)
    return 0 if result["correct"] else 1


def _versions() -> dict:
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = None
    return out


if __name__ == "__main__":
    sys.exit(main())
