"""Find the rate that `longtail_echo.poisson_1pct_8conn` offers: the sweep
ISSUE 32 sets out, to be run once on the chip (`chiprun -- python
tools/longtail_sweep.py`). Every run is the benchmark's own command in a
process of its own; the rate is changed in a throw-away copy of the
benchmark's files (under `chiprun_out/`), never in the repository's.

Upward from `--start` in steps of `--step`, two runs of `--seconds` a
step, each with a seed of its own. A rate is sustained if, in both runs,
the calls completed inside the window are at least 99% of the arrivals
scheduled in it, the p99 of the issue lateness is under 5 ms, and the
short calls' p50 is at most 3x its value at the first rate. A step that
fails is run once more (two more runs): one stall of the host fails a
run at any rate, saturation fails it again. The knee is the highest
sustained rate under the first that is not. Then `--runs`
runs at 0.8 x knee (rounded down to a multiple of 10); if `call_p50_us`
spreads (max - min over the median) over 5% there, the same at 0.6 x
knee. One JSON line a run goes to `--out`, a summary to stdout."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "longtail_echo.poisson_1pct_8conn"
TRAFFIC = os.path.join("benchmark", "traffic", "poisson_1pct_8conn.json")


def make_copy(where: str) -> str:
    """BENCHMARK.json and benchmark/ copied, the program linked."""
    shutil.rmtree(where, ignore_errors=True)
    os.makedirs(where)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), where)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(where, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "brpc_tpu"),
               os.path.join(where, "brpc_tpu"))
    return where


def set_rate(copy: str, rate: float, key: str) -> None:
    path = os.path.join(copy, TRAFFIC)
    with open(path) as f:
        traffic = json.load(f)
    traffic[key] = rate
    with open(path, "w") as f:
        json.dump(traffic, f, indent=2)


def one_run(copy: str, rate: float, seed: int, seconds: float,
            extra=()) -> dict:
    # a rehearsal reads its rate under a key of its own
    set_rate(copy, rate, "rehearse_rate_calls_per_s" if extra
             else "rate_calls_per_s")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", "0", *extra],
        cwd=copy, capture_output=True, text=True, timeout=900)
    row = {"rate": rate, "seed": seed, "rc": proc.returncode,
           "wall_s": round(time.monotonic() - t0, 1)}
    infos: dict = {}
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    for ln in lines[:-1]:
        try:
            infos.update(json.loads(ln).get("info", {}))
        except ValueError:
            pass
    if not lines or proc.returncode not in (0, 1):
        row["stderr"] = proc.stderr[-1500:]
        return row
    res = json.loads(lines[-1])
    loop = infos.get("open_loop", {})      # the window's (the last one)
    row.update(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"],
        metrics={k: v["value"] for k, v in res["metrics"].items()},
        memory_peak_bytes=res["device"].get("memory_peak_bytes"),
        device=res["device"].get("kind"),
        scheduled=loop.get("scheduled"),
        scheduled_long=loop.get("scheduled_long"),
        completed_in_window=loop.get("completed_in_window"),
        lateness_us=loop.get("issue_lateness_us"),
        long_call_us=loop.get("long_call_us"),
        short_p50_us=infos.get("call_p50_us"),
        short_p99_us=infos.get("call_p99_us"),
        samples=infos.get("samples"), per_second=infos.get("per_second"),
        generator_wait_us=infos.get("generator_wait_us"),
        process_cpu_share=infos.get("process_cpu_share"),
        problems=infos.get("problems"))
    return row


def sustained(row: dict, p50_at_first) -> bool:
    lateness = row.get("lateness_us") or {}
    late = lateness.get("p99")
    if late is None:        # under 1,000 arrivals: the largest stands in
        late = lateness.get("max")
    return bool(
        row.get("correct") and row["scheduled"]
        and row["completed_in_window"] >= 0.99 * row["scheduled"]
        and late is not None and late < 5000.0
        and (p50_at_first is None
             or row["short_p50_us"] <= 3.0 * p50_at_first))


def spread(values) -> float:
    return (max(values) - min(values)) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--start", type=float, default=200.0)
    ap.add_argument("--step", type=float, default=50.0)
    ap.add_argument("--max", type=float, default=2000.0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--runs", type=int, default=6,
                    help="runs at the chosen rate (0: the sweep alone)")
    ap.add_argument("--seed", type=int, default=3200000000,
                    help="the first seed; every run takes the next")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU: debugs this script, measures nothing")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "longtail_sweep.jsonl"))
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    copy = make_copy(os.path.join(os.path.dirname(args.out),
                                  "longtail_sweep_copy"))
    extra = ("--rehearse",) if args.rehearse else ()
    seed = [args.seed]

    def run(rate: float, what: str) -> dict:
        row = one_run(copy, rate, seed[0], args.seconds, extra)
        seed[0] += 1
        row["what"] = what
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps({k: row.get(k) for k in (
            "what", "rate", "seed", "rc", "correct", "scheduled",
            "completed_in_window", "short_p50_us", "short_p99_us",
            "lateness_us", "wall_s")}), flush=True)
        if "stderr" in row:
            print(row["stderr"], file=sys.stderr, flush=True)
        return row

    knee, p50_first, rate, table = None, None, args.start, []
    while rate <= args.max:
        pair = [run(rate, "sweep") for _ in range(2)]
        if any("correct" not in r for r in pair):
            print(json.dumps({"aborted": "a run gave no result"}))
            return 1
        if p50_first is None:
            p50_first = statistics.mean(r["short_p50_us"] for r in pair)
        ok = all(sustained(r, p50_first) for r in pair)
        again = False
        if not ok:
            # one stall of the host (they occur: PERF.md section 6, PR 22)
            # fails a run at any rate: a step that fails is run once more,
            # and only a step that fails twice ends the sweep
            again = True
            ok = all(sustained(run(rate, "sweep_again"), p50_first)
                     for _ in range(2))
        table.append({"rate": rate, "sustained": ok, "run_twice": again})
        if not ok:
            break
        knee = rate
        rate += args.step
    summary = {"knee": knee, "p50_at_first_rate_us": p50_first,
               "steps": table}
    print(json.dumps(summary), flush=True)
    if knee is None or not args.runs:
        return 0 if knee is not None else 1
    for factor in (0.8, 0.6):
        chosen = int(factor * knee // 10) * 10
        rows = [run(chosen, f"at_{factor}") for _ in range(args.runs)]
        good = [r for r in rows if r.get("correct")]
        out = {"factor": factor, "rate": chosen, "correct": len(good),
               "of": len(rows)}
        if len(good) == len(rows):
            out["call_p50_us"] = [r["short_p50_us"] for r in rows]
            out["p50_spread"] = spread(out["call_p50_us"])
            p99 = [r["short_p99_us"] for r in rows]
            if all(v is not None for v in p99):
                out["call_p99_us"] = p99
                out["p99_spread"] = spread(p99)
        print(json.dumps(out), flush=True)
        if out.get("p50_spread", 1.0) <= 0.05:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
