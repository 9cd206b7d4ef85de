"""Serving-observatory smoke: the cpu-dryrun proof that the inference
lane is MEASURED before anyone tunes it (gate_serving_obs in
tools/preflight.py --gate).

One process, a tcp:// loopback GenerateService with the toy engine:

  1. a mixed-length generate burst under rpcz must produce serving
     spans whose queue/prefill/decode/emit stamps account for >= 90%
     of each generation's stream latency (by construction the stages
     TELESCOPE, so anything below ~100% means a stamp went missing) —
     a span set that can't explain its own latency is decoration, not
     measurement;
  2. every serving span must be a CHILD of the owning RPC span
     (parent_span_id != 0 — trace inheritance through the controller);
  3. the /serving builders must agree: the in-process payload, the
     HTTP page served by the same process's admin port, and the
     supervisor merge over a single-shard pane all report the same
     per-method counters;
  4. the flight deck must cost <= 5% — the MEDIAN over order-balanced
     (off, on) pairs of per-STEP median latency, stepping a full-batch
     decode wave directly on a realistically sized engine (the cost is
     per-iteration-fixed; RPC round-trips drift more than it costs),
     cumulative retry rounds; BRPC_TPU_PERF_SMOKE=0 skips just this
     criterion.

Prints one JSON line; exit 0 iff every criterion held.
BRPC_TPU_SERVING_OBS_SMOKE=0 skips the lane (handled by preflight).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import List

BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BASE)
sys.path.insert(0, os.path.join(BASE, "tools"))

# the toy model is host math lowered through jax; a smoke tool leaves
# the chip to whoever holds it (one process at a time)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ATTRIBUTION_MIN_PCT = 90.0
OVERHEAD_PCT_MAX = 5.0
METHOD_KEY = "GenerateService.Generate"
# counter keys the three /serving builders must agree on exactly
# (rates and reservoir re-exports are time- or shape-variant by design)
_TWIN_KEYS = ("requests", "admitted", "completed", "evicted", "shed",
              "canceled", "rejected", "tokens_out")


def _gen(ch, prompt: str, max_tokens: int):
    cntl = ch.call_sync(
        "GenerateService", "Generate",
        json.dumps({"prompt": prompt,
                    "max_tokens": max_tokens}).encode())
    if cntl.failed():
        raise RuntimeError(f"generate failed: {cntl.error_text}")
    return cntl


def _step_window(batcher, open_gen, ntok: int = 48,
                 nreq: int = 8) -> float:
    """Drive one full-batch generation wave by stepping the batcher
    DIRECTLY -> MEDIAN per-step latency (us). Direct stepping on
    purpose: the flight deck's cost is per-iteration, and an RPC
    round-trip on a loaded sandbox drifts 10-50% of pure scheduling
    noise per window (measured) — far above the cost being gated. The
    per-step median over ~50 steps shrugs off the few steps a gc
    pause or allocator stall lands on."""
    from brpc_tpu.serving.batcher import GenRequest
    done: List[str] = []
    for _ in range(nreq):
        r = GenRequest(list(b"obs!"), ntok,
                       on_finish=lambda r_, s_: done.append(s_))
        r.tracker = open_gen("ServingObs", "Generate", None)
        if not batcher.submit(r):
            raise RuntimeError("overhead window request not admitted")
    steps: List[int] = []
    while len(done) < nreq:
        t0 = time.perf_counter_ns()
        batcher.step(0)
        steps.append(time.perf_counter_ns() - t0)
    steps.sort()
    return steps[len(steps) // 2] / 1e3


def run_smoke(out: dict) -> None:
    from spawn_util import http_get_local

    from brpc_tpu.butil.flags import set_flag
    from brpc_tpu.rpc import Channel, ChannelOptions, Server, \
        ServerOptions
    from brpc_tpu.rpc.span import global_collector
    from brpc_tpu.serving import add_generate_service
    from brpc_tpu.serving import serving_stats as ss
    from brpc_tpu.serving.service import serving_page_payload

    problems: List[str] = []
    set_flag("serving_stats_enabled", True)
    server = Server(ServerOptions(enable_builtin_services=True))
    add_generate_service(server, max_batch=4, max_waiting=16,
                         cache_len=128)
    ep = server.start("tcp://127.0.0.1:0")
    ch = Channel(f"tcp://127.0.0.1:{ep.port}",
                 ChannelOptions(timeout_ms=30000))
    _gen(ch, "warm", 2)                               # jit warm-up

    # ---- 1 + 2. stage-resolved serving spans under rpcz
    lengths = (4, 24, 8, 48, 12, 4, 32, 16, 8, 24, 4, 40)
    set_flag("rpcz_enabled", True)
    global_collector.clear()
    for i, n in enumerate(lengths):
        _gen(ch, f"burst-{i}", n)
    set_flag("rpcz_enabled", False)
    spans = [s.to_dict() for s in global_collector.recent(600)
             if s.side == "serving"]
    out["serving_spans"] = len(spans)
    if len(spans) < len(lengths):
        problems.append(f"only {len(spans)} serving spans for "
                        f"{len(lengths)} generations")
    ratios = [(d["queue_us"] + d["prefill_us"] + d["decode_us"]
               + d["emit_us"]) / d["latency_us"]
              for d in spans if d["latency_us"] > 0]
    att = round(100.0 * sum(ratios) / len(ratios), 1) if ratios else 0.0
    out["serving_stage_attribution_pct"] = att
    if att < ATTRIBUTION_MIN_PCT:
        problems.append(f"stage attribution {att}% < "
                        f"{ATTRIBUTION_MIN_PCT}%")
    orphans = [d for d in spans
               if d["parent_span_id"] == f"{0:016x}"]
    if orphans:
        problems.append(f"{len(orphans)} serving spans with no parent "
                        "RPC span (trace inheritance broken)")

    # ---- 3. the three /serving builders agree on the counters
    page = serving_page_payload(server)
    row = (page.get("stats", {}).get("methods") or {}).get(METHOD_KEY)
    if row is None:
        problems.append(f"no {METHOD_KEY} cell in the in-process pane")
        row = {}
    if row and (row.get("completed", 0) < len(lengths)
                or row.get("tokens_out", 0) <= 0):
        problems.append(f"cell undercounts the burst: {row}")
    status, body = http_get_local(ep.port, "/serving")
    if status != 200:
        problems.append(f"/serving HTTP {status}")
    else:
        hrow = (json.loads(body).get("stats", {}).get("methods")
                or {}).get(METHOD_KEY) or {}
        if any(hrow.get(k) != row.get(k) for k in _TWIN_KEYS):
            problems.append(
                "HTTP /serving counters != in-process pane: "
                f"{ {k: (row.get(k), hrow.get(k)) for k in _TWIN_KEYS} }")
    mrow = (ss.merge_serving_panes([page["stats"]])["methods"]
            or {}).get(METHOD_KEY) or {}
    if any(mrow.get(k) != row.get(k) for k in _TWIN_KEYS):
        problems.append("single-pane supervisor merge != in-process "
                        f"pane: { {k: (row.get(k), mrow.get(k)) for k in _TWIN_KEYS} }")
    if not page.get("stats", {}).get("steps"):
        problems.append("step ring empty after the burst")

    # ---- 4. overhead: flight deck on vs off (rpcz off — the deck's
    # own cost, not the span collector's), on a private batcher with a
    # REALISTICALLY sized decode step (dim=128, cache 512, batch 8 —
    # ~1.5ms/step; the deck's cost is per-iteration-fixed, so gating
    # it against the microscopic default toy step would quote a 3x
    # pessimistic ratio no real model sees). PAIR-WISE estimator, arm
    # order alternating, MEDIAN over pairs, cumulative retry rounds —
    # the device-observatory gate's discipline.
    if os.environ.get("BRPC_TPU_PERF_SMOKE", "1") != "0":
        from brpc_tpu.serving.batcher import ContinuousBatcher
        from brpc_tpu.serving.model import TinyDecoder, \
            TinyDecoderConfig
        model = TinyDecoder(TinyDecoderConfig(dim=128, cache_len=512,
                                              seed=7))
        ob = ContinuousBatcher(model, max_batch=8, max_waiting=16)
        overhead = None
        _step_window(ob, ss.open_generation, ntok=8)  # jit warm-up
        pair_pcts: List[float] = []
        for _ in range(3):
            for _ in range(2):
                off_first = (len(pair_pcts) % 2 == 0)
                t = {}
                for arm in ((False, True) if off_first
                            else (True, False)):
                    set_flag("serving_stats_enabled", arm)
                    t[arm] = _step_window(ob, ss.open_generation)
                pair_pcts.append(
                    (t[True] - t[False]) / t[False] * 100.0)
            s = sorted(pair_pcts)
            overhead = round(max(0.0, s[len(s) // 2]), 2)
            if overhead <= OVERHEAD_PCT_MAX:
                break
        set_flag("serving_stats_enabled", True)
        out["serving_stats_overhead_pct"] = overhead
        if overhead is None or overhead > OVERHEAD_PCT_MAX:
            problems.append(f"serving_stats overhead {overhead}% > "
                            f"{OVERHEAD_PCT_MAX}%")
    else:
        out["overhead_skipped"] = "BRPC_TPU_PERF_SMOKE=0"

    ch.close()
    server.stop()
    server.join(2)
    out["problems"] = problems
    out["ok"] = not problems


def main() -> int:
    import faulthandler
    # a wedged engine must leave stacks, not a silent gate timeout
    faulthandler.dump_traceback_later(150, exit=True)
    out: dict = {"ok": False}
    t0 = time.monotonic()
    try:
        run_smoke(out)
    except BaseException as e:  # noqa: BLE001 - one JSON line always
        out["error"] = f"{type(e).__name__}: {e}"[:500]
    out["elapsed_s"] = round(time.monotonic() - t0, 1)
    print(json.dumps(out, default=str), flush=True)
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
