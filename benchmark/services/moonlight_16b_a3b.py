"""The Moonlight-16B-A3B deployment: one chip's share of an EP8 x PP3
serving deployment behind the program's own ``GenerateService``.

One ``Server`` on chip 0 with ``GenerateService(model=...)``: the
program's ``MoonlightDecoder`` (``brpc_tpu/serving/moonlight.py``, the
configuration's widths and share) in the program's
``ContinuousBatcher`` with ``slots`` slots over a latent cache of
``cache_len`` positions on the device, stepped by ``ServingEngine`` on
the server's fiber workers. Its weights are drawn here from the seed
(``reference.draw_state``, the published checkpoint's layout) and
loaded by the program. One ``Channel`` (one connection, ``local-d2d``)
carries unary ``Generate`` calls: the prompt's ids as one int32 array
that the lane puts on chip 0, the budget in the JSON body; the reply
holds the generated ids.

The client is a closed loop (``GenerateLoop``) of ``slots`` calls in
flight from set-up on: a pool of ``pool`` requests made from the seed
(prompt ids uniform over the vocabulary slice, lognormal prompt lengths
and budgets, clipped), issued in rotation. Warm-up runs until
``warm_completions`` generations have completed; ``drivers/
generate_loop.py`` then marks windows on the running loop.

``correct`` (the configuration's ``guarantees``): every generation the
window completes carries exactly its budget of ids, each below the
vocabulary; nothing failed, was evicted, cancelled or shed while a
window was open. For ``probes`` calls the seed picks among the first
``probe_from`` issued in the window, the model is wrapped
(``ProbedModel``) so that the TIMED programs' own outputs are kept as
device arrays: the logits that chose the first token, every
``probe_every``-th and the last, the experts chosen at every position
in every expert layer, and what each expert layer's router read at
every decoded position. After the window the float32 reference
(``benchmark/reference/moonlight_16b_a3b.py``, on the same weights
drawn again) is held against them (``check_probe``): every kept row
within ``LOGIT_ATOL``; every position's experts the reference's, but
for the (k+1)-th taken for the k-th where those two lie within
``TIE_EPS``; every decoded position's experts the reference router's
on the program's own router input, within ``ROUTER_EPS``. Two controls
run beside them and are reported: the reference in float8 operands,
whose distance must exceed ``LOGIT_ATOL`` (else the run fails: the
tolerance would not catch a lower precision), and the reference
router in bfloat16 on the same inputs, whose mismatches with the
program's choices are counted.

What the per-layer readers need is left in ``LAST`` after ``finish``.
"""

from __future__ import annotations

import json
import queue
import random
import statistics
import threading
import time

import numpy as np

from benchmark.lib.stamps import now_ns
from benchmark.reference import moonlight_16b_a3b as reference

SERVICE = "GenerateService"
METHOD_LABEL = "GenerateService.Generate"
# Logit tolerance. The program multiplies bfloat16 operands (8
# significant bits: a rounding is up to 2^-9 relative) and keeps the
# latent cache in bfloat16; the reference is float32 throughout on the
# same weights, whose logits spread about 1. On the chip, at the cell's
# size, a probe's largest distance read 0.024-0.027 and the reference
# in float8 operands 1.24-1.52; with its cache alone in float8 the
# program reads 0.067-0.091 on the CPU at smaller sizes. 2^-4 lies
# between them (PERF.md, section 6, has the readings).
LOGIT_ATOL = 2.0 ** -4
# Near-tie margin of the routing end to end: the router reads a hidden
# state that carries the bfloat16 roundings of every layer below it, so
# where choice scores (sigmoid + bias, about 0.2-0.8) lie close the
# program may choose otherwise. On the chip such inversions read up to
# 0.0039; with the cache in float8, 0.016-0.024.
TIE_EPS = 2.0 ** -7
# The router on its own input: the program and the reference compute the
# same float32 scores from the same float32 input and bfloat16 weights,
# apart in the order of a 2,048-term sum (a score moves by some 1e-7);
# 2^-16 leaves a hundredfold room. A router in bfloat16 moves scores by
# some 2e-4 (its input rounded to 8 bits) and chooses otherwise at gaps
# up to 0.0013.
ROUTER_EPS = 2.0 ** -16
WARM_TIMEOUT_S = 900.0
PROBE_WAIT_S = 300.0
STEP_RING = 1 << 17
STRATA = 64

LAST: dict = {}


def build(ctx):
    return MoonlightDeployment(ctx)


def model_config(config: dict, sizes: dict):
    """The program's ``MoonlightConfig`` from the configuration file: the
    published keys, ``n_routed_experts`` as the experts HELD (the
    router's width and the first id held are under ``deployment``),
    then the size overrides (``sizes["model"]``, by the program's
    names)."""
    import dataclasses

    from brpc_tpu.serving.moonlight import MoonlightConfig

    names = {f.name for f in dataclasses.fields(MoonlightConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    dep = config["deployment"]
    kw["experts_held"] = config["n_routed_experts"]
    kw["n_routed_experts"] = dep["router_experts"]
    kw["first_expert_held"] = dep["first_expert_held"]
    kw.update(sizes.get("model", {}))
    return MoonlightConfig(**kw)


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` draws of the clipped lognormal ``spec``, stratified: each
    block of STRATA consecutive requests draws once from each of STRATA
    equally likely slices of the distribution, in an order the seed
    shuffles. Any stretch of the rotation then holds the distribution,
    where independent draws would give each seed's window (a few
    hundred generations) a median some 5% apart."""
    normal = statistics.NormalDist(np.log(spec["median"]), spec["sigma"])
    blocks = -(-n // STRATA)
    u = (np.concatenate([rng.permutation(STRATA) for _ in range(blocks)])
         + rng.random(blocks * STRATA)) / STRATA
    u = np.clip(u[:n], 1e-12, 1.0 - 1e-12)
    x = np.exp([normal.inv_cdf(v) for v in u])
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


# ------------------------------------------------------------- probes

class Probe:
    """One probed generation: what the timed programs produced for it."""

    def __init__(self, issue: int, prompt: list, max_tokens: int,
                 every: int):
        self.issue = issue
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.every = every
        self.generated = 0
        self.logits: list = []          # (generated index, device row)
        self.prefill_experts = None     # device [moe, bucket, k]
        self.decode_experts: list = []  # device [moe, k] a step
        self.router_in: list = []       # device [moe, hidden] a step
        self.slot = None
        self.reply: list = []

    def keeps(self, g: int) -> bool:
        return g == 1 or g % self.every == 0 or g == self.max_tokens


def _slot_rows(aux, slot):
    """A slot's row of a decode step's outputs: (logits, experts
    [moe, k], router inputs [moe, hidden])."""
    return (aux["logits"][slot], aux["experts"][:, slot],
            aux["router_in"][:, slot])


class ProbedModel:
    """The program's model, unchanged, with the probes' bookkeeping
    around its calls. ``arm`` names a prompt; its prefill binds the
    probe to the slot, each decode of that slot keeps the slot's rows
    (one small program, compiled in ``warm_up``)."""

    def __init__(self, inner):
        import jax

        self.inner = inner
        self.vocab = inner.vocab
        self.cache_len = inner.cache_len
        self.max_prompt = inner.max_prompt
        self._lock = threading.Lock()
        self._armed: dict = {}
        self._bound: dict = {}
        self._rows = jax.jit(_slot_rows)

    def arm(self, probe: Probe) -> None:
        with self._lock:
            self._armed[tuple(probe.prompt)] = probe

    def disarm(self) -> None:
        """Forget the probes not yet admitted (a new window picks its
        own)."""
        with self._lock:
            self._armed.clear()

    def new_cache(self, max_batch, cache_len):
        return self.inner.new_cache(max_batch, cache_len)

    def warm_up(self, cache):
        import jax

        cache = self.inner.warm_up(cache)
        batch = cache[0].shape[0]
        cache, *out = self.inner.decode(cache, np.zeros((batch,), np.int32),
                                        np.full((batch,), -1, np.int32))
        jax.block_until_ready(self._rows(out[2], np.int32(0)))
        return cache

    def prefill(self, cache, slot, tokens):
        out = self.inner.prefill(cache, slot, tokens)
        if self._armed:
            with self._lock:
                probe = self._armed.pop(tuple(tokens), None)
            if probe is not None:
                probe.slot = slot
                probe.generated = 1
                probe.logits.append((1, out[3]["logits"]))
                probe.prefill_experts = out[3]["experts"]
                if probe.max_tokens > 1:
                    self._bound[slot] = probe
        return out

    def decode(self, cache, last_tokens, lengths):
        out = self.inner.decode(cache, last_tokens, lengths)
        for slot, probe in list(self._bound.items()):
            if lengths[slot] < 0:
                continue
            probe.generated += 1
            g = probe.generated
            row, experts, router_in = self._rows(out[3], np.int32(slot))
            probe.decode_experts.append(experts)
            probe.router_in.append(router_in)
            if probe.keeps(g):
                probe.logits.append((g, row))
            if g >= probe.max_tokens:
                del self._bound[slot]
        return out


_CHECKS: dict = {}


def _jitted(name: str, cfg, **kw):
    """The reference's ``forward`` or ``router_check``, jitted once a
    process for each setting."""
    import functools

    import jax

    key = (name, cfg, tuple(sorted(kw.items(), key=str)))
    if key not in _CHECKS:
        _CHECKS[key] = jax.jit(functools.partial(
            getattr(reference, name), cfg=cfg, **kw))
    return _CHECKS[key]


def check_probe(cfg, state, p: Probe, padded: int, block: int,
                decodes: int) -> dict:
    """One probe against the reference on the weights ``state``: the
    sequence padded to ``padded`` positions (a multiple of ``block``),
    its decoded positions to ``decodes``. ``ok`` where every kept row
    lies within LOGIT_ATOL and no expert choice differs but within the
    near-tie margins; the controls beside."""
    import jax
    import jax.numpy as jnp

    out = {"issue": p.issue, "prompt": len(p.prompt),
           "max_tokens": p.max_tokens, "ok": False}
    steps = len(p.decode_experts)
    if len(p.reply) != p.max_tokens or p.prefill_experts is None \
            or steps != p.max_tokens - 1:
        out["why"] = f"not whole: reply {len(p.reply)}, decodes {steps}"
        return out
    n = len(p.prompt)
    seq = p.prompt + p.reply[:-1]
    length = len(seq)
    tokens = np.zeros((padded,), np.int32)
    tokens[:length] = seq
    moe, k = cfg.moe_layers, cfg.num_experts_per_tok
    chosen = np.zeros((moe, padded, k), np.int32)
    chosen[:, :n] = np.asarray(p.prefill_experts)[:, :n]
    router_in = jnp.zeros((moe, decodes, cfg.hidden_size), jnp.float32)
    dec = np.zeros((moe, decodes, k), np.int32)
    if steps:
        dec[:, :steps] = np.stack(jax.device_get(p.decode_experts), 1)
        chosen[:, n:length] = dec[:, :steps]
        router_in = router_in.at[:, :steps].set(jnp.stack(p.router_in, 1))
    rows = np.asarray([n - 1 + g - 1 for g, _ in p.logits])
    prog = jnp.stack([row for _, row in p.logits])
    forward = _jitted("forward", cfg, tie_eps=TIE_EPS, block=block)
    ref_logits, mism, ties, split = forward(state, tokens=tokens,
                                            valid=np.int32(length),
                                            chosen=chosen)
    err = np.asarray(jnp.max(jnp.abs(ref_logits[rows] - prog), -1))
    r_mism, r_ties, r_split = _jitted("router_check", cfg, eps=ROUTER_EPS)(
        state, router_in=router_in, chosen=dec, valid=np.int32(steps))
    low = _jitted("forward", cfg, tie_eps=TIE_EPS, block=block,
                  operand_dtype=jnp.float8_e4m3fn)(
        state, tokens=tokens, valid=np.int32(length), chosen=chosen)[0]
    bf16_router = _jitted("router_check", cfg, eps=ROUTER_EPS,
                          operand_dtype=jnp.bfloat16)(
        state, router_in=router_in, chosen=dec, valid=np.int32(steps))
    out.update(kept_rows=len(rows), max_err=float(err.max()),
               mean_err=float(err.mean()), mismatches=int(mism),
               near_ties=int(ties), widest_split=float(split),
               positions=length * moe,
               router_mismatches=int(r_mism), router_near_ties=int(r_ties),
               router_widest_split=float(r_split),
               router_positions=steps * moe,
               fp8_reference_max_err=float(jnp.max(jnp.abs(
                   low[rows] - ref_logits[rows]))),
               bf16_router_mismatches=int(bf16_router[0]))
    out["ok"] = bool(err.max() <= LOGIT_ATOL and int(mism) == 0
                     and int(r_mism) == 0)
    return out


# --------------------------------------------------------------- loop

class _Window:
    def __init__(self, stamps, first_sample: int, start_ns: int):
        self.stamps = stamps
        self.first_sample = first_sample
        self.next = first_sample
        self.start_ns = start_ns
        self.end_ns = None
        self.issued_at_open = 0
        self.wall_ms = [time.time_ns() // 1_000_000, None]


class GenerateLoop:
    """``depth`` calls in flight: each completion (handed from the
    fabric's callback over a queue to one issuing thread) issues the
    next call."""

    def __init__(self, dep, depth: int):
        self.dep = dep
        self.depth = depth
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self.issued = 0
        self.finished = 0           # completed or failed
        self.completed = 0
        self.warm_failures: list = []
        self.issuing = True
        self.window = None
        self._cpu_s = 0.0           # the loop thread's CPU since start
        self._cpu_at_open = 0.0
        self._thread = threading.Thread(target=self._run,
                                        name="bench-generate-loop",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        c0 = time.thread_time()
        for _ in range(self.depth):
            self._issue()
        while True:
            item = self._q.get()
            if item is None:
                break
            self._complete(*item)
            if self.issuing:
                self._issue()
            self._cpu_s = time.thread_time() - c0

    def _issue(self) -> None:
        i = self.issued
        self.issued += 1
        w = self.window
        if w is not None and w.end_ns is None:
            self.dep.maybe_probe(i, i - w.issued_at_open)
        ids, body = self.dep.request(i)
        t_issue = now_ns()
        q = self._q

        def on_done(cntl) -> None:
            q.put((i, t_issue, cntl, now_ns()))
        self.dep.channel.call(SERVICE, "Generate", body, done=on_done,
                              request_device_arrays=[ids])

    def _complete(self, i, t_issue, cntl, t_done) -> None:
        err = self.dep.check_reply(i, cntl)
        with self._lock:
            self.finished += 1
            if err is None:
                self.completed += 1
            w = self.window
            if w is not None and w.start_ns <= t_done \
                    and (w.end_ns is None or t_done <= w.end_ns):
                sample = w.next
                w.next += 1
                if err is None:
                    w.stamps.calls.append((sample, t_issue, t_done))
                else:
                    w.stamps.fail(sample, err)
            elif err is not None and self.issuing:
                self.warm_failures.append((i, err))

    # ------------------------------------------------- driver's side
    def open_window(self, stamps, first_sample: int) -> None:
        self.dep.window_opened()        # before any issue can see it
        with self._lock:
            w = _Window(stamps, first_sample, now_ns())
            w.issued_at_open = self.issued
            self.window = w
            self._cpu_at_open = self._cpu_s

    def close_window(self) -> int:
        with self._lock:
            w = self.window
            w.end_ns = now_ns()
            w.wall_ms[1] = time.time_ns() // 1_000_000
            # the window's generations: completed or failed in it, or
            # still running at its end
            return w.next - w.first_sample + self.issued - self.finished

    def thread_cpu_s(self) -> dict:
        return {"issue+complete": self._cpu_s - self._cpu_at_open}

    def stop(self, timeout_s: float = 30.0) -> None:
        self.issuing = False
        self._q.put(None)
        self._thread.join(timeout_s)


# --------------------------------------------------------- deployment

class MoonlightDeployment:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell.config
        self.sizes = ctx.cell.sizes
        self.traffic = dict(ctx.cell.traffic, **self.sizes.get("traffic",
                                                               {}))
        self.serving = self.sizes["serving"]
        self.device = ctx.devices[0]
        self.first_seq = 0
        self.server = None
        self.channel = None
        self.gs = None
        self.loop = None
        self.probes: dict = {}
        self._probe_picks: dict = {}
        self._counts_at_open = None
        self.problems: list = []

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        from brpc_tpu.butil.flags import set_flag
        from brpc_tpu.serving.moonlight import MoonlightDecoder

        # the window's every step record, for the readers
        set_flag("serving_step_ring_cap", STEP_RING)
        self.cfg = model_config(self.config, self.sizes)
        sv = self.serving
        # the program loads the weights and keeps its own tree; the
        # reference draws them again after the window
        self.model = MoonlightDecoder(
            self.cfg, reference.draw_state(self.cfg, self.ctx.seed,
                                           self.device),
            cache_len=sv["cache_len"], max_prompt=sv["max_prompt"],
            min_bucket=sv["min_bucket"], device=self.device)
        self.probed = ProbedModel(self.model)
        t = self.traffic
        rng = np.random.default_rng(self.ctx.seed)
        pool = int(t["pool"])
        lengths = _lognormal(rng, t["prompt_tokens"], pool)
        self.budgets = _lognormal(rng, t["max_tokens"], pool)
        self.prompts = [rng.integers(0, self.cfg.vocab_size, n,
                                     dtype=np.int32) for n in lengths]
        self.bodies = [json.dumps({"max_tokens": int(b)}).encode()
                       for b in self.budgets]
        picks = random.Random(self.ctx.seed).sample(
            range(int(t["probe_from"])), int(t["probes"]))
        self._probe_picks = dict.fromkeys(picks)

    def start(self) -> None:
        from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                                  ServerOptions)
        from brpc_tpu.serving import add_generate_service

        layout = self.config["layout"]
        slots = int(self.serving["slots"])
        self.server = Server(ServerOptions(enable_builtin_services=False))
        self.gs = add_generate_service(
            self.server, model=self.probed, max_batch=slots,
            max_waiting=slots, warmup=True)
        ep = self.server.start(layout["servers"][0])
        self.channel = Channel(layout["dial"].format(port=ep.port),
                               ChannelOptions(**layout["channel_options"]))

    def warm(self) -> int:
        """The loop starts and runs until ``warm_completions``
        generations have completed (the slots out of step)."""
        want = int(self.traffic["warm_completions"])
        self.loop = GenerateLoop(self, int(self.serving["slots"]))
        self.loop.start()
        deadline = time.monotonic() + WARM_TIMEOUT_S
        while self.loop.completed < want:
            if self.loop.warm_failures:
                raise RuntimeError(f"warm-up generations failed: "
                                   f"{self.loop.warm_failures[:3]}")
            if time.monotonic() > deadline:
                raise RuntimeError(f"{self.loop.completed} of {want} warm-up "
                                   f"generations in {WARM_TIMEOUT_S} s")
            time.sleep(0.05)
        return self.loop.issued

    # ------------------------------------------------------------ client
    def request(self, i: int):
        """(the prompt's ids, the body with the budget) of call i: the
        ids a host array that the lane puts on chip 0."""
        k = i % len(self.prompts)
        return self.prompts[k], self.bodies[k]

    def maybe_probe(self, issue: int, in_window: int) -> None:
        if in_window in self._probe_picks \
                and self._probe_picks[in_window] is None:
            k = issue % len(self.prompts)
            probe = Probe(issue, self.prompts[k].tolist(),
                          int(self.budgets[k]),
                          int(self.traffic["probe_every"]))
            self._probe_picks[in_window] = probe
            self.probes[issue] = probe
            self.probed.arm(probe)

    def check_reply(self, i: int, cntl):
        """None where the reply is a whole generation of request i, else
        why not."""
        if cntl.failed():
            return f"call failed: {cntl.error_code} {cntl.error_text}"[:300]
        try:
            doc = json.loads(cntl.response_payload.to_bytes())
        except ValueError as e:
            return f"reply is not json: {e}"
        want = int(self.budgets[i % len(self.prompts)])
        toks = doc.get("tokens")
        if doc.get("status") != "completed" or doc.get("n") != want \
                or not isinstance(toks, list) or len(toks) != want:
            return (f"reply has {doc.get('n')} of {want} tokens, status "
                    f"{doc.get('status')}")
        if min(toks) < 0 or max(toks) >= self.cfg.vocab_size:
            return "a token id outside the vocabulary"
        probe = self.probes.get(i)
        if probe is not None:
            probe.reply = toks
        return None

    def window_opened(self) -> None:
        """A window opens (the harness's short one after set-up, then the
        measured one): its probes are picked afresh, the batcher's
        verdicts and the reservoirs start from here."""
        from brpc_tpu.serving import serving_stats

        self.probed.disarm()
        self.probes = {}
        self._probe_picks = dict.fromkeys(self._probe_picks)
        b = self.gs.batcher
        self._counts_at_open = (b.evicted, b.canceled, b.shed)
        serving_stats.global_serving_stats().serving_cell(
            METHOD_LABEL).restart_samples()

    # ------------------------------------------------------------ checks
    def finish(self) -> int:
        """After the window: the batcher's verdicts, then the probes
        against the reference. Returns the count of problems."""
        from brpc_tpu.serving import serving_stats

        b = self.gs.batcher
        w = self.loop.window
        counts = (b.evicted, b.canceled, b.shed)
        bad = 0
        if self._counts_at_open is not None \
                and counts != self._counts_at_open:
            self.problems.append(
                f"evicted, cancelled, shed moved from {self._counts_at_open}"
                f" to {counts} while a window was open")
            bad += 1
        reg = serving_stats.global_serving_stats()
        cell = reg.serving_cell(METHOD_LABEL)
        steps = [r for r in reg.step_records(STEP_RING)
                 if w.wall_ms[0] <= r["t_ms"] <= w.wall_ms[1]]
        LAST.clear()
        LAST.update(cfg=self.cfg, model=self.model, steps=steps,
                    ttft_us=cell.ttft_samples(), gap_us=cell.tpot_samples())
        # the probes finish their generations; nothing new is issued
        self.loop.issuing = False
        deadline = time.monotonic() + PROBE_WAIT_S
        probes = list(self.probes.values())
        while any(not p.reply for p in probes) \
                and time.monotonic() < deadline and not b.stopped:
            time.sleep(0.05)
        self.loop.stop()
        b.stop()            # cancels what runs; frees the cache
        report = self._check_probes(probes)
        bad += sum(1 for r in report if not r["ok"])
        if len(probes) < int(self.traffic["probes"]):
            self.problems.append(f"{len(probes)} probes were issued")
            bad += 1
        controls = [r["fp8_reference_max_err"] for r in report
                    if "fp8_reference_max_err" in r]
        if controls and min(controls) <= LOGIT_ATOL:
            self.problems.append(
                f"the float8 reference lies {min(controls)} from the "
                f"float32 one, within the tolerance {LOGIT_ATOL}")
            bad += 1
        print(json.dumps({"info": {
            "probes": report, "logit_atol": LOGIT_ATOL,
            "tie_eps": TIE_EPS, "router_eps": ROUTER_EPS,
            "fp8_reference_min_err": min(controls) if controls else None,
            "bf16_router_mismatches": sum(
                r.get("bf16_router_mismatches", 0) for r in report),
            "problems": self.problems, "step_records": len(steps),
            "prefill_runs": self.model.prefill_counts}}, default=str),
            flush=True)
        return bad

    def _check_probes(self, probes: list) -> list:
        """Every probe against the reference, on the weights drawn again
        from the seed (the program's cache is freed by then)."""
        if not probes:
            return []
        state = reference.draw_state(self.cfg, self.ctx.seed, self.device)
        longest = int(self.traffic["max_tokens"]["max"])
        padded = int(self.serving["max_prompt"]) + longest
        block = min(512, padded)
        padded = -(-padded // block) * block
        return [check_probe(self.cfg, state, p, padded, block, longest)
                for p in probes]

    def describe(self) -> dict:
        kinds = [self.channel.device_lane_kind()]
        want = self.config["layout"]["lane"]
        if kinds != [want]:
            raise AssertionError(f"device lanes are {kinds}, the "
                                 f"configuration says {want!r}")
        return {"lanes": kinds, "slots": int(self.serving["slots"]),
                "cache_len": int(self.serving["cache_len"]),
                "params_held": _param_count(self.model)}

    def close(self) -> None:
        if self.loop is not None:
            self.loop.stop(5.0)
        if self.channel is not None:
            self.channel.close()
        if self.server is not None:
            self.server.stop()
            self.server.join(5)


def _param_count(model) -> int:
    from brpc_tpu.serving.moonlight import param_count
    return param_count(model.params)
