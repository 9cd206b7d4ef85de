"""Serving-lane tests: the continuous batcher's scheduling invariants
(iteration-level admission, deadline eviction, shed, retirement-order
independence), the WorkerModule co-scheduled engine, and the streaming
front-end over tpu_std streams, HTTP chunked transfer, and unary calls
— including a seeded client-flap chaos run (ISSUE 8)."""

import http.client
import json
import random
import threading
import time

import numpy as np
import pytest

from brpc_tpu.rpc import Channel, Server, ServerOptions
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.stream import StreamOptions
from brpc_tpu.serving import (CANCELED, COMPLETED, EVICTED,
                              ContinuousBatcher, GenRequest,
                              RequestTooLong, TinyDecoder,
                              TinyDecoderConfig, add_generate_service)

_seq = iter(range(100000))


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(TinyDecoderConfig(cache_len=96))


def _drain(batcher, limit=500):
    """Run the batcher dry inline (no workers needed at this layer)."""
    steps = 0
    while batcher.has_work() and steps < limit:
        batcher.step(0)
        steps += 1
    return steps


def _deadline_cntl(ms: float) -> Controller:
    cntl = Controller()
    cntl.__dict__["_deadline_ns"] = time.monotonic_ns() + int(ms * 1e6)
    return cntl


# ---------------------------------------------------------------- model

def test_decode_attention_matches_reference(model):
    """The ops-layer decode primitive: one query over a partially-valid
    KV cache must equal full attention over exactly the valid rows."""
    import jax.numpy as jnp

    from brpc_tpu.ops.flash_attention import (attention_reference,
                                              decode_attention)
    rng = np.random.RandomState(7)
    B, L, d = 3, 40, 16
    k = rng.randn(B, L, d).astype(np.float32)
    v = rng.randn(B, L, d).astype(np.float32)
    q = rng.randn(B, d).astype(np.float32)
    lens = np.array([5, 40, 17])
    out = decode_attention(jnp.asarray(q), jnp.asarray(k),
                           jnp.asarray(v), jnp.asarray(lens))
    for i, n in enumerate(lens):
        ref = attention_reference(jnp.asarray(q[i][None, :]),
                                  jnp.asarray(k[i, :n]),
                                  jnp.asarray(v[i, :n]))
        np.testing.assert_allclose(np.asarray(out)[i], np.asarray(ref)[0],
                                   rtol=1e-4, atol=1e-4)


def test_model_deterministic(model):
    a = model.generate(list(b"determinism"), 12)
    b = model.generate(list(b"determinism"), 12)
    assert a == b and len(a) == 12
    # a different seed is a different model
    other = TinyDecoder(TinyDecoderConfig(cache_len=96, seed=99))
    assert other.generate(list(b"determinism"), 12) != a


# -------------------------------------------------------------- batcher

class TestBatcherScheduling:
    def test_mid_flight_admission(self, model):
        """Iteration-level scheduling: a request submitted while an
        earlier sequence is decoding joins the RUNNING batch at the
        next step — observed as the batch composition changing
        mid-generation, never as wait-for-drain."""
        b = ContinuousBatcher(model, max_batch=4, max_waiting=8)
        order = []
        fin = {}

        def track(tag):
            def on_token(req, tok):
                order.append(tag)
            return on_token

        rA = GenRequest(list(b"aaaa"), 30, on_token=track("A"),
                        on_finish=lambda r, s: fin.setdefault("A", s))
        assert b.submit(rA)
        for _ in range(5):
            b.step(0)
        assert order.count("A") == 5 and b.running_count() == 1
        rB = GenRequest(list(b"bbbb"), 10, on_token=track("B"),
                        on_finish=lambda r, s: fin.setdefault("B", s))
        assert b.submit(rB)
        b.step(0)
        # B decoded its first token in the very next step, with A still
        # mid-flight
        assert order.count("B") == 1 and order.count("A") == 6
        assert b.running_count() == 2
        _drain(b)
        assert fin == {"A": COMPLETED, "B": COMPLETED}
        # the step-size histogram shows both compositions
        assert b.batch_hist[1] > 0 and b.batch_hist[2] > 0

    def test_deadline_eviction_frees_kv_and_sets_timeout(self, model):
        b = ContinuousBatcher(model, max_batch=2, max_waiting=8)
        fin = {}
        victim = GenRequest(list(b"victim"), 80, cntl=_deadline_cntl(60),
                            on_finish=lambda r, s: fin.setdefault("v", s))
        keeper = GenRequest(list(b"keeper"), 80,
                            on_finish=lambda r, s: fin.setdefault("k", s))
        assert b.submit(victim) and b.submit(keeper)
        deadline = time.monotonic() + 5
        while "v" not in fin and time.monotonic() < deadline:
            b.step(0)
        assert fin["v"] == EVICTED
        assert victim.error_code == berr.ERPCTIMEDOUT
        assert 0 < victim.ntokens < 80       # evicted MID-generation
        assert victim.slot is None           # KV slot freed...
        late = GenRequest(list(b"late"), 5,
                          on_finish=lambda r, s: fin.setdefault("l", s))
        assert b.submit(late)                # ...and reusable
        _drain(b)
        assert fin["k"] == COMPLETED and fin["l"] == COMPLETED
        assert b.evicted == 1 and b.kv_occupancy() == 0.0

    def test_expired_before_admission_evicts_from_queue(self, model):
        b = ContinuousBatcher(model, max_batch=1, max_waiting=8)
        fin = {}
        hog = GenRequest(list(b"hog"), 20,
                         on_finish=lambda r, s: fin.setdefault("h", s))
        dead = GenRequest(list(b"dead"), 20, cntl=_deadline_cntl(-1),
                          on_finish=lambda r, s: fin.setdefault("d", s))
        assert b.submit(hog) and b.submit(dead)
        b.step(0)                       # admits hog; dead waits
        _drain(b)
        assert fin["d"] == EVICTED and dead.error_code == berr.ERPCTIMEDOUT
        assert fin["h"] == COMPLETED

    def test_shed_when_wait_queue_full(self, model):
        b = ContinuousBatcher(model, max_batch=1, max_waiting=2)
        reqs = [GenRequest(list(b"x"), 5) for _ in range(4)]
        # slot is only claimed at a step boundary: everything queues,
        # and the queue bound is what sheds
        assert b.submit(reqs[0]) and b.submit(reqs[1])
        assert not b.submit(reqs[2])
        assert reqs[2].state == "shed"
        assert reqs[2].error_code == berr.ELIMIT
        assert b.shed == 1
        _drain(b)
        # capacity freed: submits accepted again
        assert b.submit(reqs[3])
        _drain(b)
        assert reqs[3].state == COMPLETED

    def test_retirement_order_independence(self, model):
        """A sequence's tokens must not depend on what shares the
        batch: three prompts decoded in a mixed, staggered batch must
        equal their single-sequence oracles."""
        prompts = [b"first prompt", b"the second", b"prompt iii"]
        budgets = [18, 7, 12]
        oracle = [model.generate(list(p), n)
                  for p, n in zip(prompts, budgets)]
        b = ContinuousBatcher(model, max_batch=2, max_waiting=8)
        fin = {}
        reqs = [GenRequest(list(p), n,
                           on_finish=lambda r, s, i=i: fin.setdefault(i, s))
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        # staggered admission: 0 alone, then 1 joins, 2 replaces the
        # first retiree (max_batch=2 forces rolling composition)
        assert b.submit(reqs[0])
        b.step(0); b.step(0); b.step(0)
        assert b.submit(reqs[1]) and b.submit(reqs[2])
        _drain(b)
        assert fin == {0: COMPLETED, 1: COMPLETED, 2: COMPLETED}
        for req, want in zip(reqs, oracle):
            assert req.tokens == want

    def test_prompt_too_long_rejected(self, model):
        b = ContinuousBatcher(model, max_batch=1)
        with pytest.raises(RequestTooLong):
            b.submit(GenRequest(list(range(96)), 5))

    def test_cancel_frees_slot(self, model):
        b = ContinuousBatcher(model, max_batch=1, max_waiting=4)
        fin = {}
        r = GenRequest(list(b"gone"), 50,
                       on_finish=lambda r_, s: fin.setdefault("g", s))
        assert b.submit(r)
        b.step(0); b.step(0)
        b.cancel(r)
        b.step(0)
        assert fin["g"] == CANCELED and b.running_count() == 0
        assert b.canceled == 1


# ------------------------------------------------------------ lookahead

class _Recorder:
    """The model behind a log: each decode call's live rows (and what
    ``watch()`` reads then) go into ``log`` beside whatever the test's
    callbacks write there, and the decode reports the rows as its
    counter, so that a step record's ``routed`` names the decode it
    collected."""

    def __init__(self, inner, log):
        self.inner = inner
        self.log = log
        self.watch = lambda: None
        self.vocab = inner.vocab
        self.cache_len = inner.cache_len
        self.max_prompt = inner.max_prompt
        self.rows = []

    def new_cache(self, max_batch, cache_len):
        return self.inner.new_cache(max_batch, cache_len)

    def warm_up(self, cache):
        return self.inner.warm_up(cache)

    def prefill(self, cache, slot, tokens):
        return self.inner.prefill(cache, slot, tokens)

    def decode(self, cache, last_tokens, lengths):
        import jax.numpy as jnp

        live = int((np.asarray(lengths) >= 0).sum())
        self.rows.append(live)
        self.log.append(("decode", live, self.watch()))
        cache, nxt, _counts, aux = self.inner.decode(cache, last_tokens,
                                                     lengths)
        return cache, nxt, jnp.asarray([live], jnp.int32), aux


class _Deadline:
    """A controller whose deadline the test expires by hand."""

    expired = False

    def deadline_expired(self) -> bool:
        return self.expired

    def remaining_ms(self):
        return None


def _records_of(run):
    """The step records that ``run()`` wrote, and its result."""
    from brpc_tpu.serving import serving_stats as ss

    reg = ss.global_serving_stats()
    before = reg.steps_recorded()
    out = run()
    n = reg.steps_recorded() - before
    return (reg.step_records(n) if n else []), out


class TestLookahead:
    def test_next_decode_is_queued_before_this_steps_tokens(self, model):
        """While the batch goes on, each step() dispatches the decode of
        the next step before it hands out any of its own tokens (none
        appended, no on_token fired), and says so in its record
        (``overlapped``) and in ``serving_decode_overlapped``; the step
        that drains the batch dispatches nothing."""
        from brpc_tpu.serving import batcher as bm

        log = []
        rec = _Recorder(model, log)
        b = ContinuousBatcher(rec, max_batch=2, max_waiting=4)
        rec.watch = lambda: b.tokens_out
        prompts, budgets = [b"order one", b"order two"], [12, 8]
        reqs = [GenRequest(list(p), n,
                           on_token=lambda r, t: log.append(("token",
                                                             r.req_id)))
                for p, n in zip(prompts, budgets)]
        for r in reqs:
            assert b.submit(r)
        overlapped_before = bm.ndecode_overlapped.get_value()

        def run():
            calls = 0
            while b.has_work():
                log.append(("step", calls, b.tokens_out))
                b.step(0)
                calls += 1
            return calls

        records, calls = _records_of(run)
        assert calls == 12 and len(records) == calls
        steps = [i for i, e in enumerate(log) if e[0] == "step"]
        for k, (lo, hi) in enumerate(zip(steps, steps[1:] + [len(log)])):
            kinds = [e[0] for e in log[lo + 1:hi]]
            assert "token" in kinds
            if k < calls - 1:
                # the decode of step k+1 precedes step k's tokens
                assert kinds.count("decode") == 1
                assert kinds.index("decode") < kinds.index("token"), kinds
                decode = log[lo + 1 + kinds.index("decode")]
                assert decode[2] == log[lo][2]
            else:
                assert "decode" not in kinds
        assert [r["overlapped"] for r in records] == [1] * 11 + [0]
        assert bm.ndecode_overlapped.get_value() - overlapped_before == 11
        for r, p, n in zip(reqs, prompts, budgets):
            assert r.state == COMPLETED
            assert r.tokens == model.generate(list(p), n)

    def test_streams_through_admission_cancel_deadline_and_stop(
            self, model):
        """Every stream equals the single-sequence oracle, cut where the
        request left: staggered admission, a deadline and a cancel
        between steps (each with its next row already in flight), a stop
        token, a request admitted into the evicted slot in the step that
        sweeps it, and the cancelled slot left empty with its row in
        flight. No on_token after on_finish."""
        prompts = [b"alpha run", b"bravo!", b"charlie c", b"delta",
                   b"echo echo"]
        budgets = [30, 20, 20, 40, 10]
        oracle = [model.generate(list(p), n)
                  for p, n in zip(prompts, budgets)]
        stop_at = next(j for j in range(3, len(oracle[3]))
                       if oracle[3][j] not in oracle[3][:j])
        events = {i: [] for i in range(5)}
        deadline = _Deadline()
        b = ContinuousBatcher(model, max_batch=3, max_waiting=8)

        def on_token(i):
            def cb(req, tok):
                events[i].append("token")
                if i == 1 and req.ntokens == 4:
                    b.cancel(req)
                if i == 2 and req.ntokens == 3:
                    deadline.expired = True
            return cb

        reqs = [GenRequest(list(p), n, on_token=on_token(i),
                           on_finish=lambda r, s, i=i:
                           events[i].append(s),
                           cntl=deadline if i == 2 else None,
                           stop_token=oracle[3][stop_at] if i == 3
                           else None)
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        assert b.submit(reqs[0])
        b.step(0); b.step(0)
        assert b.submit(reqs[1]) and b.submit(reqs[2])
        b.step(0)
        assert b.submit(reqs[3])
        while reqs[1].state != CANCELED:
            b.step(0)
        assert b.running_count() == 2 and b.waiting_count() == 0
        b.step(0)
        assert b.submit(reqs[4])
        _drain(b)
        assert reqs[1].state == CANCELED and reqs[2].state == EVICTED
        assert [r.state for r in (reqs[0], reqs[3], reqs[4])] \
            == [COMPLETED] * 3
        want = [oracle[0], oracle[1][:4], oracle[2][:3],
                oracle[3][:stop_at + 1], oracle[4]]
        for r, w in zip(reqs, want):
            assert r.tokens == w
        for i, ev in events.items():
            assert ev[-1] in (COMPLETED, CANCELED, EVICTED)
            assert ev[:-1] == ["token"] * len(want[i]), (i, ev)
        assert b.kv_occupancy() == 0.0 and b.running_count() == 0

    def test_step_records_pair_each_decode_with_its_rows(self, model):
        """The records of a staggered run: their ``tokens`` sum to the
        tokens emitted, and each record's ``tokens - admitted`` is the
        live rows of the very decode whose counter it carries in
        ``routed`` (how benchmark/lib/generation.py pairs them), in
        dispatch order."""
        log = []
        rec = _Recorder(model, log)
        b = ContinuousBatcher(rec, max_batch=3, max_waiting=8)
        prompts = [b"p-%d" % i for i in range(6)]
        budgets = [9, 4, 13, 6, 11, 5]
        reqs = [GenRequest(list(p), n) for p, n in zip(prompts, budgets)]

        def run():
            for i, r in enumerate(reqs):
                assert b.submit(r)
                b.step(0)
                if i % 2:
                    b.step(0)
            _drain(b)

        records, _ = _records_of(run)
        assert sum(r["tokens"] for r in records) \
            == sum(len(r.tokens) for r in reqs) == sum(budgets)
        made = [r["tokens"] - r["admitted"] for r in records
                if r["routed"] is not None]
        assert made == rec.rows
        assert all(r["routed"] == (r["tokens"] - r["admitted"],)
                   for r in records if r["routed"] is not None)
        assert all(r["tokens"] == r["admitted"] for r in records
                   if r["routed"] is None)
        for r, p, n in zip(reqs, prompts, budgets):
            assert r.tokens == model.generate(list(p), n)

    def test_stop_with_a_decode_in_flight(self, model):
        """stop() between steps, with the next decode in flight: every
        request retires CANCELED with no token from that decode, the
        cache and the decode in flight are let go, and no step runs."""
        b = ContinuousBatcher(model, max_batch=2, max_waiting=4)
        seen = {}
        reqs = [GenRequest(list(p), 20,
                           on_token=lambda r, t: seen.setdefault(
                               r.req_id, []).append("token"),
                           on_finish=lambda r, s: seen.setdefault(
                               r.req_id, []).append(s))
                for p in (b"stop me", b"and me")]
        for r in reqs:
            assert b.submit(r)
        b.step(0); b.step(0)
        assert b._flight is not None
        retired = b.stop()
        assert set(map(id, retired)) == set(map(id, reqs))
        for r in reqs:
            assert r.state == CANCELED and r.ntokens == 2
            assert seen[r.req_id] == ["token", "token", CANCELED]
        assert b._cache is None and b._flight is None
        assert b.cache_bytes() == b._lens.nbytes + b._last.nbytes
        assert not b.step(0) and not b.has_work()
        assert b.kv_occupancy() == 0.0


# ------------------------------------------------------------ end-to-end

def _start_serving_server(addr="tcp://127.0.0.1:0", builtin=True, **kw):
    server = Server(ServerOptions(enable_builtin_services=builtin))
    kw.setdefault("cache_len", 160)
    kw.setdefault("warmup", True)
    gs = add_generate_service(server, **kw)
    ep = server.start(addr)
    return server, gs, ep


class _StreamClient:
    """One streaming Generate call: collects tagged frames."""

    def __init__(self, ch, prompt: bytes, max_tokens: int,
                 timeout_ms: float = 30000):
        self.tokens = []
        self.token_ns = []
        self.done = None            # ("d", doc) | ("e", errno)
        self.t0 = time.monotonic_ns()
        cntl = Controller()
        cntl.timeout_ms = timeout_ms
        self.cntl = ch.call_sync(
            "GenerateService", "Generate",
            json.dumps({"prompt": prompt.decode("latin-1"),
                        "max_tokens": max_tokens}).encode(),
            cntl=cntl,
            stream_options=StreamOptions(on_received=self._on_frame))
        self.stream = getattr(self.cntl, "stream", None)

    def _on_frame(self, s, msg):
        p = msg.payload.to_bytes()
        tag, rest = p[:1], p[1:]
        if tag == b"t":
            self.tokens.append(rest[0])
            self.token_ns.append(time.monotonic_ns())
        elif tag == b"d":
            self.done = ("d", json.loads(rest.decode()))
        elif tag == b"e":
            self.done = ("e", int(rest.decode()))

    def wait_done(self, timeout_s=15.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while self.done is None and time.monotonic() < deadline:
            time.sleep(0.005)
        return self.done is not None


class TestServingE2E:
    def test_stream_tokens_and_ttft(self):
        server, gs, ep = _start_serving_server(builtin=False)
        try:
            oracle = gs.batcher.model.generate(list(b"hello world"), 60)
            ch = Channel(str(ep))
            # warm the channel: the first call on a fresh channel pays
            # one-time connect/dispatch setup that would drown TTFT
            warm = _StreamClient(ch, b"w", 2)
            assert warm.wait_done()
            c = _StreamClient(ch, b"hello world", 60)
            assert not c.cntl.failed(), c.cntl.error_text
            assert c.wait_done()
            assert c.done == ("d", {"n": 60, "status": "completed"})
            assert c.tokens == oracle
            # streaming is real: the first token landed well before the
            # last (TTFT != full-generation latency)
            ttft = c.token_ns[0] - c.t0
            total = c.token_ns[-1] - c.t0
            assert ttft < total * 0.5, (ttft, total)
            # decode slices ran on fiber workers via the WorkerModule
            # hook — no dedicated engine thread exists to attribute to
            assert gs.engine.steps > 0
            assert sum(gs.batcher.steps_by_group.values()) > 0
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_stream_deadline_eviction(self):
        server, gs, ep = _start_serving_server(
            builtin=False, cache_len=4096, warmup=True)
        try:
            ch = Channel(str(ep))
            c = _StreamClient(ch, b"slow one", 4000, timeout_ms=400)
            assert not c.cntl.failed(), c.cntl.error_text
            assert c.wait_done()
            assert c.done == ("e", berr.ERPCTIMEDOUT)
            assert 0 < len(c.tokens) < 4000    # evicted MID-generation
            # KV slot freed and engine healthy: a fresh request works
            deadline = time.monotonic() + 5
            while gs.batcher.running_count() and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert gs.batcher.running_count() == 0
            c2 = _StreamClient(ch, b"after", 5)
            assert c2.wait_done() and c2.done[0] == "d"
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_unary_roundtrip_and_eviction(self):
        server, gs, ep = _start_serving_server(builtin=False,
                                               cache_len=4096)
        try:
            ch = Channel(str(ep))
            oracle = gs.batcher.model.generate(list(b"unary"), 10)
            cntl = Controller(); cntl.timeout_ms = 20000
            cntl = ch.call_sync(
                "GenerateService", "Generate",
                json.dumps({"prompt": "unary", "max_tokens": 10}).encode(),
                cntl=cntl)
            assert not cntl.failed(), cntl.error_text
            doc = json.loads(cntl.response_payload.to_bytes())
            assert doc["tokens"] == oracle and doc["n"] == 10
            # a unary call whose budget dies mid-generation FAILS with
            # ERPCTIMEDOUT (either the server's eviction or the
            # client's own deadline — same verdict)
            c2 = Controller(); c2.timeout_ms = 300
            c2 = ch.call_sync(
                "GenerateService", "Generate",
                json.dumps({"prompt": "long", "max_tokens": 4000}).encode(),
                cntl=c2)
            assert c2.failed()
            assert c2.error_code == berr.ERPCTIMEDOUT, c2.error_text
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_http_chunked_streaming(self):
        server, gs, ep = _start_serving_server()
        try:
            oracle = gs.batcher.model.generate(list(b"http body"), 16)
            conn = http.client.HTTPConnection(ep.host, ep.port,
                                              timeout=15)
            conn.request("POST", "/GenerateService/Generate",
                         body=json.dumps({"prompt": "http body",
                                          "max_tokens": 16}))
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Transfer-Encoding") == "chunked"
            body = resp.read()
            payload, _, footer = body.rpartition(b"\n#")
            assert footer == b"completed n=16"
            assert list(payload) == oracle
            # /serving page renders from the shared builder
            conn.request("GET", "/serving")
            page = json.loads(conn.getresponse().read())
            assert page["enabled"] and page["completed"] >= 1
            assert page["tokens_out"] >= 16
            conn.close()
        finally:
            server.stop(); server.join(2)

    def test_shed_when_engine_full(self):
        server, gs, ep = _start_serving_server(
            builtin=False, max_batch=1, max_waiting=1, cache_len=4096)
        try:
            ch = Channel(str(ep))
            # occupy the slot and the whole wait queue with long gens
            hogs = [_StreamClient(ch, b"hog%d" % i, 3000)
                    for i in range(2)]
            for h in hogs:
                assert not h.cntl.failed(), h.cntl.error_text
            c = Controller(); c.timeout_ms = 5000
            c = ch.call_sync(
                "GenerateService", "Generate",
                json.dumps({"prompt": "extra", "max_tokens": 4}).encode(),
                cntl=c)
            assert c.failed() and c.error_code == berr.ELIMIT, \
                (c.error_code, c.error_text)
            assert gs.batcher.shed >= 1
            for h in hogs:          # client walks away; slots free
                if h.stream is not None:
                    h.stream.close()
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_slow_consumer_still_gets_terminal_frame(self):
        """A client that drains slower than the engine decodes runs the
        server's credit window dry mid-tail: the buffered remainder —
        including the terminal d-frame — must still arrive (the finish
        path hands the tail to a fiber that parks on credits), never be
        silently dropped at stream close."""
        server, gs, ep = _start_serving_server(builtin=False,
                                               cache_len=256)
        try:
            ch = Channel(str(ep))
            tokens, done = [], []

            def slow_recv(s, msg):
                p = msg.payload.to_bytes()
                if p[:1] == b"t":
                    time.sleep(0.005)   # ~5ms/frame vs ~1ms decode
                    tokens.append(p[1])
                elif p[:1] in (b"d", b"e"):
                    done.append(p)

            cntl = Controller()
            cntl.timeout_ms = 60000
            cntl = ch.call_sync(
                "GenerateService", "Generate",
                json.dumps({"prompt": "slow reader",
                            "max_tokens": 120}).encode(),
                cntl=cntl,
                stream_options=StreamOptions(on_received=slow_recv))
            assert not cntl.failed(), cntl.error_text
            deadline = time.monotonic() + 30
            while not done and time.monotonic() < deadline:
                time.sleep(0.01)
            assert done and done[0][:1] == b"d", done
            assert len(tokens) == 120
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_expired_in_queue_evicted_while_batch_full(self):
        """A deadline-dead request must get its e1008 verdict from the
        QUEUE sweep — not wait out the full batch ahead of it pinning
        max_waiting capacity."""
        server, gs, ep = _start_serving_server(
            builtin=False, max_batch=1, max_waiting=4, cache_len=4096)
        try:
            ch = Channel(str(ep))
            hog = _StreamClient(ch, b"hog", 3000)        # owns the slot
            assert not hog.cntl.failed(), hog.cntl.error_text
            victim = _StreamClient(ch, b"queued", 50, timeout_ms=300)
            assert not victim.cntl.failed(), victim.cntl.error_text
            t0 = time.monotonic()
            assert victim.wait_done(10)
            verdict_s = time.monotonic() - t0
            assert victim.done == ("e", berr.ERPCTIMEDOUT), victim.done
            assert victim.tokens == []     # never admitted
            # verdict arrived near ITS deadline, not the hog's ~3s+
            assert verdict_s < 2.0, verdict_s
            if hog.stream is not None:
                hog.stream.close()
            ch.close()
        finally:
            server.stop(); server.join(2)

    def test_builtin_serving_stats_rpc(self):
        server, gs, ep = _start_serving_server()
        try:
            ch = Channel(str(ep))
            cntl = ch.call_sync("builtin", "serving", b"")
            assert not cntl.failed(), cntl.error_text
            doc = json.loads(cntl.response_payload.to_bytes())
            assert doc["enabled"] and doc["max_batch"] == 8
            ch.close()
        finally:
            server.stop(); server.join(2)


# ---------------------------------------------------------------- chaos

def test_chaos_client_flap_mid_stream():
    """Seeded client flap/drop mid-stream: survivors finish with their
    exact oracle streams (zero errors), the flapped requests' KV slots
    are reclaimed, and the engine never wedges (a fresh request
    completes afterwards)."""
    server, gs, ep = _start_serving_server(
        builtin=False, max_batch=4, cache_len=1024)
    try:
        from brpc_tpu.rpc import ChannelOptions
        rng = random.Random(1234)
        n_clients = 6
        flappers = set(rng.sample(range(n_clients), 2))
        # private connections: a flapped client must take down ITS
        # transport only (the default "single" type shares one socket
        # per endpoint process-wide)
        chans = [Channel(str(ep),
                         ChannelOptions(share_connections=False))
                 for _ in range(n_clients)]
        clients = [_StreamClient(chans[i], b"client-%d" % i, 150)
                   for i in range(n_clients)]
        for c in clients:
            assert not c.cntl.failed(), c.cntl.error_text
        # drop the flappers' CONNECTIONS (not a polite close) once
        # their streams are visibly mid-generation
        dropped = set()
        deadline = time.monotonic() + 20
        while len(dropped) < len(flappers) and \
                time.monotonic() < deadline:
            for i in flappers - dropped:
                if len(clients[i].tokens) >= 3:
                    # abrupt transport death, not a polite stream close
                    clients[i].stream.socket.set_failed(
                        ConnectionError("chaos flap"))
                    chans[i].close()
                    dropped.add(i)
            time.sleep(0.005)
        assert dropped == flappers
        for i in range(n_clients):
            if i in flappers:
                continue
            c = clients[i]
            assert c.wait_done(30), f"survivor {i} never finished"
            assert c.done == ("d", {"n": 150, "status": "completed"})
            assert c.tokens == gs.batcher.model.generate(
                list(b"client-%d" % i), 150), f"survivor {i} corrupted"
        # flapped sequences retire as canceled and free their slots
        deadline = time.monotonic() + 10
        while (gs.batcher.canceled < len(flappers)
               or gs.batcher.running_count()) and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert gs.batcher.canceled >= len(flappers)
        assert gs.batcher.running_count() == 0
        assert gs.batcher.kv_occupancy() == 0.0
        # engine not wedged; evicted/canceled slots reused
        ch = Channel(str(ep))
        c = _StreamClient(ch, b"post-storm", 8)
        assert c.wait_done() and c.done[0] == "d"
        ch.close()
        for i in range(n_clients):
            if i not in flappers:
                chans[i].close()
    finally:
        server.stop(); server.join(2)


# ------------------------------------------------- recorder attribution

def test_flight_recorder_attributes_decode_to_serving_method():
    """Acceptance pin: busy samples taken during decode slices attribute
    to the serving method THROUGH the worker-module label — proof the
    engine runs on the fiber workers, not a private thread pool."""
    from brpc_tpu.builtin.flight_recorder import global_recorder
    server, gs, ep = _start_serving_server(builtin=True, cache_len=4096)
    try:
        rec = global_recorder()
        rec.ensure_running()
        ch = Channel(str(ep))
        c = _StreamClient(ch, b"attribute me", 4000, timeout_ms=30000)
        assert not c.cntl.failed(), c.cntl.error_text
        # sample while decoding (20 Hz: give it ~1.2s of busy engine)
        deadline = time.monotonic() + 12
        found = False
        while time.monotonic() < deadline and not found:
            time.sleep(0.2)
            labels = rec.merged().get("labels", {})
            found = any(k == "rpc:GenerateService.Generate"
                        for k in labels)
        if c.stream is not None:
            c.stream.close()
        assert found, rec.merged().get("labels")
        ch.close()
    finally:
        server.stop(); server.join(2)
