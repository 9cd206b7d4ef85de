"""Moonlight's block (``serving/moonlight.py``) at a small size on the
CPU against the plain float32 reference (``serving/reference.py``):
prefill then decode through the ``ContinuousBatcher`` agrees in logits
with the reference's full forward pass, on weights drawn in the
published layout by the benchmark's reference; the held-expert parts of four
shares add up to the uncut layer; the comparison fails when part of the
mathematics is left out or the cache is stored in float8; the decode
program's cache is donated."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.moonlight_16b_a3b import draw_state
from brpc_tpu.ops.latent_attention import latent_attention
from brpc_tpu.serving import ContinuousBatcher, GenRequest
from brpc_tpu.serving import moonlight as ml
from brpc_tpu.serving import reference as ref

SMALL = ml.MoonlightConfig(
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=3, n_shared_experts=1, num_hidden_layers=3,
    vocab_size=512, experts_held=16)
# Logits spread about 1 here. The program's bfloat16 operands and cache
# (8 significant bits) put it 0.01-0.03 from the float32 reference on
# these seeds; the cache in float8 (4 significant bits) puts it 0.2-0.3
# away, and leaving out a part of the mathematics 2 or more. 0.08 lies
# between with room on both sides.
ATOL = 0.08
# Near-tie margin of the router's k-th and (k+1)-th choice scores: one
# bfloat16 ulp of a score in [0.5, 1). The router reads a hidden state
# that carries the bfloat16 roundings of the layers below it; here the
# program chose otherwise than the reference only at gaps under 2^-10.
TIE_EPS = 2.0 ** -8
CACHE_LEN = 64


class Recorder:
    """The model behind the batcher, keeping every slot's logits and
    chosen experts of the programs the batcher ran."""

    def __init__(self, inner):
        self.inner = inner
        self.vocab = inner.vocab
        self.cache_len = inner.cache_len
        self.max_prompt = inner.max_prompt
        self.runs = {}                      # slot -> [(logits, experts)]
        self.by_prompt = {}                 # prompt -> the same list

    def new_cache(self, max_batch, cache_len):
        return self.inner.new_cache(max_batch, cache_len)

    def warm_up(self, cache):
        return self.inner.warm_up(cache)

    def prefill(self, cache, slot, tokens):
        out = self.inner.prefill(cache, slot, tokens)
        experts = np.asarray(out[3]["experts"])[:, :len(tokens)]
        self.runs[slot] = [(np.asarray(out[3]["logits"]), experts)]
        self.by_prompt[tuple(tokens)] = self.runs[slot]
        return out

    def decode(self, cache, last_tokens, lengths):
        out = self.inner.decode(cache, last_tokens, lengths)
        logits = np.asarray(out[3]["logits"])
        experts = np.asarray(out[3]["experts"])
        for slot in np.flatnonzero(np.asarray(lengths) >= 0):
            self.runs[int(slot)].append(
                (logits[slot], experts[:, slot:slot + 1]))
        return out


def _model(state, cfg=SMALL):
    return ml.MoonlightDecoder(cfg, state, cache_len=CACHE_LEN,
                               max_prompt=32, min_bucket=8)


def _generate(model, prompts, budgets, stagger=3):
    """The prompts through a batcher of 4 slots, the first alone for
    ``stagger`` steps, the rest admitted together. Returns, per request,
    (prompt, tokens, [logits], experts [moe_layers, positions, k])."""
    rec = Recorder(model)
    b = ContinuousBatcher(rec, max_batch=4, max_waiting=8)
    b.warm_up()
    reqs = [GenRequest(p, n) for p, n in zip(prompts, budgets)]
    kept = {}

    def grab(req, _state):
        runs = rec.by_prompt[tuple(req.prompt)]
        kept[req.req_id] = ([r[0] for r in runs],
                            np.concatenate([r[1] for r in runs], 1))
    for r in reqs:
        r.on_finish = grab
    assert b.submit(reqs[0])
    for _ in range(stagger):
        b.step(0)
    for r in reqs[1:]:
        assert b.submit(r)
    steps = 0
    while b.has_work() and steps < 500:
        b.step(0)
        steps += 1
    out = []
    for r in reqs:
        assert r.state == "completed" and len(r.tokens) == r.max_new_tokens
        logits, experts = kept[r.req_id]
        out.append((r.prompt, r.tokens, logits, experts))
    return out


def _compare(state, cfg, runs):
    """(largest logit error, routing mismatches, near-ties) of the runs
    against the reference's full forward pass over prompt + generated."""
    worst, mism, ties = 0.0, 0, 0
    for prompt, tokens, logits, experts in runs:
        seq = np.asarray(prompt + tokens[:-1], np.int32)
        n = len(prompt)
        full, m, t, _split = ref.forward(state, cfg, jnp.asarray(seq),
                                 valid=len(seq),
                                 chosen=jnp.asarray(experts[:, :len(seq)]),
                                 tie_eps=TIE_EPS)
        full = np.asarray(full)
        for j, row in enumerate(logits):
            worst = max(worst, float(np.abs(full[n - 1 + j] - row).max()))
        mism += int(m)
        ties += int(t)
    return worst, mism, ties


PROMPTS = [[5, 300, 17, 9, 511, 2, 77], [40] * 3 + [200, 100],
           list(range(100, 121)), [1, 2]]
BUDGETS = [12, 9, 15, 6]


@pytest.fixture(scope="module")
def state():
    return draw_state(SMALL, 11)


@pytest.fixture(scope="module")
def model(state):
    return _model(state)


@pytest.fixture(scope="module")
def served(model):
    return _generate(model, PROMPTS, BUDGETS)


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_batcher_agrees_with_reference(state, served, seed):
    """Prefill then decode through the batcher, requests of different
    lengths admitted at different steps, against the reference's full
    forward pass: every logit row within ATOL, every position's experts
    the reference's (near-ties aside)."""
    if seed != 11:
        state = draw_state(SMALL, seed)
        served = _generate(_model(state), PROMPTS, BUDGETS, stagger=5)
    worst, mism, ties = _compare(state, SMALL, served)
    assert worst <= ATOL, worst
    assert mism == 0
    positions = sum(len(p) + len(t) - 1 for p, t, _l, _e in served) \
        * SMALL.moe_layers
    assert ties < positions // 4


def test_held_expert_shares_add_up_to_the_uncut_layer(model, state):
    """Expert parallelism: four shares of four experts each, computed by
    the program's expert layer told which experts it holds, plus the
    shared experts counted once, equal the reference's uncut layer."""
    p = model.params["layers"][1]
    x = jax.random.normal(jax.random.key(3), (40, SMALL.hidden_size))
    valid = jnp.ones((40,), bool)
    w, e = ml.route(x, p["router"], p["router_bias"], SMALL)
    total = ml._swiglu(x, p["shared"])
    counts = []
    for share in range(4):
        held = {k: v[4 * share:4 * share + 4] for k, v in
                p["experts"].items()}
        part, c = ml.held_experts(x, w, e, valid, held, 4 * share, 4)
        total = total + part
        counts.append(np.asarray(c))
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_layer(x, state, 1, SMALL, e, held_range=(0, 16))
    scale = float(jnp.max(jnp.abs(uncut)))
    assert float(jnp.max(jnp.abs(total - uncut))) <= 0.02 * scale
    # every (token, choice) pair landed in exactly one share
    assert np.concatenate(counts).sum() == 40 * SMALL.num_experts_per_tok


def _no_shared(m, p, cfg, valid):
    if "dense" in p:
        return ml._swiglu(m, p["dense"]), None, None
    w, e = ml.route(m, p["router"], p["router_bias"], cfg)
    out, counts = ml.held_experts(m, w, e, valid, p["experts"],
                                  cfg.first_expert_held, cfg.experts_held)
    return out, counts, e


def _scores_without_rope(patch):
    latent, expanded = ml._latent_query, ml._expanded_scores
    patch.setattr(ml, "_latent_query", lambda q_lat, q_rope, width: latent(
        q_lat, jnp.zeros_like(q_rope), width))
    patch.setattr(ml, "_expanded_scores", lambda qn, kn, qr, kr: expanded(
        qn, kn, jnp.zeros_like(qr), jnp.zeros_like(kr)))


@pytest.mark.parametrize("broken", ["shared experts left out",
                                    "k_rope left out of the scores",
                                    "cache stored in float8"])
def test_the_comparison_catches(state, broken, monkeypatch):
    """The tolerance is tight enough to fail a program that leaves out
    part of the mathematics or keeps its cache in a lower precision."""
    if broken == "shared experts left out":
        monkeypatch.setattr(ml, "_mlp", _no_shared)
    elif broken == "k_rope left out of the scores":
        _scores_without_rope(monkeypatch)
    else:
        monkeypatch.setattr(ml, "LATENT_DTYPE", jnp.float8_e4m3fn)
    runs = _generate(_model(state), PROMPTS, BUDGETS)
    worst, mism, _ties = _compare(state, SMALL, runs)
    assert worst > ATOL or mism > 0, (broken, worst, mism)


def test_decode_and_prefill_take_the_cache_donated(model):
    """The programs write the cache in place: its buffers are donated
    and aliased to the output (a copy would show as no alias)."""
    batch = 4
    cache = model.new_cache(batch, CACHE_LEN)
    cache_bytes = sum(c.nbytes for c in cache)
    zeros = np.zeros((batch,), np.int32)
    dec = model._decode.lower(model.params, cache, zeros, zeros).compile()
    assert dec.memory_analysis().alias_size_in_bytes == cache_bytes
    pre = model._prefill[8].lower(model.params, cache, np.int32(0),
                                  np.zeros((8,), np.int32),
                                  np.int32(3)).compile()
    assert pre.memory_analysis().alias_size_in_bytes == cache_bytes
    # and a decode really hands its input buffers over
    new, *_ = model.decode(cache, zeros, zeros)
    assert all(c.is_deleted() for c in cache)
    assert dataclasses.is_dataclass(model.config) and len(new) == len(cache)


def test_each_program_is_compiled_once(state):
    """A fresh cache meets the programs as their outputs do: after the
    batcher's warm-up, generations of every bucket build nothing new."""
    model = _model(state)
    b = ContinuousBatcher(model, max_batch=4, max_waiting=8)
    b.warm_up()
    built = {b_: p._cache_size() for b_, p in model._prefill.items()}
    assert set(built.values()) == {1}
    assert model._decode._cache_size() == 1
    _generate(model, [[3] * 7, [4] * 12, [5] * 30], [3, 3, 3])
    assert {b_: p._cache_size() for b_, p in model._prefill.items()} \
        == built
    assert model._decode._cache_size() == 1


@pytest.mark.parametrize("cache_len,rows", [
    (48, [0, 1, 16, 17, 48]), (48, [48, 47, 33, 2, 5]),
    # chunk - 1, and an idle slot between two live ones
    (48, [15, 0, 17, 48, 1]),
    # the first slots idle, the last live
    (48, [0, 0, 16, 15, 48]),
    # nothing live: nothing fetched, nothing computed
    (48, [0, 0, 0, 0, 0]),
    # several turns of four chunks (64 rows): a whole turn, one less,
    # one more, the whole cache; turns ending in 1, 2 and 3 chunks
    (208, [64, 63, 0, 65, 208]), (208, [0, 129, 112, 0, 17])])
def test_latent_attention_kernel_over_blocks(cache_len, rows):
    """The decode kernel (chunks of 16 rows here, up to four a turn of
    its loop) against a plain softmax over each slot's live rows: one
    row, a chunk less one, a whole chunk, a chunk and one, the whole
    cache; the stream of copies passes over idle slots (0 rows), whose
    output the caller leaves alone."""
    kq, kc = jax.random.split(jax.random.key(5))
    width, kvr, heads = 128, 96, 4
    q = jax.random.normal(kq, (5, heads, width)).astype(jnp.bfloat16)
    cache = jax.random.normal(kc, (5, cache_len, width)).astype(jnp.bfloat16)
    n = jnp.asarray(rows, jnp.int32)
    out = np.asarray(latent_attention(q, cache, n, scale=0.25, kvr=kvr))
    s = jnp.einsum("bhc,btc->bht", q.astype(jnp.float32),
                   cache.astype(jnp.float32)) * 0.25
    s = jnp.where(jnp.arange(cache_len)[None, None] < n[:, None, None], s,
                  -jnp.inf)
    want = np.asarray(jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, -1),
                                 cache.astype(jnp.float32))[..., :kvr])
    live = np.asarray(n) > 0
    # the kernel weights the rows in bfloat16 (2^-9 relative)
    np.testing.assert_allclose(out[live], want[live], atol=0.03)


@pytest.mark.parametrize("cache_len,chunk", [(48, 16), (64, 64),
                                             (4096, 256)])
def test_fetched_rows_are_live_rows_in_whole_chunks(cache_len, chunk):
    """What the kernel fetches: each live slot's rows rounded up to whole
    chunks (the largest of 256 ... 8 rows that divides the cache's
    length), nothing for an idle slot or an all-idle batch; the decode
    program returns it as ``attn_fetched``."""
    from brpc_tpu.ops.latent_attention import _chunk_rows, fetched_rows

    assert _chunk_rows(cache_len) == chunk
    rows = np.array([0, 1, chunk - 1, chunk, chunk + 1, cache_len, 0])
    rows = np.minimum(rows, cache_len)
    want = sum(-(-int(r) // chunk) * chunk for r in rows if r > 0)
    got = fetched_rows(jnp.asarray(rows, jnp.int32), cache_len)
    assert got.dtype == jnp.int32 and int(got) == want
    assert int(fetched_rows(jnp.zeros((5,), jnp.int32), cache_len)) == 0


def test_decode_reports_what_its_attention_fetched(model):
    """The decode program's ``attn_fetched``: a slot at length L attends
    over L + 1 rows, fetched in whole chunks of 64 here (the cache's 64
    positions); an idle slot (length -1) fetches nothing."""
    cache = model.new_cache(4, CACHE_LEN)
    lengths = np.array([0, -1, 63, 30], np.int32)
    _cache, _nxt, _counts, aux = model.decode(
        cache, np.zeros((4,), np.int32), lengths)
    assert int(aux["attn_fetched"]) == 64 + 64 + 64
