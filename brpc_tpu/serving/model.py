"""The model interface the continuous batcher drives, and TinyDecoder,
the deterministic toy behind it in tests and smokes.

**The interface.** A model owns its cache, a pytree of device arrays
with one slot a sequence, and three calls; the batcher
(``serving/batcher.py``) never looks inside the cache:

  * ``new_cache(max_batch, cache_len)``: the empty cache;
  * ``prefill(cache, slot, tokens) -> (cache, first_token, counters,
    aux)``: write the prompt's rows into ``slot`` and give the first
    generated token (a device scalar);
  * ``decode(cache, last_tokens, lengths) -> (cache, next_tokens,
    counters, aux)``: ``lengths[b]`` rows are cached for slot b and
    ``last_tokens[b]`` sits at position ``lengths[b]``; write its row
    there and give the next token of every slot ([max_batch]). A slot
    with ``lengths[b] < 0`` is idle: nothing is written for it.

Both take the cache DONATED and return the new one: the rows are
written in place on the device, and per step only the tokens and the
small ``counters`` array (or None) cross to the host. ``aux`` holds
device arrays for checks (the logits), or None; of a decode's aux the
batcher reads ``attn_fetched`` alone where it is there (an int32
scalar: the cached rows the attention fetched a layer) into that
decode's step record, in the same fetch as its tokens. Beside
the calls a model has ``vocab``, ``cache_len`` (positions a slot
holds), ``max_prompt`` and ``warm_up(cache) -> cache``, which compiles
every program the batcher will run. ``serving/moonlight.py`` is the
second model behind the interface.

**TinyDecoder.** One attention layer over byte-level tokens, with
weights derived from a seed (``numpy.random.RandomState``): tests need
no checkpoint, and the same (seed, prompt) always generates the same
token stream, which is what lets the scheduling tests assert
"retirement order independence" (a sequence's tokens must not depend on
what else shares the batch). Its cache is ``{"k", "v"}`` of
[max_batch, cache_len, dim]; a position's row is a function of its
token and position alone. Decode attends with
``ops.flash_attention.decode_attention`` (the blockwise online-softmax
kernel), then greedy argmax. The prefill program is compiled once a
prompt-length bucket (powers of two), the decode program once for the
slot shape: admission and retirement change which slots are live,
never the compiled shapes.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import numpy as np

DEFAULT_SEED = 20260803
MIN_BUCKET = 8


class TinyDecoderConfig:
    def __init__(self, vocab: int = 256, dim: int = 32,
                 cache_len: int = 160, seed: int = DEFAULT_SEED,
                 block_k: int = 64):
        self.vocab = vocab
        self.dim = dim
        self.cache_len = cache_len    # KV slot capacity (prompt + gen)
        self.seed = seed
        self.block_k = block_k


def _sinusoid(n: int, d: int) -> np.ndarray:
    pos = np.arange(n)[:, None].astype(np.float64)
    div = np.exp(np.arange(0, d, 2) * (-np.log(10000.0) / d))
    pe = np.zeros((n, d), np.float64)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div[: (d + 1) // 2][: pe[:, 1::2].shape[1]])
    return pe.astype(np.float32)


def _logits(w, o, cur_pos):
    # logits from the ATTENTION output plus a strong position term (no
    # embedding residual: emb[t]·emb[t] self-similarity would make every
    # sequence collapse to a one-token fixed point) — attention +
    # per-step position keep the stream varying as the cache grows,
    # still fully deterministic and a function of THIS sequence alone
    return (o @ w["wo"] + 3.0 * cur_pos) @ w["emb"].T


def _tiny_prefill(w, cache, slot, tokens, n, *, block_k):
    import jax
    import jax.numpy as jnp

    from brpc_tpu.ops.flash_attention import decode_attention

    width = tokens.shape[0]
    top = w["pos"].shape[0] - 1
    h = w["emb"][tokens] + w["pos"][:width]
    at = (slot, jnp.int32(0), jnp.int32(0))
    k = jax.lax.dynamic_update_slice(cache["k"], (h @ w["wk"])[None], at)
    v = jax.lax.dynamic_update_slice(cache["v"], (h @ w["wv"])[None], at)
    q = jax.lax.dynamic_index_in_dim(h, n - 1) @ w["wq"]
    o = decode_attention(q, jax.lax.dynamic_index_in_dim(k, slot),
                         jax.lax.dynamic_index_in_dim(v, slot), n[None],
                         block_k=block_k)
    logits = _logits(w, o, w["pos"][jnp.minimum(n, top)][None])
    return ({"k": k, "v": v}, jnp.argmax(logits[0]).astype(jnp.int32),
            None, None)


def _tiny_decode(w, cache, last_tokens, lengths, *, block_k):
    import jax.numpy as jnp

    from brpc_tpu.ops.flash_attention import decode_attention

    slots = jnp.arange(last_tokens.shape[0])
    top = w["pos"].shape[0] - 1
    h = w["emb"][last_tokens] + w["pos"][jnp.clip(lengths, 0, top)]
    at = jnp.where(lengths >= 0, lengths, cache["k"].shape[1])
    k = cache["k"].at[slots, at].set(h @ w["wk"], mode="drop")
    v = cache["v"].at[slots, at].set(h @ w["wv"], mode="drop")
    o = decode_attention(h @ w["wq"], k, v, jnp.maximum(lengths + 1, 1),
                         block_k=block_k)
    logits = _logits(w, o, w["pos"][jnp.clip(lengths + 1, 0, top)])
    return ({"k": k, "v": v}, jnp.argmax(logits, axis=-1).astype(jnp.int32),
            None, None)


@functools.lru_cache(maxsize=None)
def _programs(block_k: int):
    """(prefill, decode), jitted once a process for each block_k: every
    TinyDecoder of the same shapes shares one compile."""
    import jax

    from brpc_tpu.butil.jax_runtime import ensure_compile_cache

    ensure_compile_cache()
    return (jax.jit(functools.partial(_tiny_prefill, block_k=block_k),
                    donate_argnums=(1,)),
            jax.jit(functools.partial(_tiny_decode, block_k=block_k),
                    donate_argnums=(1,)))


class TinyDecoder:
    """Deterministic seed-derived weights behind the model interface."""

    def __init__(self, config: TinyDecoderConfig = None):
        self.config = cfg = config or TinyDecoderConfig()
        rng = np.random.RandomState(cfg.seed)
        s = cfg.dim ** -0.5
        # embedding variance deliberately > weight variance: greedy
        # argmax must be well-separated so a float tie can't flip a
        # token between runs (determinism is load-bearing for tests)
        self.emb = rng.randn(cfg.vocab, cfg.dim).astype(np.float32)
        self.wq = (rng.randn(cfg.dim, cfg.dim) * s).astype(np.float32)
        self.wk = (rng.randn(cfg.dim, cfg.dim) * s).astype(np.float32)
        self.wv = (rng.randn(cfg.dim, cfg.dim) * s).astype(np.float32)
        self.wo = (rng.randn(cfg.dim, cfg.dim) * s).astype(np.float32)
        self.pos = _sinusoid(cfg.cache_len, cfg.dim)
        self.vocab = cfg.vocab
        self.cache_len = cfg.cache_len
        self.max_prompt = cfg.cache_len - 1
        self.buckets = []
        b = MIN_BUCKET
        while True:
            self.buckets.append(min(b, cfg.cache_len))
            if b >= self.max_prompt:
                break
            b *= 2
        self._weights = None    # device copies, made at the first call

    def _w(self) -> dict:
        if self._weights is None:
            import jax.numpy as jnp
            self._weights = {n: jnp.asarray(getattr(self, n)) for n in
                             ("emb", "wq", "wk", "wv", "wo", "pos")}
        return self._weights

    # --------------------------------------------------- model interface
    def new_cache(self, max_batch: int, cache_len: int):
        import jax.numpy as jnp

        if cache_len != self.cache_len:
            raise ValueError(f"TinyDecoder holds {self.cache_len} "
                             f"positions a slot, not {cache_len}")
        shape = (int(max_batch), int(cache_len), self.config.dim)
        return {"k": jnp.zeros(shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.float32)}

    def prefill(self, cache, slot: int, tokens: Sequence[int]):
        n = len(tokens)
        width = next(b for b in self.buckets if n <= b)
        padded = np.zeros((width,), np.int32)
        padded[:n] = tokens
        return _programs(self.config.block_k)[0](
            self._w(), cache, np.int32(slot), padded, np.int32(n))

    def decode(self, cache, last_tokens, lengths):
        return _programs(self.config.block_k)[1](
            self._w(), cache, np.asarray(last_tokens, np.int32),
            np.asarray(lengths, np.int32))

    def warm_up(self, cache):
        batch = cache["k"].shape[0]
        for b in self.buckets:
            cache, tok, _, _ = self.prefill(cache, 0, [0] * b)
        cache, nxt, _, _ = self.decode(cache, np.zeros(batch, np.int32),
                                       np.full(batch, -1, np.int32))
        nxt.block_until_ready()
        return cache

    # ---------------------------------------------------------- reference
    def generate(self, prompt_tokens, max_new_tokens: int) -> List[int]:
        """Single-sequence oracle: the exact token stream the batched
        engine must reproduce regardless of batch composition."""
        n = len(prompt_tokens)
        out: List[int] = []
        if max_new_tokens < 1 or n >= self.cache_len:
            return out
        cache = self.new_cache(1, self.cache_len)
        cache, tok, _, _ = self.prefill(cache, 0, list(prompt_tokens))
        out.append(int(tok))
        while len(out) < max_new_tokens and n + len(out) < self.cache_len:
            cache, nxt, _, _ = self.decode(cache, [out[-1]],
                                           [n + len(out) - 1])
            out.append(int(nxt[0]))
        return out
