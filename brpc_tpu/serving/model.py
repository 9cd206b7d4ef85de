"""TinyDecoder: the deterministic toy model behind the serving lane.

One attention layer over byte-level tokens, with weights derived from a
seed (``numpy.random.RandomState``) — tests and smokes need no
checkpoint files, and the same (seed, prompt) always generates the same
token stream, which is what lets the scheduling tests assert
"retirement order independence" (a sequence's tokens must not depend on
what else shares the batch).

The split mirrors a real single-layer decoder's serving shape:

  * **prefill** is position-wise: with one layer, a position's KV-cache
    entry is a function of that position's embedding alone (no attention
    needed to build the cache), so admission costs one vectorized numpy
    pass over the prompt — cheap enough to run inline in the decode
    loop between steps;
  * **decode step** is the attention-bound part: one query row per
    running sequence attends over its KV cache via
    ``ops.flash_attention.decode_attention`` (the blockwise
    online-softmax kernel), then greedy-argmax picks the next token and
    the step returns that token's fresh (k, v, h) row for the host to
    append. The step is jitted ONCE for the engine's fixed
    (max_batch, cache_len) slot shape — admission/retirement change
    which slots are live, never the compiled shape.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

DEFAULT_SEED = 20260803


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


class TinyDecoder:
    """Deterministic seed-derived weights + the jitted decode step."""

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
        self._step_fn = None    # jitted lazily (first decode compiles)

    # ------------------------------------------------------------ prefill
    def prefill(self, tokens) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Build the KV rows for a prompt (position-wise, pure numpy).
        Returns (k [L, d], v [L, d], h_last [d])."""
        toks = np.asarray(tokens, np.int64)
        h = self.emb[toks] + self.pos[: len(toks)]
        return h @ self.wk, h @ self.wv, h[-1]

    # -------------------------------------------------------- decode step
    def _build_step(self):
        import jax
        import jax.numpy as jnp

        from brpc_tpu.butil.jax_runtime import ensure_compile_cache
        from brpc_tpu.ops.flash_attention import decode_attention

        ensure_compile_cache()
        emb = jnp.asarray(self.emb)
        wq, wk = jnp.asarray(self.wq), jnp.asarray(self.wk)
        wv, wo = jnp.asarray(self.wv), jnp.asarray(self.wo)
        pos = jnp.asarray(self.pos)
        block_k = self.config.block_k

        @jax.jit
        def step(k_cache, v_cache, h_last, lengths):
            # k_cache/v_cache: [B, L, d]; h_last: [B, d]; lengths: [B]
            q = h_last @ wq
            o = decode_attention(q, k_cache, v_cache, lengths,
                                 block_k=block_k)
            # logits from the ATTENTION output plus a strong position
            # term (no embedding residual: emb[t]·emb[t]
            # self-similarity would make every sequence collapse to a
            # one-token fixed point) — attention + per-step position
            # keep the stream varying as the cache grows, still fully
            # deterministic and still a function of THIS sequence alone
            cur_pos = pos[jnp.clip(lengths, 0, pos.shape[0] - 1)]
            logits = (o @ wo + 3.0 * cur_pos) @ emb.T
            nxt = jnp.argmax(logits, axis=-1)
            # the NEW token's cache row (position = lengths, i.e. the
            # slot right after the current last valid row)
            h_new = emb[nxt] + cur_pos
            return nxt, h_new @ wk, h_new @ wv, h_new

        return step

    def decode_step(self, k_cache: np.ndarray, v_cache: np.ndarray,
                    h_last: np.ndarray, lengths: np.ndarray):
        """One greedy decode step for a fixed-shape slot batch. Returns
        numpy (next_tokens [B], k_new [B, d], v_new [B, d],
        h_new [B, d]); rows of inactive slots are garbage the caller
        masks by its own active set."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        nxt, k_new, v_new, h_new = self._step_fn(
            k_cache, v_cache, h_last, lengths.astype(np.int32))
        return (np.asarray(nxt), np.asarray(k_new), np.asarray(v_new),
                np.asarray(h_new))

    # ---------------------------------------------------------- reference
    def generate(self, prompt_tokens, max_new_tokens: int):
        """Single-sequence oracle: the exact token stream the batched
        engine must reproduce regardless of batch composition."""
        cfg = self.config
        k = np.zeros((1, cfg.cache_len, cfg.dim), np.float32)
        v = np.zeros((1, cfg.cache_len, cfg.dim), np.float32)
        h = np.zeros((1, cfg.dim), np.float32)
        kp, vp, hl = self.prefill(prompt_tokens)
        n = len(prompt_tokens)
        k[0, :n], v[0, :n], h[0] = kp, vp, hl
        out = []
        lens = np.array([n], np.int64)
        for _ in range(max_new_tokens):
            if lens[0] >= cfg.cache_len:
                break
            nxt, kn, vn, hn = self.decode_step(k, v, h, lens)
            tok = int(nxt[0])
            out.append(tok)
            k[0, lens[0]], v[0, lens[0]], h[0] = kn[0], vn[0], hn[0]
            lens[0] += 1
        return out
