"""Decode attention over a latent cache (multi-head latent attention,
DeepSeek-V2/V3): every head of a slot attends over ONE cached row a
position, in one Pallas kernel (``mla_decode``).

A query is the head's query taken into the latent, [q_lat ; q_rope ;
zeros] (``serving/moonlight.py``); a cached row is [c ; k_rope ; zeros]
of the same width, so a score is one contraction over the row and the
softmax-weighted rows give the latent output (the first ``kvr``
columns), which the caller takes out to each head's value. Each grid
step (slot, block of rows) reads its block ONCE into VMEM for both
products, with an online softmax across blocks; blocks past a slot's
live rows are neither fetched nor computed. On a backend other than the
TPU the kernel runs in Pallas's interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _block_rows(cache_len: int) -> int:
    """Cached rows a grid step of the latent attention kernel reads."""
    for rows in (1024, 512, 256, 128, 64, 32, 16, 8):
        if cache_len % rows == 0:
            return rows
    raise ValueError(f"cache_len {cache_len} is not a multiple of 8")


def latent_attention(q, cache, rows, *, scale: float, kvr: int):
    """Decode attention of every slot over its own cached rows, one
    Pallas kernel (``mla_decode``): q [B, heads, W] (the query in the
    latent), cache [B, T, W], rows [B] (rows 0 .. rows-1 are live; 0:
    an idle slot, whose output is garbage). Returns the softmax-weighted
    latents [B, heads, kvr] in float32.

    The block index stops at a slot's last live block, so no block past
    it is fetched again, and no step past it computes: a call costs the
    live rows of each slot, not the cache's length."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, width = q.shape
    block = _block_rows(cache.shape[1])
    f32 = jnp.float32

    def rows_map(b, t, live):
        return (b, jnp.minimum(t, jnp.maximum(live[b] - 1, 0) // block), 0)

    def kernel(live_ref, q_ref, kv_ref, o_ref, m_sc, l_sc, acc_sc):
        b, t = pl.program_id(0), pl.program_id(1)

        @pl.when(t == 0)
        def _():
            m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, f32)
            l_sc[...] = jnp.zeros(l_sc.shape, f32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

        n = live_ref[b]

        @pl.when(t * block < n)
        def _():
            kv = kv_ref[0]
            s = jax.lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * scale
            at = t * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at < n, s, -jnp.inf)
            m_prev = m_sc[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[...] = alpha * l_sc[...] + jnp.sum(e, axis=1, keepdims=True)
            acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
                e.astype(kv.dtype), kv[:, :kvr], preferred_element_type=f32)
            m_sc[...] = m_new

        @pl.when(t == pl.num_programs(1) - 1)
        def _():
            o_ref[0] = acc_sc[...] / l_sc[...]

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(batch, cache.shape[1] // block),
        in_specs=[pl.BlockSpec((1, heads, width), lambda b, t, live: (b, 0, 0)),
                  pl.BlockSpec((1, block, width), rows_map)],
        out_specs=pl.BlockSpec((1, heads, kvr), lambda b, t, live: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((heads, 1), f32),
                        pltpu.VMEM((heads, 1), f32),
                        pltpu.VMEM((heads, kvr), f32)])
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((batch, heads, kvr), f32),
        grid_spec=grid, name="mla_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu")(rows, q, cache)
