"""Decode attention over a latent cache (multi-head latent attention,
DeepSeek-V2/V3): every head of a slot attends over ONE cached row a
position, in one Pallas kernel (``mla_decode``).

A query is the head's query taken into the latent, [q_lat ; q_rope ;
zeros] (``serving/moonlight.py``); a cached row is [c ; k_rope ; zeros]
of the same width, so a score is one contraction over the row and the
softmax-weighted rows give the latent output (the first ``kvr``
columns), which the caller takes out to each head's value.

The kernel walks each slot's live rows itself: the grid is over slots,
in order, and the cache stays in HBM. A slot's rows are fetched in
chunks (``_chunk_rows``: the largest of 256 ... 8 rows that divides the
cache's length) and computed in turns of up to ``_TURN_CHUNKS`` chunks,
through two VMEM buffers of a turn each, in ONE stream of copies that
runs across slots: turn i+1's copies start before turn i computes, and
before a slot's last turn computes the next slot's first turn starts.
A turn copies and computes only its chunks that hold live rows. So no
copy waits behind a grid step with nothing to do, a slot fetches its
live rows rounded up to a chunk (``fetched_rows``), and an idle slot
fetches and computes nothing. Small chunks keep what a slot reads past
its rows small; a turn of several chunks spreads the fixed cost of a
turn of the loop (its copies' starts and waits, and the chain of the
two products and the softmax between them) over more rows. Each chunk
is read ONCE into VMEM for both products, with an online softmax
across turns. On a backend other than the TPU the kernel runs in
Pallas's interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# chunks one turn of the kernel's loop computes over, at most: a turn
# has a fixed cost of its own (about 0.34 us on a TPU v5e), which four
# 256-row chunks hide behind their copies and one does not
_TURN_CHUNKS = 4


def _chunk_rows(cache_len: int) -> int:
    """Cached rows one copy of the latent attention kernel fetches."""
    for rows in (256, 128, 64, 32, 16, 8):
        if cache_len % rows == 0:
            return rows
    raise ValueError(f"cache_len {cache_len} is not a multiple of 8")


def fetched_rows(rows, cache_len: int):
    """The cached rows ``latent_attention`` fetches for these live
    counts (int32): each slot's rows rounded up to whole chunks, none
    for an idle slot."""
    chunk = _chunk_rows(cache_len)
    return jnp.sum((rows + chunk - 1) // chunk * chunk).astype(jnp.int32)


def latent_attention(q, cache, rows, *, scale: float, kvr: int):
    """Decode attention of every slot over its own cached rows, one
    Pallas kernel (``mla_decode``): q [B, heads, W] (the query in the
    latent), cache [B, T, W], rows [B] (rows 0 .. rows-1 are live; 0:
    an idle slot, whose output is garbage). Returns the softmax-weighted
    latents [B, heads, kvr] in float32. A call costs the live rows of
    each slot, rounded up to a chunk, not the cache's length."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, width = q.shape
    chunk = _chunk_rows(cache.shape[1])
    turn = _TURN_CHUNKS * chunk
    f32 = jnp.float32

    def kernel(live_ref, q_ref, kv_hbm, o_ref, buf, sem, first_ref):
        b = pl.program_id(0)

        def copies(slot, i, k):
            # turn i of ``slot`` into buffer k: each chunk's copy, and
            # whether the chunk holds rows of the slot
            for c in range(_TURN_CHUNKS):
                at = pl.multiple_of(i * turn + c * chunk, chunk)
                yield at < live_ref[slot], pltpu.make_async_copy(
                    kv_hbm.at[slot, pl.ds(at, chunk)],
                    buf.at[k, pl.ds(c * chunk, chunk)], sem.at[k, c])

        def start(slot, i, k):
            for held, copy in copies(slot, i, k):
                pl.when(held)(copy.start)

        @pl.when(b == 0)
        def _():
            first_ref[0] = 0
            start(0, 0, 0)

        # this slot's first turn is in flight into buffer ``first``
        # (started by the slot before); the stream passes to slot b+1
        # where it has rows
        n = live_ref[b]
        trips = (n + turn - 1) // turn
        first = first_ref[0]
        pass_on = (b + 1 < batch) & (
            live_ref[jnp.minimum(b + 1, batch - 1)] > 0)

        @pl.when(pass_on & (trips == 0))
        def _():
            start(b + 1, 0, first)

        def attend(held, i, k, carry):
            # the online softmax over the turn's first ``held`` chunks
            m_prev, l_prev, acc = carry
            kv = buf[k, :held * chunk]
            s = jax.lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * scale
            at = i * turn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at < n, s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            return (m_new, alpha * l_prev + jnp.sum(e, axis=1, keepdims=True),
                    alpha * acc + jnp.dot(e.astype(kv.dtype), kv[:, :kvr],
                                          preferred_element_type=f32))

        def step(i, carry):
            k = (first + i) % 2

            @pl.when(i + 1 < trips)
            def _():
                start(b, i + 1, 1 - k)

            @pl.when((i + 1 == trips) & pass_on)
            def _():
                start(b + 1, 0, 1 - k)

            for held, copy in copies(b, i, k):
                pl.when(held)(copy.wait)
            held = jnp.minimum((n - i * turn + chunk - 1) // chunk, _TURN_CHUNKS)
            return jax.lax.switch(
                held - 1, [functools.partial(attend, c + 1)
                           for c in range(_TURN_CHUNKS)], i, k, carry)

        _, l_sum, acc = jax.lax.fori_loop(
            0, trips, step, (jnp.full((heads, 1), -jnp.inf, f32),
                             jnp.zeros((heads, 1), f32),
                             jnp.zeros((heads, kvr), f32)))
        first_ref[0] = (first + trips) % 2
        o_ref[0] = acc / l_sum

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(batch,),
        in_specs=[pl.BlockSpec((1, heads, width), lambda b, live: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, heads, kvr), lambda b, live: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, turn, width), cache.dtype),
                        pltpu.SemaphoreType.DMA((2, _TURN_CHUNKS)),
                        pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((batch, heads, kvr), f32),
        grid_spec=grid, name="mla_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=jax.default_backend() != "tpu")(rows, q, cache)
