"""Plain reference of the lowered allreduce, independent of brpc_tpu
(the ``collective_allreduce`` configuration's own copy): the request's
rows split into ``n`` equal blocks, each block times 2, the blocks
summed. Float32; the data are small integers, so the bf16 result must
equal it exactly. With it, the bytes the lowered program has to move,
from the shapes, for the kernel's roofline."""

from __future__ import annotations


def allreduce_reference(request, n_shards: int):
    import jax.numpy as jnp

    rows = request.shape[0] // n_shards
    blocks = request.astype(jnp.float32).reshape(
        (n_shards, rows) + request.shape[1:])
    return jnp.sum(blocks * 2.0, axis=0)


def collective_bytes(n_chips: int, block_bytes: int) -> int:
    """Bytes the busiest chip must SEND for an all-reduce of one block a
    chip over ``n_chips``: a reduce-scatter and an all-gather, each of
    which sends (n-1)/n of the block, whatever the algorithm's steps."""
    return 2 * (n_chips - 1) * block_bytes // n_chips


def scatter_bytes(n_chips: int, block_bytes: int) -> int:
    """Bytes that leave the source chip when it scatters a request of
    ``n_chips`` blocks: every block but its own."""
    return (n_chips - 1) * block_bytes


def hbm_bytes(n_chips: int, block_bytes: int, scatter_inside: bool) -> int:
    """The least memory traffic of the busiest chip: it reads its block
    and writes the sum; the source of an in-program scatter reads the
    whole request."""
    read = n_chips * block_bytes if scatter_inside else block_bytes
    return read + block_bytes
