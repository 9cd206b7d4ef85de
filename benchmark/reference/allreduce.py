"""Plain reference of the sharded allreduce, independent of brpc_tpu:
the request's rows split into ``n`` equal blocks, shard i answers its
block times 2, the caller gets the sum of the answers. Float32; the
data are small integers, so the bf16 result must equal it exactly."""

from __future__ import annotations


def allreduce_reference(request, n_shards: int):
    import jax.numpy as jnp

    rows = request.shape[0] // n_shards
    blocks = request.astype(jnp.float32).reshape(
        (n_shards, rows) + request.shape[1:])
    return jnp.sum(blocks * 2.0, axis=0)
