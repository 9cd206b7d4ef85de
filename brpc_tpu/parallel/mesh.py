"""RpcMesh: the pod fabric as a named device mesh.

The reference addresses peers by EndPoint; inside a pod the natural
address space is mesh coordinates. RpcMesh wraps jax.sharding.Mesh with
the two axes the RPC combinators use:

  'replica' — interchangeable servers (SelectiveChannel's replica set;
              data-parallel axis)
  'shard'   — partitions of one logical service (PartitionChannel's
              shards; tensor/sequence-parallel axis)

Collectives ride ICI when the mesh axes are laid out along the physical
torus — jax.make_mesh picks that layout by default on TPU.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from brpc_tpu.butil.jax_runtime import ensure_compile_cache

REPLICA_AXIS = "replica"
SHARD_AXIS = "shard"


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` over an RpcMesh. Every lowering in parallel/
    and ops/ring_attention.py comes through here, so this is also where
    the compile cache is placed before their first compile."""
    ensure_compile_cache()
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_rpc_mesh(n_replicas: Optional[int] = None,
                  n_shards: Optional[int] = None,
                  devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n_replicas is None and n_shards is None:
        n_replicas, n_shards = 1, n
    elif n_replicas is None:
        n_replicas = n // n_shards
    elif n_shards is None:
        n_shards = n // n_replicas
    if n_replicas * n_shards != n:
        raise ValueError(
            f"{n_replicas}x{n_shards} mesh does not cover {n} devices")
    return jax.make_mesh((n_replicas, n_shards), (REPLICA_AXIS, SHARD_AXIS),
                         devices=devices)


def shard_spec(*names: Optional[str]) -> PartitionSpec:
    return PartitionSpec(*names)


def sharding(mesh: Mesh, *names: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*names))


def endpoint_for_coords(mesh: Mesh, replica: int, shard: int):
    """Mesh coordinate -> the device at that coordinate (the 'address' a
    tpu:// endpoint's device= extra refers to)."""
    return mesh.devices[replica][shard]
