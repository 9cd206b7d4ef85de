"""ParallelChannel 8-shard allreduce (BASELINE.md's new combo-channel
bench), shown both ways:

  host path   — ParallelChannel fans one request out to 8 servers, each
                reduces its shard, the merger sums on the host
  device path — CollectiveChannel lowers the same dataflow to one SPMD
                psum over the mesh (the TPU-native answer)
"""

import sys
import time

sys.path.insert(0, __file__.rsplit("/examples", 1)[0])

import numpy as np


def main(n_shards: int = 8, dim: int = 1 << 16) -> None:
    n_shards, dim = int(n_shards), int(dim)

    # ---------------- host path: 8 real servers + ParallelChannel
    from brpc_tpu.rpc import (Channel, ParallelChannel, ResponseMerger, Server,
                              ServerOptions, Service, SubCall, CallMapper,
                              Controller)

    servers = []
    for i in range(n_shards):
        s = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Reduce")

        def Sum(cntl, request, _i=i):
            arr = np.frombuffer(request, dtype=np.float32)
            return np.array([arr.sum()], dtype=np.float32).tobytes()
        svc.register_method("Sum", Sum)
        s.add_service(svc)
        servers.append((s, s.start(f"mem://allreduce-{i}")))

    class ShardMapper(CallMapper):
        def map(self, i, n, service, method, request, cntl):
            shard = request[i * len(request) // n: (i + 1) * len(request) // n]
            return SubCall(service, method, shard)

    pch = ParallelChannel(call_mapper=ShardMapper())
    for _, ep in servers:
        pch.add_sub_channel(Channel(str(ep)))

    data = np.ones(dim, dtype=np.float32)
    t0 = time.perf_counter()
    cntl = pch.call_sync("Reduce", "Sum", data.tobytes())
    host_ms = (time.perf_counter() - t0) * 1e3
    total = sum(np.frombuffer(r, np.float32)[0] for r in cntl.sub_responses)
    print(f"host ParallelChannel: sum={total:.0f} (expect {dim}) in {host_ms:.2f}ms")
    for s, _ in servers:
        s.stop(); s.join(2)

    # ---------------- device path: one psum over the mesh
    import jax
    import jax.numpy as jnp
    from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh

    # the mesh shrinks to the devices there are; the result line says so
    n_dev = min(n_shards, len(jax.devices()))
    mesh = make_rpc_mesh(n_replicas=1, n_shards=n_dev,
                         devices=jax.devices()[:n_dev])
    cc = CollectiveChannel(mesh)
    x = jnp.ones((n_dev, dim // n_dev), jnp.float32)

    def shard_sum(s):  # one stable fn: cc.call caches the compilation by it
        return s.sum()[None]

    out = cc.call(shard_sum, x, merge="sum")  # warm compile
    t0 = time.perf_counter()
    out = jax.block_until_ready(cc.call(shard_sum, x, merge="sum"))
    dev_ms = (time.perf_counter() - t0) * 1e3
    print(f"device CollectiveChannel psum: sum={float(out[0]):.0f} in {dev_ms:.2f}ms "
          f"on {n_dev} of {n_shards} requested {jax.devices()[0].platform} "
          f"device(s)")


if __name__ == "__main__":
    main(*sys.argv[1:])
