"""ParallelChannel allreduce over the devices of one host (BASELINE.md's
combo-channel bench), the same call both ways:

  fan-out  — one server a device on ``ici://``; the stock
             ``RowScatterMapper`` sends block i of the request's rows to
             sub i through the device lane, every shard reduces its
             block, the stock ``SumMerger`` adds the replies on the
             caller's reply device
  lowered  — ``attach_collective`` on the SAME channel: the call becomes
             one XLA program (scatter, the shard function, ``psum``)
             over the mesh, and no message is sent at all
"""

import sys
import time

sys.path.insert(0, __file__.rsplit("/examples", 1)[0])


def shard_sum(s):
    """What one shard computes on its block of rows (and the function
    the lowered program runs per shard: one stable object, the compiled
    program is cached by it)."""
    return s.sum(axis=1)


def main(n_shards: int = 8, dim: int = 1 << 16) -> None:
    n_shards, dim = int(n_shards), int(dim)

    import jax
    import jax.numpy as jnp

    from brpc_tpu.parallel import CollectiveChannel, make_rpc_mesh
    from brpc_tpu.rpc import (Channel, Controller, ParallelChannel,
                              RowScatterMapper, Server, ServerOptions,
                              Service, SumMerger)

    # the mesh shrinks to the devices there are; the result lines say so
    n = min(n_shards, len(jax.devices()))
    devices = jax.devices()[:n]
    jitted = jax.jit(shard_sum)

    servers, subs = [], []
    pch = ParallelChannel(call_mapper=RowScatterMapper(),
                          response_merger=SumMerger())
    for i in range(n):
        srv = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Reduce")

        def Sum(cntl, request):
            cntl.response_device_arrays = [
                jitted(cntl.request_device_arrays[0])]
            return b""
        svc.register_method("Sum", Sum)
        srv.add_service(svc)
        ep = srv.start(f"ici://127.0.0.1:0#device={i}")
        servers.append(srv)
        subs.append(Channel(f"ici://127.0.0.1:{ep.port}#reply_device=0"))
        pch.add_sub_channel(subs[-1])

    # one row a shard, committed to device 0 as a caller's tensor is
    x = jax.device_put(jnp.ones((n, dim // n), jnp.float32), devices[0])

    def call():
        cntl = Controller()
        cntl.request_device_arrays = [x]
        t0 = time.perf_counter()
        cntl = pch.call("Reduce", "Sum", b"", cntl=cntl)
        assert cntl.join(30) and not cntl.failed(), cntl.error_text
        out = jax.block_until_ready(cntl.response_device_arrays[0])
        return cntl, float(out[0]), (time.perf_counter() - t0) * 1e3

    try:
        call()                                  # warm: dials, compiles
        _, total, ms = call()
        print(f"fan-out ParallelChannel: sum={total:.0f} (expect "
              f"{n * (dim // n)}) in {ms:.2f}ms over {n} lane RPCs")

        mesh = make_rpc_mesh(n_replicas=1, n_shards=n, devices=devices)
        pch.attach_collective(CollectiveChannel(mesh),
                              {("Reduce", "Sum"): shard_sum})
        call()                                  # warm: the one compile
        cntl, total, ms = call()
        assert cntl.collective_lowered and pch.collective_fallbacks == 0
        print(f"lowered to one collective: sum={total:.0f} in {ms:.2f}ms "
              f"on {n} of {n_shards} requested "
              f"{devices[0].platform} device(s)")
    finally:
        for sub in subs:
            sub.close()
        for srv in servers:
            srv.stop()
            srv.join(2)


if __name__ == "__main__":
    main(*sys.argv[1:])
