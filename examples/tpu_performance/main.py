"""tpu_performance: the 4B-4MB payload sweep (example/rdma_performance
rebuilt for the device fabric — BASELINE.md's north-star config).

Runs over ici:// — the PjRt pull-DMA data plane (the RDMA slot) — and
reports per-size throughput plus p50/p99 latency from a
bvar.LatencyRecorder, the same runtime shape as
example/rdma_performance/client.cpp:261 (QPS + bvar percentiles).

Usage: main.py [iters] [address]
  address defaults to an in-process ici:// loopback on 127.0.0.1; point
  it at another host's ici_echo server for a true two-process run.
"""

import sys
import time

sys.path.insert(0, __file__.rsplit("/examples", 1)[0])


def main(iters: int = 30, address: str = "") -> None:
    import jax
    import jax.numpy as jnp

    from brpc_tpu.bvar.latency_recorder import LatencyRecorder
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                              Service)

    iters = int(iters)
    server = None
    if not address:
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Perf")

        @svc.method()
        def Echo(cntl, request):
            cntl.response_device_arrays = cntl.request_device_arrays
            return b""

        server.add_service(svc)
        ep = server.start("ici://127.0.0.1:0#device=0")
        address = f"ici://127.0.0.1:{ep.port}#reply_device=0"

    ch = Channel(address, ChannelOptions(timeout_ms=60000))

    print(f"{'size':>10} {'avg_us':>10} {'p50_us':>10} {'p99_us':>10} "
          f"{'GB/s':>8}")
    size = 4
    lane = None
    while size <= 4 * 1024 * 1024:
        n = max(1, size // 4)
        payload = jax.block_until_ready(jnp.ones((n,), jnp.float32))
        for _ in range(3):
            cntl = ch.call_sync("Perf", "Echo", b"",
                                request_device_arrays=[payload])
            assert not cntl.failed(), cntl.error_text
        if lane is None:
            lane = ch._get_socket().conn.lane_kind
        rec = LatencyRecorder()
        t0 = time.perf_counter()
        for _ in range(iters):
            c0 = time.perf_counter_ns()
            cntl = ch.call_sync("Perf", "Echo", b"",
                                request_device_arrays=[payload])
            assert not cntl.failed(), cntl.error_text
            rec.record((time.perf_counter_ns() - c0) / 1e3)
        dt = time.perf_counter() - t0
        gbps = iters * n * 4 * 2 / dt / 1e9
        print(f"{n*4:>10} {rec.latency():>10.1f} "
              f"{rec.latency_percentile(0.5):>10.1f} "
              f"{rec.latency_percentile(0.99):>10.1f} {gbps:>8.3f}")
        size *= 4
    print(f"lane: {lane}")
    ch.close()
    if server is not None:
        server.stop()
        server.join(2)


if __name__ == "__main__":
    main(*sys.argv[1:])
