"""Bidirectional streaming echo (example/streaming_echo_c++) over the
ici:// device-fabric transport, with per-frame latency percentiles.

Streams ride the same connection as ordinary RPCs (stream ids
piggyback on the Open call), so this exercises credit-based stream
flow control on top of the ici framing.

    python examples/streaming_echo/main.py [n_frames] [address] [device_frames]

With ``device_frames`` every frame carries one jax array beside its
tag, through the calls the benchmark's ring uses
(``benchmark/services/stream_ring.py``): ``stream_options=`` on the
Open call, ``stream_accept`` in the handler, ``await stream.write(tag,
device_arrays=[x])``; the acceptor adds one on its own device in
``on_received`` and writes the result back from the drainer fiber."""

import sys
import time

sys.path.insert(0, __file__.rsplit("/examples", 1)[0])

from brpc_tpu import fiber
from brpc_tpu.bvar.latency_recorder import LatencyRecorder
from brpc_tpu.rpc import Channel, Server, ServerOptions, Service
from brpc_tpu.rpc.stream import StreamOptions, stream_accept


def main(n_frames: int = 20, address: str = "",
         device_frames: str = "") -> None:
    n_frames = int(n_frames)
    device_frames = str(device_frames).lower() in ("1", "true",
                                                   "device_frames")
    if device_frames:
        import jax
        import jax.numpy as jnp
        import numpy as np

        add_one = jax.jit(lambda x: x + jnp.asarray(1, x.dtype))
    server = None
    if not address:
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("StreamEcho")

        @svc.method()
        def Open(cntl, request):
            async def on_received(stream, msg):
                arrays = [add_one(a) for a in msg.device_arrays]
                await stream.write(b"echo:" + msg.payload.to_bytes(),
                                   device_arrays=arrays or None)
            s = stream_accept(cntl, StreamOptions(on_received=on_received))
            if s is not None:
                # handler-owned stream: self-close on the client's close
                s.on_close(lambda st: st.close())
            return b"accepted"

        server.add_service(svc)
        ep = server.start("ici://127.0.0.1:0#device=0")
        address = f"ici://127.0.0.1:{ep.port}"

    got = []
    rec = LatencyRecorder()
    sent_ns = {}
    ch = Channel(address)
    arrays = []

    def on_echo(s, m):
        body = m.payload.to_bytes()
        got.append(body)
        arrays.extend(m.device_arrays)
        idx = body.rsplit(b"-", 1)[-1]
        t0 = sent_ns.pop(idx, None)
        if t0 is not None:
            rec.record((time.perf_counter_ns() - t0) / 1e3)

    cntl = ch.call_sync("StreamEcho", "Open", b"",
                        stream_options=StreamOptions(on_received=on_echo))
    stream = cntl.stream

    async def producer():
        for i in range(n_frames):
            sent_ns[str(i).encode()] = time.perf_counter_ns()
            frame = None
            if device_frames:
                # small integers: the echo's + 1 is exact in bf16
                frame = [jnp.full((8, 128), i % 100, jnp.bfloat16)]
            ok = await stream.write(f"frame-{i}".encode(),
                                    device_arrays=frame)
            assert ok, "stream write failed"

    f = fiber.spawn(producer)
    f.join(10)
    deadline = time.monotonic() + 5
    while len(got) < n_frames and time.monotonic() < deadline:
        time.sleep(0.01)
    print(f"sent {n_frames} frames, got {len(got)} echoes; "
          f"first={got[0]!r} last={got[-1]!r}")
    if device_frames:
        assert [int(np.asarray(a)[0, 0]) for a in arrays] == \
            [i % 100 + 1 for i in range(n_frames)], "device frames differ"
        print(f"{len(arrays)} device frames came back, each + 1, in order")
    print(f"frame rtt: p50={rec.latency_percentile(0.5):.1f}us "
          f"p99={rec.latency_percentile(0.99):.1f}us")
    stream.close()
    ch.close()
    if server is not None:
        server.stop()
        server.join(2)


if __name__ == "__main__":
    main(*sys.argv[1:])
