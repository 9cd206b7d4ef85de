"""Benchmark: echo RPC bandwidth + latency percentiles, harness-proof.

Two measured planes, mirroring how the reference publishes its numbers
(docs/cn/benchmark.md:104 — 2.3 GB/s max single-client large-payload
throughput over plain sockets; latency CDFs :126-199;
example/rdma_performance/client.cpp:261 prints QPS + bvar percentiles
at runtime):

1. **Headline — tpu_std echo over TCP loopback, 1MB payloads.** The
   framework's own data path (framing, IOBuf, socket write queue,
   fiber scheduler) over the kernel loopback, server in its own
   process, payload riding the attachment zero-copy — the direct
   analog of the reference's single-client big-payload benchmark
   environment (standalone server, pooled connections, attachment as
   the byte carrier like rdma_performance), so ``vs_baseline`` against
   2.3 GB/s is apples-to-apples. Small-payload (4B) p50/p99 is
   captured too (the reference's latency CDF shape).

2. **Device lane — ici:// with REAL byte movement.** Runs in a
   DEDICATED child probe (tools/device_probe.py) with its own budget
   (env BRPC_TPU_DEVICE_BUDGET_S, default 150s) OUTSIDE the TCP wall
   budget, armed with faulthandler + /proc forensics: the artifact
   carries either the 4B-4MB sweep (GB/s, p50/p99, lane_kind, platform,
   link floors) or a hang report naming the exact blocking
   frame/syscall. Partial state is mirrored to DEVICE_PROBE.json on
   disk as the probe runs. Per call the request is H2D-staged and the
   response materialized D2H (host<->HBM crossed twice). A device lane
   that errors makes the whole run exit non-zero.

Harness-proofing (every lesson from the round-2 rc=1 capture):
  * backend init RETRIES with backoff on exception inside the probe
    child (a transient UNAVAILABLE doesn't kill the run), and a HANG is
    watched from outside by the probe parent with forensics armed;
  * every phase streams one JSON line to STDERR the moment it
    completes, so a timeout still leaves parseable data;
  * the TCP phases fit a WALL BUDGET (default 100s, env
    BRPC_TPU_BENCH_BUDGET_S) that starts ticking only after the device
    probe returns: iteration counts derive from measured per-call
    cost, and points that don't fit are reported as skipped instead of
    hanging; the device probe has its own separate budget (see above);
  * a failure after the headline still prints the final JSON with
    whatever was captured (partial=true).

Prints ONE JSON line on stdout.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, __file__.rsplit("/", 1)[0])

BASELINE_GBPS = 2.3  # reference max single-client large-payload throughput
WALL_BUDGET_S = float(os.environ.get("BRPC_TPU_BENCH_BUDGET_S", "100"))
# the device probe runs OUTSIDE the wall budget (round-4 verdict: the
# flagship evidence must not be starved by the TCP phases' clock): one
# long child attempt with hang forensics, then the 4B-4MB device sweep.
# The TCP wall budget starts ticking only after the probe returns.
DEVICE_BUDGET_S = float(os.environ.get("BRPC_TPU_DEVICE_BUDGET_S", "150"))


def _progress(obj: dict) -> None:
    """Stream a progress record to stderr immediately (survives a
    harness timeout that would lose the final stdout line)."""
    print(json.dumps(obj), file=sys.stderr, flush=True)


class Deadline:
    def __init__(self, budget_s: float):
        self.t0 = time.perf_counter()
        self.budget = budget_s

    def remaining(self) -> float:
        return self.budget - (time.perf_counter() - self.t0)


def clamp(v, lo, hi):
    return max(lo, min(hi, v))


def spawn_tcp_server(deadline):
    """Echo server in its OWN process (own GIL), the reference's
    benchmark shape (standalone server + standalone client,
    docs/cn/benchmark.md 单机1). Returns (proc, port) or (None, None) —
    callers fall back to an in-process server so the headline still
    lands if spawning is broken on the harness."""
    base = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(base, "tools"))
    from spawn_util import spawn_port_server

    return spawn_port_server(
        [os.path.join(base, "tools", "bench_echo_server.py")],
        wall_s=min(30.0, max(5.0, deadline.remaining())))


_RAW_ECHO_SRC = r"""
import socket, sys
s = socket.socket(); s.bind(("127.0.0.1", 0)); s.listen(1)
print(f"PORT {s.getsockname()[1]}", flush=True)
c, _ = s.accept()
c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
buf = bytearray(1 << 20); mv = memoryview(buf)
while True:
    n = c.recv_into(mv)
    if not n: break
    c.sendall(mv[:n])
"""

# message-shaped calibration: 4-byte length framing, server ASSEMBLES
# the whole message before echoing — the memory/backpressure behavior an
# RPC framework is obliged to have (the stream blast above echoes each
# chunk while it is still cache-hot and never holds a message boundary;
# measured ~2.3 GB/s stream vs ~1.5 GB/s message on this box, so the
# stream figure is not an achievable bound for any RPC system here)
_RAW_MSG_ECHO_SRC = r"""
import socket, sys
s = socket.socket(); s.bind(("127.0.0.1", 0)); s.listen(1)
print(f"PORT {s.getsockname()[1]}", flush=True)
c, _ = s.accept()
c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
buf = bytearray()
mv = memoryview(bytearray(1 << 20))
while True:
    n = c.recv_into(mv)
    if not n: break
    buf += mv[:n]
    while len(buf) >= 4:
        ln = int.from_bytes(buf[:4], "big")
        if len(buf) < 4 + ln: break
        c.sendall(buf[:4 + ln])
        del buf[:4 + ln]
"""


def measure_raw_msg_loopback(n_msgs: int = 120) -> float:
    """The message-echo machine ceiling (see _RAW_MSG_ECHO_SRC):
    1MB length-prefixed frames, window of 8 in flight. GB/s or 0.0."""
    import subprocess

    proc = None
    c = None
    gbps = 0.0
    try:
        proc = subprocess.Popen([sys.executable, "-c", _RAW_MSG_ECHO_SRC],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL)
        port = int(proc.stdout.readline().split()[1])
        import socket as pysock

        c = pysock.create_connection(("127.0.0.1", port))
        c.setsockopt(pysock.IPPROTO_TCP, pysock.TCP_NODELAY, 1)
        c.settimeout(30.0)
        frame = (1 << 20).to_bytes(4, "big") + b"m" * (1 << 20)
        got = [0]

        def drain():
            b = bytearray(1 << 20)
            m = memoryview(b)
            try:
                while got[0] < n_msgs * len(frame):
                    n = c.recv_into(m)
                    if not n:
                        return
                    got[0] += n
            except OSError:
                return  # main thread closed the socket under us: done

        th = threading.Thread(target=drain, daemon=True)
        th.start()
        t0 = time.perf_counter()
        for i in range(n_msgs):
            c.sendall(frame)
            while got[0] < (i - 8) * len(frame):
                time.sleep(0.0003)
        deadline = time.perf_counter() + 20
        while got[0] < n_msgs * len(frame) and time.perf_counter() < deadline:
            time.sleep(0.001)
        dt = time.perf_counter() - t0
        if got[0] >= n_msgs * len(frame):
            gbps = n_msgs * (1 << 20) * 2 / dt / 1e9
    except Exception:
        pass
    finally:
        try:
            if c is not None:
                c.close()
        except Exception:
            pass
        try:
            if proc is not None:
                proc.terminate()
                proc.wait(5)
        except Exception:
            pass
    return gbps


def measure_raw_loopback(window_s: float = 2.5) -> float:
    """Machine calibration: a bare two-process socket echo (no
    framework) in the same shape as the headline, so the result can
    report how close the framework runs to this box's kernel loopback
    ceiling. Returns GB/s (echoed payload bytes x2 / wall, the same
    accounting as the headline) or 0.0 on any failure."""
    import subprocess

    proc = None
    c = None
    gbps = 0.0
    try:
        proc = subprocess.Popen([sys.executable, "-c", _RAW_ECHO_SRC],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL)
        port = int(proc.stdout.readline().split()[1])
        import socket as pysock

        c = pysock.create_connection(("127.0.0.1", port))
        c.setsockopt(pysock.IPPROTO_TCP, pysock.TCP_NODELAY, 1)
        # a dead child mid-window would leave sendall blocked forever on
        # full buffers; a timeout turns that into an exception
        c.settimeout(window_s + 5.0)
        payload = b"r" * (1 << 20)
        got = [0]
        stop = [False]

        def drain():
            buf = bytearray(1 << 20)
            mv = memoryview(buf)
            try:
                while not stop[0]:
                    n = c.recv_into(mv)
                    if not n:
                        return
                    got[0] += n
            except OSError:
                return  # main thread closed the socket under us: done

        th = threading.Thread(target=drain, daemon=True)
        th.start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < window_s:
            c.sendall(payload)
        dt = time.perf_counter() - t0
        stop[0] = True
        gbps = got[0] * 2 / dt / 1e9
    except Exception:
        pass
    finally:
        try:
            if c is not None:
                c.close()
        except Exception:
            pass
        try:
            if proc is not None:
                proc.terminate()
                proc.wait(5)
        except Exception:
            pass
    return gbps


def measure_native_delta() -> dict:
    """Before/after numbers for each C++-core piece that backs a Python
    fallback, so 'native is wired' is a measured claim: MB/s through the
    native path vs the pure-Python path on the same input."""
    out: dict = {}
    try:
        from brpc_tpu import native
        from brpc_tpu.butil import hash as bh

        if not native.available():
            return {"available": False}
        data = b"\xc3" * (1 << 20)
        # python hashing is ~9 MB/s: a 64KB slice keeps its side cheap
        small = data[:65536]

        def rate(fn, buf, reps) -> float:
            """Best-of-reps MB/s, with one warm call — both sides get
            the same treatment so the speedup factor is fair."""
            fn(buf)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                fn(buf)
                best = min(best, time.perf_counter() - t0)
            return len(buf) / best / 1e6

        out["crc32c_native_MBps"] = round(rate(bh.crc32c, data, 5), 1)
        out["crc32c_python_MBps"] = round(rate(bh.crc32c_py, small, 3), 1)
        out["murmur3_native_MBps"] = round(
            rate(bh.murmur3_x64_128, data, 5), 1)
        out["murmur3_python_MBps"] = round(
            rate(bh.murmur3_x64_128_py, small, 3), 1)
        from brpc_tpu import native
        from brpc_tpu.butil import snappy_codec as sz

        comp = b"compressible wire payload " * 40330  # ~1MB
        out["snappy_native_MBps"] = round(
            rate(native.snappy_compress, comp, 5), 1)
        out["snappy_python_MBps"] = round(
            rate(sz.compress, comp[:65536], 3), 1)
        out["available"] = True
    except Exception as e:  # noqa: BLE001 - diagnostics only
        out["error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def measure_wake_under_load(ch, n: int = 200) -> dict:
    """Fiber spawn->first-step latency while RPC load saturates the
    core (the wake path's accountability number; round 3 measured
    p50 ~1ms / p99 ~25ms here because every call paid 3-5 wakes that
    convoyed — the inline rework removed them from the data path).

    The LOAD RATE ships next to the percentiles: the probe's tail is
    GIL/timeslice contention against the hammer threads, so a faster
    RPC path makes the load heavier and the tail longer — comparing
    percentiles across rounds without the load figure misreads a
    faster data path as a slower wake path (round 5's lanes roughly
    doubled the hammer throughput and the p99 moved with it)."""
    from brpc_tpu.fiber import global_control

    ctl = global_control()
    stop = [False]
    calls = [0, 0]

    def hammer(i):
        while not stop[0]:
            ch.call_sync("Bench", "Echo", b"w")
            calls[i] += 1

    ths = [threading.Thread(target=hammer, args=(i,), daemon=True)
           for i in range(2)]
    for t in ths:
        t.start()
    time.sleep(0.2)
    lat = []
    t_load0 = time.perf_counter()
    try:
        for _ in range(n):
            t0 = time.perf_counter_ns()
            box = {}

            def work():
                box["dt"] = (time.perf_counter_ns() - t0) / 1e3

            f = ctl.spawn(work)
            if f.join(5) and "dt" in box:
                lat.append(box["dt"])
            time.sleep(0.002)
    finally:
        load_dt = time.perf_counter() - t_load0
        stop[0] = True
    for t in ths:
        t.join(10)
    if not lat:
        return {}
    lat.sort()
    return {
        "fiber_wake_under_load_p50_us": round(lat[len(lat) // 2], 1),
        "fiber_wake_under_load_p99_us": round(lat[int(len(lat) * 0.99)], 1),
        "fiber_wake_load_qps": round(sum(calls) / max(load_dt, 1e-9), 1),
    }


def make_runner(ch, deadline, np):
    """Callback-driven pipelined runner over `ch`; returns wall seconds.

    Host payloads ride the ATTACHMENT (zero-copy in and out of the
    framing on both sides), the reference's large-payload benchmark
    shape — rdma_performance moves its bytes in
    cntl.request_attachment, not the serialized pb. The next call is
    issued FROM the completion callback (the reference's async client
    loop): the whole client side runs on the event thread with no
    issue-thread/semaphore GIL ping-pong — measured worth ~20% on a
    single-core box. ``threads`` is accepted for signature compatibility
    and ignored (issue threads only added GIL contention here)."""
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc import Controller

    from pipeline_runner import run_pipelined

    def run_batch(iters: int, inflight: int, rec, payload: bytes = b"",
                  threads: int = 1) -> float:
        expect = len(payload)

        def issue(on_done) -> None:
            cntl = None
            if payload:
                cntl = Controller()
                att = IOBuf()
                att.append(payload)  # zero-copy wrap (>=16KB)
                cntl.request_attachment = att
            t_start = time.perf_counter_ns()

            def _done(c) -> None:
                try:
                    if c.failed():
                        raise RuntimeError(c.error_text)
                    if c.response_attachment.size != expect:
                        raise RuntimeError("payload size mismatch")
                    if rec is not None:
                        rec.record((time.perf_counter_ns() - t_start) / 1e3)
                except BaseException as e:  # noqa: BLE001
                    on_done(e)
                else:
                    on_done(None)

            ch.call("Bench", "Echo", b"", cntl=cntl, done=_done)

        return run_pipelined(iters, inflight, issue,
                             max(20.0, deadline.remaining() + 20.0))

    return run_batch


def main() -> None:
    import numpy as np

    from brpc_tpu.bvar.latency_recorder import LatencyRecorder
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                              Service)

    from brpc_tpu import native

    from brpc_tpu.native import fastcore

    result: dict = {
        "metric": "echo_rpc_1mb_bandwidth_tcp_loopback",
        "value": 0.0, "unit": "GB/s", "vs_baseline": 0.0,
        "partial": False, "device_lane": {},
        # which C++ core pieces are load-bearing on the per-call hot
        # path (src/fastcore.cc binds them via the CPython C API; the
        # ctypes lane covers bulk codecs)
        "native": {"available": native.available(),
                   "fastcore": fastcore.available(),
                   "wired": [
                       "pack_frame (tpu_std request+response framing)",
                       "parse_head (tpu_std frame probe)",
                       "scan_frames (per-call loop: frame cut + meta "
                       "decode in one C pass)",
                       "serve_scan (echo-class methods served "
                       "end-to-end in C)",
                       "pluck_scan (client sync receive loop: poll + "
                       "recv + frame scan in one C call per slice)",
                       "serve_drain (server per-event loop: recv + cut "
                       "+ match + response build in one C call)",
                       "http_parse_request / http_parse_resp_head "
                       "(HTTP/1.x head parse, httpparse.cc)",
                       "respool.cc Pool (correlation ids + socket ids)",
                       "queues.cc Mpsc writer-retire (socket write queue)",
                       "crc32c", "murmur3 (c_murmurhash LB)",
                       "trpc_scan (flag tpu_std_batch_parse)"],
                   "delta": measure_native_delta()},
    }

    def make_server():
        server = Server(ServerOptions(enable_builtin_services=False))
        svc = Service("Bench")

        @svc.method()
        def Echo(cntl, request):
            # device payloads were *moved* to this server's recv device
            # by the lane (H2D stage or D2D copy), not handed off; host
            # payloads ride the attachment and echo back zero-copy
            # (the reference's rdma_performance shape)
            if cntl.request_device_arrays:
                cntl.response_device_arrays = cntl.request_device_arrays
            if cntl.request_attachment.size:
                cntl.response_attachment = cntl.request_attachment
            return bytes(request)

        server.add_service(svc)
        return server

    tcp_server = None
    server_proc = None

    # ---------------- phase 0: preflight + DEDICATED device probe
    # (four rounds of device-lane evidence died undiagnosed — the probe
    # now runs in its own child with its own budget, armed with
    # faulthandler + /proc forensics, so the artifact carries either
    # real numbers or the exact blocking frame/syscall. ONE PROCESS
    # HOLDS THE CHIP: this bench process never imports jax, the probe
    # child is the chip's one holder, and every later child is a
    # host-path tool pinned to the CPU below.)
    base = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(base, "tools"))
    try:
        from preflight import run_preflight
        result["preflight"] = run_preflight()
        _progress({"progress": "preflight", **result["preflight"]})
    except Exception as e:  # noqa: BLE001 - evidence, not control flow
        result["preflight"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    try:
        from device_probe import run_probe
        lane = run_probe(DEVICE_BUDGET_S,
                         out_path=os.path.join(base, "DEVICE_PROBE.json"),
                         progress=_progress)
    except BaseException as e:  # noqa: BLE001 - salvage: TCP still runs
        lane = {"error": f"probe driver failed: {type(e).__name__}: {e}"[:400]}
    result["device_lane"] = lane
    if "lane_error" in lane:
        # healthy bring-up, failed sweep: keep the bring-up evidence
        # but the run is partial like every other failure path
        result["partial"] = True
        _progress({"progress": "error", "phase": "device_lane",
                   "error": lane["lane_error"]})
    if "error" in lane:
        lane["preflight_chip_holders"] = \
            result["preflight"].get("chip_holders", [])
        result["partial"] = True
        _progress({"progress": "error", "phase": "device_probe",
                   "error": lane["error"]})
    # every child from here on is a host-path tool; the ones that import
    # jax (serving/fabric smokes, echo servers) must not reach for the
    # chip, so the whole remaining process tree is pinned to the CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    # the TCP wall budget starts AFTER the probe: the device lane can
    # no longer starve the host-path phases (or vice versa)
    deadline = Deadline(WALL_BUDGET_S)

    # ---------------- phase 1: TCP loopback headline (framework path)
    try:
        server_proc, port = spawn_tcp_server(deadline)
        if port is None:
            # harness can't spawn: in-process fallback (shares the GIL
            # with the client — reported so the number is interpretable)
            tcp_server = make_server()
            tcp_ep = tcp_server.start("tcp://127.0.0.1:0")
            port = tcp_ep.port
        result["server_process"] = ("subprocess" if server_proc is not None
                                    else "in-process")
        # small-payload latency FIRST, on a quiet box (the reference
        # measures its latency CDFs in dedicated runs; sampling after
        # the 1MB blast would measure a cache-hot-box tax instead of
        # the path). One multiplexed connection, sequential sync echoes
        # — echo_c++'s client shape.
        lat_ch = Channel(f"tcp://127.0.0.1:{port}",
                         ChannelOptions(timeout_ms=5000))
        for _ in range(200):                     # warm the connection
            if deadline.remaining() < 8.0:
                break
            lat_ch.call_sync("Bench", "Echo", b"ping")
        rec = LatencyRecorder()
        failures = 0
        samples = 0
        best_us = None
        # >=5k samples (round-4 verdict: 600 made the tail a
        # scheduling-noise lottery); the budget guard still caps a
        # pathologically slow path
        for _ in range(5000):
            if deadline.remaining() < 45.0:
                break
            t0 = time.perf_counter_ns()
            cl = lat_ch.call_sync("Bench", "Echo", b"ping")
            if cl.failed():
                failures += 1
                if failures >= 10:
                    break            # dead server: don't grind the budget
            else:
                samples += 1
                us = (time.perf_counter_ns() - t0) / 1e3
                rec.record(us)
                if best_us is None or us < best_us:
                    best_us = us
        lat_ch.close()
        if samples:
            result["small_rpc_samples"] = samples
            result["small_rpc_p50_us"] = round(rec.latency_percentile(0.5), 1)
            result["small_rpc_p99_us"] = round(rec.latency_percentile(0.99), 1)
            # noise-robust floor: one bad scheduling draw on a shared
            # box inflates percentiles; the min is the machine-honest
            # "what the path costs" figure
            result["small_rpc_min_us"] = round(best_us, 1)
        else:
            # an empty recorder would report a record-looking 0.0
            result["partial"] = True
            result["small_rpc_error"] = \
                f"no successful latency samples ({failures} failures)"
        _progress({"progress": "tcp_small",
                   "p50_us": result.get("small_rpc_p50_us"),
                   "p99_us": result.get("small_rpc_p99_us"),
                   **({"error": result["small_rpc_error"]}
                      if "small_rpc_error" in result else {})})
        # ------------- StreamingRPC one-way throughput (the reference's
        # streaming_echo_c++ north-star config, BASELINE.md): stream
        # 256KB frames through a credit-windowed Stream to the server's
        # sink, which answers with one done-frame when every byte
        # arrived — flow control live on the wire, not a socket blast
        if deadline.remaining() > 10.0:
            try:
                from brpc_tpu import fiber as _fiber
                from brpc_tpu.rpc.stream import StreamOptions
                frame = b"\x5a" * (256 << 10)
                n_frames = 256                    # 64MB one way

                def stream_pass(count):
                    """One complete open -> push -> ack -> close cycle;
                    returns (seconds, reply|None). A SEPARATE warm cycle
                    keeps the measured window honest: sharing one stream
                    would leave up to a credit window of warm frames in
                    flight at t0 (the sink acks once, so the measured dt
                    would silently include delivering them)."""
                    done_evt = threading.Event()
                    got_box = {}

                    def on_done(stream, msg):
                        got_box["reply"] = msg.payload.to_bytes()
                        done_evt.set()

                    sch = Channel(f"tcp://127.0.0.1:{port}",
                                  ChannelOptions(timeout_ms=30000))
                    stream = None
                    try:
                        scntl = sch.call_sync(
                            "Bench", "StreamSink",
                            str(count * len(frame)).encode(),
                            stream_options=StreamOptions(
                                on_received=on_done))
                        stream = scntl.stream
                        if scntl.failed() or stream is None:
                            raise RuntimeError(
                                f"stream open failed: {scntl.error_text}")
                        t0 = time.perf_counter()

                        async def producer():
                            for _ in range(count):
                                if not await stream.write(frame):
                                    break

                        _fiber.spawn(producer).join(
                            min(60.0, deadline.remaining()))
                        ok = done_evt.wait(min(20.0, deadline.remaining()))
                        return (time.perf_counter() - t0,
                                got_box.get("reply") if ok else None)
                    finally:
                        # every exit tears down: a failed open must not
                        # leak the pool-registered client Stream or the
                        # channel for the rest of the run
                        if stream is not None:
                            stream.close()
                        sch.close()

                # full-size warm pass: measured on this box the stream
                # path reaches steady state only after ~64MB (delivery
                # cadence + block recycling); a short warm under-reports
                # the steady figure by ~30%
                stream_pass(n_frames)
                dt, reply = stream_pass(n_frames)
                if reply is not None:
                    result["streaming_GBps"] = round(
                        n_frames * len(frame) / dt / 1e9, 3)
                    result["streaming_frames"] = n_frames
                    _progress({"progress": "streaming",
                               "GBps": result["streaming_GBps"],
                               "reply": reply.decode("ascii", "replace")})
                else:
                    result["streaming_error"] = \
                        f"done-frame not received (dt={dt:.1f}s)"
                    result["partial"] = True
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["streaming_error"] = f"{type(e).__name__}: {e}"[:200]
                result["partial"] = True
                _progress({"progress": "error", "phase": "streaming",
                           "error": result["streaming_error"]})
        # pooled connections: the reference's headline shape
        # (multi-connection pooled client, docs/cn/benchmark.md:104).
        # Inflight 8: re-measured sweet spot with the round-5 lanes
        # (matches the sweep's 16MB in-flight-bytes window; 1.81-1.86
        # vs 1.70-1.81 at depth 6 across two tuning rounds) — deeper
        # pipelines only grow the cache working set and regress
        ch = Channel(f"tcp://127.0.0.1:{port}",
                     ChannelOptions(timeout_ms=120000,
                                    connection_type="pooled"))
        run = make_runner(ch, deadline, np)
        payload = b"\xa5" * (1 << 20)
        # warm with the MEASUREMENT shape (pooled sockets get created
        # per inflight slot; a single-threaded warm leaves half the
        # pool cold and the first measured batch pays connection setup)
        warm_dt = run(24, 8, None, payload=payload, threads=2)
        per_call = warm_dt / 24
        tcp_budget = min(deadline.remaining() * 0.35, 30.0)
        iters = int(clamp(tcp_budget / 2 / max(per_call, 1e-9), 16, 400))
        rec = LatencyRecorder()
        gbps = 0.0
        for b in range(2):
            if b > 0 and deadline.remaining() < iters * per_call * 1.2:
                break
            dt = run(iters, 8, rec, payload=payload, threads=2)
            gbps = max(gbps, iters * (1 << 20) * 2 / 1e9 / dt)
        # machine calibrations, both reported so vs_baseline has context
        # (the reference's 2.3 GB/s was multi-core + 10GbE with NIC
        # offload; this box's kernel loopback is the real ceiling):
        #   stream — boundary-less chunk echo (the old calibration; an
        #            upper bound NO message-framed system can reach here,
        #            since each chunk echoes while cache-hot)
        #   msg    — length-framed assemble-then-echo, the same
        #            obligation an RPC framework has; efficiency_vs_raw
        #            is measured against THIS like-for-like ceiling
        raw_stream = (measure_raw_loopback(min(2.5, deadline.remaining() * 0.1))
                      if deadline.remaining() > 5.0 else 0.0)
        raw_msg = (measure_raw_msg_loopback()
                   if deadline.remaining() > 5.0 else 0.0)
        result.update({
            "value": round(gbps, 3),
            "vs_baseline": round(gbps / BASELINE_GBPS, 3),
            "loopback_raw_stream_GBps": round(raw_stream, 3),
            "loopback_raw_msg_GBps": round(raw_msg, 3),
            "efficiency_vs_raw": round(gbps / raw_msg, 3) if raw_msg else None,
            "efficiency_vs_stream_raw": round(gbps / raw_stream, 3)
            if raw_stream else None,
            # headline: StreamingRPC one-way throughput as a fraction
            # of the box's boundary-less raw stream ceiling (the
            # credit-window + frame path's efficiency figure)
            "streaming_efficiency": round(
                result["streaming_GBps"] / raw_stream, 3)
            if raw_stream and result.get("streaming_GBps") else None,
            "avg_us": round(rec.latency(), 1),
            "p50_us": round(rec.latency_percentile(0.5), 1),
            "p99_us": round(rec.latency_percentile(0.99), 1),
            "p999_us": round(rec.latency_percentile(0.999), 1),
        })
        _progress({"progress": "tcp_headline", "iters": iters,
                   "GBps": result["value"],
                   "p99_us": result["p99_us"]})
        # long-tail CDF (the reference's famous latency benchmark,
        # docs/cn/benchmark.md:126-199): 1-in-100 calls hit a 50ms
        # handler on a SEPARATE connection while the normal stream runs
        # sequentially — the normal calls' percentiles must stay at the
        # quiet-path level (inline processing + worker hops keep slow
        # handlers off the fast connection's dispatch path)
        slow_ch = fast_ch = None
        try:
            if deadline.remaining() > 10.0:
                slow_ch = Channel(f"tcp://127.0.0.1:{port}",
                                  ChannelOptions(timeout_ms=5000))
                fast_ch = Channel(f"tcp://127.0.0.1:{port}",
                                  ChannelOptions(timeout_ms=5000,
                                                 share_connections=False))
                # warm: connection setup must not pollute the tail
                # percentiles this section exists to measure
                for _ in range(20):
                    fast_ch.call_sync("Bench", "Echo", b"warm")
                inflight_slow = []
                rec2 = LatencyRecorder()
                n_ok = 0
                lt_failures = 0
                for i in range(400):
                    if deadline.remaining() < 6.0 or lt_failures >= 10:
                        break
                    if i % 100 == 0:
                        inflight_slow.append(
                            slow_ch.call("Bench", "Slow", b"tail"))
                    t0 = time.perf_counter_ns()
                    cl = fast_ch.call_sync("Bench", "Echo", b"ping")
                    if cl.failed():
                        lt_failures += 1
                    else:
                        n_ok += 1
                        rec2.record((time.perf_counter_ns() - t0) / 1e3)
                for c in inflight_slow:
                    c.join(2)
                if n_ok:
                    result["longtail_normal_p50_us"] = round(
                        rec2.latency_percentile(0.5), 1)
                    result["longtail_normal_p99_us"] = round(
                        rec2.latency_percentile(0.99), 1)
                    _progress({"progress": "longtail",
                               "p50_us": result["longtail_normal_p50_us"],
                               "p99_us": result["longtail_normal_p99_us"]})
                else:
                    result["longtail_error"] = \
                        f"no successful samples ({lt_failures} failures)"
        except Exception as e:  # noqa: BLE001 - diagnostics only
            result["longtail_error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            for c in (slow_ch, fast_ch):
                if c is not None:
                    try:
                        c.close()
                    except Exception:
                        pass
        # scheduler wake-to-run latency under load — the regression gate
        # for the wake path. Since the inline-processing rework the RPC
        # data path itself needs ~zero wakes, so this is a DEDICATED
        # probe: spawn->first-step latency while CPU-bound RPC load runs
        # (harsher than sampling the bench's own wakes, and always
        # present in the artifact). The residual p99 on a 1-core box is
        # OS timeslicing of the load threads, not framework queueing —
        # the round-3 convoy (p50 ~1ms under load) is what this guards.
        try:
            wake = measure_wake_under_load(ch)
            if wake:
                result.update(wake)
                _progress({"progress": "fiber_wake",
                           "p50_us": wake["fiber_wake_under_load_p50_us"],
                           "p99_us": wake["fiber_wake_under_load_p99_us"]})
            else:
                result["fiber_wake_error"] = \
                    "probe produced zero samples (core saturated)"
        except Exception as e:  # noqa: BLE001 - diagnostics only
            result["fiber_wake_error"] = f"{type(e).__name__}: {e}"[:200]
        # the 4B-4MB TCP sweep (the reference's qps-vs-request-size
        # curves, docs/cn/benchmark.md:92-156) — adaptive iteration
        # counts, one stderr line per point, skipped points reported
        result["tcp_sweep"] = {}
        sweep_sizes = [4, 64, 1024, 16384, 262144, 1 << 20, 4 << 20]
        sweep_budget = deadline.remaining() * 0.5
        for idx, size in enumerate(sweep_sizes):
            if deadline.remaining() < 6.0:
                result["tcp_sweep"][str(size)] = {"skipped": "wall budget"}
                result["partial"] = True
                _progress({"progress": "tcp_sweep_skip", "size": size})
                continue
            pay = b"s" * size
            rec = LatencyRecorder()
            # window capped by in-flight BYTES: 8 x 4MB payloads keep
            # 64MB of blocks live and thrash every cache level
            # (measured: 4MB point 1.22 GB/s at depth 8 vs 1.52 at 4)
            win = max(2, min(8, (16 << 20) // max(size, 1)))
            warm_dt = run(4, win, None, payload=pay)
            point_budget = max(1.0, sweep_budget / len(sweep_sizes))
            it = int(clamp(point_budget / max(warm_dt / 4, 1e-9), 8, 600))
            dt = run(it, win, rec, payload=pay)
            pt = {
                "qps": round(it / dt, 1),
                "GBps": round(it * size * 2 / dt / 1e9, 4),
                "p50_us": round(rec.latency_percentile(0.5), 1),
                "p99_us": round(rec.latency_percentile(0.99), 1),
                "iters": it,
            }
            result["tcp_sweep"][str(size)] = pt
            _progress({"progress": "tcp_sweep_point", "size": size, **pt})
        # concurrency scaling (the reference's qps-vs-threads/clients
        # curves, docs/cn/benchmark.md:92-156): N clients, each a
        # thread driving its OWN single connection with sequential
        # sync 4B echoes — contention visible as sub-linear qps and a
        # widening p99 — plus the 1MB pooled shape vs pipeline depth
        result["concurrency_sweep"] = {"clients_4B": {}, "inflight_1MB": {}}
        for nclients in (1, 2, 4, 8):
            if deadline.remaining() < 8.0:
                result["concurrency_sweep"]["clients_4B"][str(nclients)] = \
                    {"skipped": "wall budget"}
                result["partial"] = True
                continue
            chs = [Channel(f"tcp://127.0.0.1:{port}",
                           ChannelOptions(timeout_ms=5000,
                                          share_connections=False))
                   for _ in range(nclients)]
            for c in chs:
                for _ in range(20):
                    c.call_sync("Bench", "Echo", b"w")
            window = min(1.5, max(0.5, deadline.remaining() * 0.04))
            stop_at = time.perf_counter() + window
            lats: list = [[] for _ in range(nclients)]
            counts = [0] * nclients

            def client_loop(i):
                c = chs[i]
                my = lats[i]
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter_ns()
                    if not c.call_sync("Bench", "Echo", b"c").failed():
                        counts[i] += 1
                        my.append((time.perf_counter_ns() - t0) / 1e3)

            ths = [threading.Thread(target=client_loop, args=(i,))
                   for i in range(nclients)]
            t0 = time.perf_counter()
            for t in ths:
                t.start()
            for t in ths:
                t.join(window + 10)
            dt = time.perf_counter() - t0
            merged = sorted(x for ls in lats for x in ls)
            for c in chs:
                c.close()
            if merged:
                pt = {"qps": round(sum(counts) / dt, 1),
                      "p50_us": round(merged[len(merged) // 2], 1),
                      "p99_us": round(merged[int(len(merged) * 0.99)], 1),
                      "calls": sum(counts)}
            else:
                # an all-failed window must be a visible data point,
                # not a silent hole in the artifact
                pt = {"failed": "no successful calls in window"}
                result["partial"] = True
            result["concurrency_sweep"]["clients_4B"][str(nclients)] = pt
            _progress({"progress": "concurrency_point",
                       "clients": nclients, **pt})
        # headline: 8-client scaling factor over 1 client (flat scaling
        # = a serialized hot path; the dispatcher-wake/batching work is
        # accountable for this number) + the absolute 8-client qps
        c4 = result["concurrency_sweep"]["clients_4B"]
        q1 = (c4.get("1") or {}).get("qps")
        q8 = (c4.get("8") or {}).get("qps")
        if q1 and q8:
            result["concurrency_scaling_8c"] = round(q8 / q1, 2)
            result["qps_8c_4B"] = q8
        for depth in (1, 2, 4, 8):
            if deadline.remaining() < 8.0:
                result["concurrency_sweep"]["inflight_1MB"][str(depth)] = \
                    {"skipped": "wall budget"}
                result["partial"] = True
                continue
            rec = LatencyRecorder()
            it = int(clamp(deadline.remaining() * 0.04
                           / max(per_call, 1e-9), 8, 60))
            dt = run(it, depth, rec, payload=payload)
            pt = {"GBps": round(it * (1 << 20) * 2 / dt / 1e9, 3),
                  "p99_us": round(rec.latency_percentile(0.99), 1),
                  "iters": it}
            result["concurrency_sweep"]["inflight_1MB"][str(depth)] = pt
            _progress({"progress": "inflight_point", "depth": depth, **pt})
        # ---------------- sharded lane (shard-group serving): the
        # SO_REUSEPORT worker-process escape from the one-core GIL
        # ceiling the clients_4B sweep exposes. Measures the
        # Python-dispatch method (PyEcho — the GIL-bound framework
        # path; the native-C echo saturates beyond what same-box
        # Python clients can generate) against the SAME multi-process
        # pipelined client load twice: the single-process server
        # above, then an N-shard group. Headline keys: qps_sharded_4B
        # and shard_scaling (sharded / single at equal client count).
        cores = os.cpu_count() or 1
        if cores < 4:
            result["sharded"] = {"skipped": f"only {cores} cores"}
        elif deadline.remaining() < 20.0:
            result["sharded"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            try:
                from qps_client import drive_multiproc
                from spawn_util import spawn_announcing_server
                nsh = max(4, min(8, cores // 3))
                ncl = max(4, min(8, cores // 3))
                win = min(2.0, max(1.0, deadline.remaining() * 0.05))
                single_mp = drive_multiproc(port, nprocs=ncl,
                                            seconds=win, conns=2,
                                            inflight=8, method="PyEcho")
                sproc, got = spawn_announcing_server(
                    [os.path.join(base, "tools", "shard_server.py"),
                     "--shards", str(nsh)], wall_s=30.0,
                    keys=("ADMIN", "PORT"))
                if got is None:
                    raise RuntimeError("shard server spawn failed")
                try:
                    sharded = drive_multiproc(got["PORT"], nprocs=ncl,
                                              seconds=win, conns=2,
                                              inflight=8,
                                              method="PyEcho")
                finally:
                    try:
                        sproc.terminate()
                        sproc.wait(10)
                    except Exception:
                        pass
                lane = {
                    "shards": nsh, "client_procs": ncl,
                    "window_s": win,
                    "qps_single_mp": single_mp["qps"],
                    "qps_sharded": sharded["qps"],
                    "client_failures": single_mp["failures"]
                    + sharded["failures"],
                    "dead_workers": single_mp["dead_workers"]
                    + sharded["dead_workers"],
                }
                result["sharded"] = lane
                result["shard_count"] = nsh
                result["qps_sharded_4B"] = sharded["qps"]
                if single_mp["qps"]:
                    result["shard_scaling"] = round(
                        sharded["qps"] / single_mp["qps"], 2)
                _progress({"progress": "sharded_lane", **lane,
                           "shard_scaling": result.get("shard_scaling")})
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["sharded"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
                result["partial"] = True
                _progress({"progress": "error", "phase": "sharded",
                           "error": result["sharded"]["error"]})
        # ---- flight-recorder lane (ISSUE 6): the measurement floor
        # for every subsequent perf PR — continuous-profiler overhead
        # (headline profiler_overhead_pct, acceptance <=5%) and the
        # resident cost of an idle connection (bytes_per_idle_conn
        # from a >=5k-conn hold, the connection-diet PR's baseline).
        # Subprocesses: a wedged lane must not take the bench down.
        if deadline.remaining() < 30.0:
            result["flight"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            import subprocess as _sp
            lane: dict = {}
            try:
                p = _sp.run(
                    [sys.executable,
                     os.path.join(base, "tools", "flight_smoke.py")],
                    capture_output=True, text=True, timeout=180)
                rep = json.loads(p.stdout.strip().splitlines()[-1])
                lane["single"] = rep
                if "profiler_overhead_pct" in rep:
                    result["profiler_overhead_pct"] = \
                        rep["profiler_overhead_pct"]
            except Exception as e:  # noqa: BLE001 - diagnostics only
                lane["error"] = f"{type(e).__name__}: {e}"[:200]
                result["partial"] = True
            if deadline.remaining() > 60.0 and (os.cpu_count() or 1) >= 4:
                try:
                    p = _sp.run(
                        [sys.executable,
                         os.path.join(base, "tools", "flight_smoke.py"),
                         "--shards", "8", "--seconds", "2"],
                        capture_output=True, text=True, timeout=180)
                    lane["sharded"] = json.loads(
                        p.stdout.strip().splitlines()[-1])
                except Exception as e:  # noqa: BLE001
                    lane["sharded"] = {
                        "error": f"{type(e).__name__}: {e}"[:200]}
            if deadline.remaining() > 45.0:
                try:
                    p = _sp.run(
                        [sys.executable,
                         os.path.join(base, "tools", "soak.py"),
                         "--idle-conns", "5000", "--settle", "3"],
                        capture_output=True, text=True, timeout=180)
                    rep = json.loads(p.stdout.strip().splitlines()[-1])
                    lane["idle_conns"] = rep
                    if rep.get("ok"):
                        result["bytes_per_idle_conn"] = \
                            rep["bytes_per_idle_conn"]
                except Exception as e:  # noqa: BLE001
                    lane["idle_conns"] = {
                        "error": f"{type(e).__name__}: {e}"[:200]}
            else:
                lane["idle_conns"] = {"skipped": "wall budget"}
                result["partial"] = True
            result["flight"] = lane
            _progress({"progress": "flight_lane",
                       "profiler_overhead_pct":
                       result.get("profiler_overhead_pct"),
                       "bytes_per_idle_conn":
                       result.get("bytes_per_idle_conn"),
                       "sharded_attribution":
                       lane.get("sharded", {}).get("attribution_ratio")})
        # ---- cluster lane (ISSUE 7): the client-side fabric floor.
        # Multi-process pipelined load through CLUSTER channels at two
        # local backends — headline cluster_qps seeds the key the
        # roadmap's fabric item (LALB/hedging) will gate on, and
        # backend_stats_overhead_pct prices the per-backend stat cells
        # (BRPC_TPU_BACKEND_STATS=0 in the off window — the env rides
        # into the qps_client worker processes).
        if deadline.remaining() < 20.0:
            result["cluster"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            try:
                from qps_client import drive_multiproc
                from spawn_util import spawn_port_server
                backends = []
                cports = []
                for _ in range(2):
                    bproc, bport = spawn_port_server(
                        [os.path.join(base, "tools",
                                      "bench_echo_server.py")],
                        wall_s=20.0)
                    if bport is None:
                        raise RuntimeError("cluster backend spawn failed")
                    backends.append(bproc)
                    cports.append(bport)
                try:
                    plist = ",".join(str(p) for p in cports)
                    ncl = max(2, min(6, (os.cpu_count() or 2) // 4))
                    win = min(2.0, max(1.0, deadline.remaining() * 0.04))
                    saved = os.environ.pop("BRPC_TPU_BACKEND_STATS", None)
                    try:
                        on_w = drive_multiproc(plist, nprocs=ncl,
                                               seconds=win, conns=2,
                                               inflight=8,
                                               method="PyEcho")
                        os.environ["BRPC_TPU_BACKEND_STATS"] = "0"
                        off_w = drive_multiproc(plist, nprocs=ncl,
                                                seconds=win, conns=2,
                                                inflight=8,
                                                method="PyEcho")
                    finally:
                        # a raising window must not leave the rest of
                        # the bench (or the operator's explicit value)
                        # stuck with cells forced off
                        if saved is None:
                            os.environ.pop("BRPC_TPU_BACKEND_STATS",
                                           None)
                        else:
                            os.environ["BRPC_TPU_BACKEND_STATS"] = saved
                    lane = {"backends": 2, "client_procs": ncl,
                            "window_s": win,
                            "qps_cells_on": on_w["qps"],
                            "qps_cells_off": off_w["qps"],
                            "client_failures": on_w["failures"]
                            + off_w["failures"],
                            "dead_workers": on_w["dead_workers"]
                            + off_w["dead_workers"]}
                    result["cluster"] = lane
                    result["cluster_qps"] = on_w["qps"]
                    if off_w["qps"]:
                        result["backend_stats_overhead_pct"] = round(
                            max(0.0, (1.0 - on_w["qps"] / off_w["qps"])
                                * 100), 2)
                    _progress({"progress": "cluster_lane", **lane,
                               "backend_stats_overhead_pct":
                               result.get("backend_stats_overhead_pct")})
                finally:
                    for bproc in backends:
                        try:
                            bproc.terminate()
                            bproc.wait(5)
                        except Exception:
                            pass
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["cluster"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
                result["partial"] = True
                _progress({"progress": "error", "phase": "cluster",
                           "error": result["cluster"]["error"]})
        # ---- fabric storm lane (ISSUE 10 + 14): the overload-control
        # loop under fault. Seeded kill/stall/outage/recover storm over
        # 3 nodes behind budget-hedging ClusterChannels, with the
        # corpus-fed PRESS tail driving >= 2x capacity so the DAGOR
        # priority-admission loop engages — headline keys
        # fault_goodput_ratio (fault-window goodput vs fault-free),
        # fault_p99_ms, priority_goodput_hi_ratio (converged top-class
        # goodput under press) and admission_overhead_pct (calm-path
        # layer cost with no priorities configured, pair-median
        # alternating windows, acceptance <= 5%). Subprocesses so a
        # wedged storm cannot take the bench down.
        if deadline.remaining() < 25.0:
            result["fabric"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            import subprocess as _sp
            try:
                p = _sp.run(
                    [sys.executable,
                     os.path.join(base, "tools", "fabric_smoke.py"),
                     "--bench", "--corpus", "auto"],
                    capture_output=True, text=True, timeout=180)
                rep = json.loads(p.stdout.strip().splitlines()[-1])
                lane = {"fault_goodput_ratio": rep.get(
                            "fault_goodput_ratio"),
                        "fault_p99_ms": rep.get("fault_p99_ms"),
                        "outage_amplification": rep.get(
                            "outage_amplification"),
                        "hedges_armed": rep.get("hedges_armed"),
                        "hedges_past_budget": rep.get(
                            "hedges_past_budget"),
                        "priority_goodput_hi_ratio": rep.get(
                            "priority_goodput_hi_ratio"),
                        "press_client_shed_frac": rep.get(
                            "press_client_shed_frac"),
                        "press_priority_sheds": rep.get(
                            "press_priority_sheds"),
                        "problems": rep.get("problems")}
                result["fabric"] = lane
                if rep.get("fault_goodput_ratio") is not None:
                    result["fault_goodput_ratio"] = \
                        rep["fault_goodput_ratio"]
                if rep.get("fault_p99_ms") is not None:
                    result["fault_p99_ms"] = rep["fault_p99_ms"]
                if rep.get("priority_goodput_hi_ratio") is not None:
                    result["priority_goodput_hi_ratio"] = \
                        rep["priority_goodput_hi_ratio"]
                _progress({"progress": "fabric_lane", **lane})
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["fabric"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
                result["partial"] = True
                _progress({"progress": "error", "phase": "fabric",
                           "error": result["fabric"]["error"]})
            # admission-layer calm-path cost (prices what every PR 10
            # server pays for the ISSUE 14 layer it isn't using)
            if deadline.remaining() >= 20.0:
                try:
                    p = _sp.run(
                        [sys.executable,
                         os.path.join(base, "tools",
                                      "fabric_smoke.py"), "--overhead"],
                        capture_output=True, text=True, timeout=180)
                    rep = json.loads(p.stdout.strip().splitlines()[-1])
                    if rep.get("admission_overhead_pct") is not None:
                        result["admission_overhead_pct"] = \
                            rep["admission_overhead_pct"]
                        result["fabric"]["admission_overhead_pct"] = \
                            rep["admission_overhead_pct"]
                    _progress({"progress": "fabric_admission_overhead",
                               "admission_overhead_pct":
                               result.get("admission_overhead_pct")})
                except Exception as e:  # noqa: BLE001 - diagnostics
                    result["fabric"]["overhead_error"] = \
                        f"{type(e).__name__}: {e}"[:200]
                    result["partial"] = True
            else:
                result["fabric"]["overhead_skipped"] = "wall budget"
                result["partial"] = True
        # ---- traffic lane (ISSUE 11): capture/replay engine. Headline
        # keys: replay_fidelity_pct (a recorded mixed-priority corpus
        # replayed at 1x reproduces the recorded qps profile) and
        # capture_overhead_pct (capture-on at production defaults vs
        # off on the pipelined multiproc driver — alternating best-of
        # windows; capture_overhead_full_pct prices the unbudgeted
        # corpus-recording mode). A subprocess so a wedged replay
        # cannot take the bench down.
        if deadline.remaining() < 35.0:
            result["traffic"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            import subprocess as _sp
            try:
                p = _sp.run(
                    [sys.executable,
                     os.path.join(base, "tools", "traffic_smoke.py"),
                     "--bench"],
                    capture_output=True, text=True, timeout=240)
                rep = json.loads(p.stdout.strip().splitlines()[-1])
                lane = {k: rep.get(k) for k in (
                    "replay_fidelity_pct", "capture_overhead_pct",
                    "capture_overhead_full_pct", "qps_capture_on",
                    "qps_capture_off", "qps_capture_full",
                    "captured_under_load", "captured_full_rate",
                    "behind_ms_max", "problems")}
                result["traffic"] = lane
                if rep.get("replay_fidelity_pct") is not None:
                    result["replay_fidelity_pct"] = \
                        rep["replay_fidelity_pct"]
                if rep.get("capture_overhead_pct") is not None:
                    result["capture_overhead_pct"] = \
                        rep["capture_overhead_pct"]
                _progress({"progress": "traffic_lane", **lane})
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["traffic"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
                result["partial"] = True
                _progress({"progress": "error", "phase": "traffic",
                           "error": result["traffic"]["error"]})
        # ---- timeline lane (ISSUE 13): the trend-ring engine's price.
        # series_overhead_pct = series-on vs BRPC_TPU_BVAR_SERIES=0 on
        # the pipelined multiproc qps driver (never a sync 1-conn
        # loop), TWO echo servers alive at once (the cost sits on the
        # SERVER's sampler tick, so the toggle rides the server env),
        # alternating best-of windows like every overhead headline.
        if deadline.remaining() < 15.0:
            result["timeline"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            try:
                from qps_client import drive_multiproc
                from spawn_util import spawn_port_server
                tservers = []
                tports = {}
                try:
                    for tag, flagval in (("on", "1"), ("off", "0")):
                        env = dict(os.environ,
                                   BRPC_TPU_BVAR_SERIES=flagval,
                                   JAX_PLATFORMS="cpu")
                        tproc, tport = spawn_port_server(
                            [os.path.join(base, "tools",
                                          "bench_echo_server.py")],
                            wall_s=20.0, env=env)
                        if tport is None:
                            raise RuntimeError(
                                f"series-{tag} server spawn failed")
                        tservers.append(tproc)
                        tports[tag] = tport
                    ncl = max(2, min(4, (os.cpu_count() or 2) // 4))
                    win = min(1.2, max(0.8, deadline.remaining() * 0.02))
                    qps_on: list = []
                    qps_off: list = []
                    for _ in range(2):     # alternating best-of
                        qps_on.append(drive_multiproc(
                            str(tports["on"]), nprocs=ncl, seconds=win,
                            conns=2, inflight=8,
                            method="PyEcho")["qps"])
                        qps_off.append(drive_multiproc(
                            str(tports["off"]), nprocs=ncl, seconds=win,
                            conns=2, inflight=8,
                            method="PyEcho")["qps"])
                    lane = {"window_s": win, "client_procs": ncl,
                            "qps_series_on": max(qps_on),
                            "qps_series_off": max(qps_off)}
                    if max(qps_off):
                        result["series_overhead_pct"] = round(
                            max(0.0, (1.0 - max(qps_on) / max(qps_off))
                                * 100), 2)
                    result["timeline"] = lane
                    _progress({"progress": "timeline_lane", **lane,
                               "series_overhead_pct":
                               result.get("series_overhead_pct")})
                finally:
                    for tproc in tservers:
                        try:
                            tproc.terminate()
                            tproc.wait(5)
                        except Exception:
                            pass
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["timeline"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
                result["partial"] = True
                _progress({"progress": "error", "phase": "timeline",
                           "error": result["timeline"]["error"]})
        # ---- serving lane (ISSUE 8): continuous-batching inference
        # over streaming RPC — a 2-shard GenerateService under a
        # chaos-flapped pipelined client mix (seeded transport drops
        # mid-stream + redial). Headline keys: tokens_per_s and
        # ttft_p99_ms; full_gen_p99_ms rides along as proof streaming
        # is incremental (TTFT p99 must sit well under it). A
        # subprocess so a wedged engine cannot take the bench down.
        if deadline.remaining() < 30.0:
            result["serving"] = {"skipped": "wall budget"}
            result["partial"] = True
        else:
            import subprocess as _sp
            try:
                win = min(6.0, max(3.0, deadline.remaining() * 0.05))
                p = _sp.run(
                    [sys.executable,
                     os.path.join(base, "tools", "serving_smoke.py"),
                     "--bench", "--seconds", str(win)],
                    capture_output=True, text=True, timeout=240)
                rep = json.loads(p.stdout.strip().splitlines()[-1])
                result["serving"] = rep
                if rep.get("tokens_per_s") is not None:
                    result["tokens_per_s"] = rep["tokens_per_s"]
                if rep.get("ttft_p99_ms") is not None:
                    result["ttft_p99_ms"] = rep["ttft_p99_ms"]
                # pre-wired at 0.0 until a prefix cache exists to hit:
                # the key is in the headline set NOW so the first PR
                # that adds prefill caching shows up as a delta, not a
                # new column
                result["prefill_cache_hit_ratio"] = 0.0
                # the flight deck's cost joins the headline set,
                # re-measured on THIS box by the observatory smoke
                # (same pair-median estimator gate_serving_obs runs)
                op = _sp.run(
                    [sys.executable,
                     os.path.join(base, "tools",
                                  "serving_obs_smoke.py")],
                    capture_output=True, text=True, timeout=240)
                try:
                    orep = json.loads(
                        op.stdout.strip().splitlines()[-1])
                    if orep.get("serving_stats_overhead_pct") \
                            is not None:
                        result["serving_stats_overhead_pct"] = \
                            orep["serving_stats_overhead_pct"]
                except (ValueError, IndexError):
                    pass
                _progress({"progress": "serving_lane",
                           "tokens_per_s": rep.get("tokens_per_s"),
                           "ttft_p99_ms": rep.get("ttft_p99_ms"),
                           "full_gen_p99_ms": rep.get("full_gen_p99_ms"),
                           "flapped": rep.get("flapped"),
                           "errors": rep.get("errors")})
            except Exception as e:  # noqa: BLE001 - diagnostics only
                result["serving"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
                result["partial"] = True
                _progress({"progress": "error", "phase": "serving",
                           "error": result["serving"]["error"]})
        ch.close()
    except BaseException as e:  # noqa: BLE001 - salvage partial data
        result["partial"] = True
        result["error"] = f"{type(e).__name__}: {e}"[:500]
        _progress({"progress": "error", "phase": "tcp",
                   "error": result["error"]})

    # (the device lane — link floors, 1MB headline, 4B-4MB sweep over
    # ici:// — ran inside the phase-0 probe child; see
    # tools/device_probe.py and DEVICE_PROBE.json)
    try:
        if tcp_server is not None:
            tcp_server.stop()
            tcp_server.join(2)
    except Exception:
        pass
    if server_proc is not None:
        try:
            server_proc.terminate()
            server_proc.wait(5)
        except Exception:
            pass

    print(json.dumps(result), flush=True)
    # compact verdict line LAST (VERDICT.md round-5 item 4): harness
    # tails truncate from the head, so the verdict-relevant numbers —
    # headline, efficiency bars, small-RPC latency, streaming, device
    # lane — must survive in the final line even when the full result
    # object above is cut off
    lane = result.get("device_lane") or {}
    # small-batch latency headline: mean of the lane sweep's avg_us
    # over the coalescable sizes (4B-16KB) — the number the descriptor
    # coalescing + adaptive window work moves
    _small = [pt.get("avg_us") for sz, pt in (lane.get("sweep")
                                              or {}).items()
              if sz.isdigit() and int(sz) <= 16384
              and isinstance(pt, dict) and pt.get("avg_us")]
    ici_small_batch_us = (round(sum(_small) / len(_small), 1)
                          if _small else None)
    summary = {
        "SUMMARY": 1,
        "GBps": result.get("value"),
        "vs_baseline": result.get("vs_baseline"),
        "eff_vs_raw_msg": result.get("efficiency_vs_raw"),
        "eff_vs_raw_stream": result.get("efficiency_vs_stream_raw"),
        "p99_us": result.get("p99_us"),
        "small_rpc_p50_us": result.get("small_rpc_p50_us"),
        "small_rpc_p99_us": result.get("small_rpc_p99_us"),
        "small_rpc_min_us": result.get("small_rpc_min_us"),
        "streaming_GBps": result.get("streaming_GBps"),
        "streaming_efficiency": result.get("streaming_efficiency"),
        "concurrency_scaling_8c": result.get("concurrency_scaling_8c"),
        "qps_8c_4B": result.get("qps_8c_4B"),
        "qps_sharded_4B": result.get("qps_sharded_4B"),
        "shard_scaling": result.get("shard_scaling"),
        "shard_count": result.get("shard_count"),
        "profiler_overhead_pct": result.get("profiler_overhead_pct"),
        "bytes_per_idle_conn": result.get("bytes_per_idle_conn"),
        "cluster_qps": result.get("cluster_qps"),
        "backend_stats_overhead_pct":
        result.get("backend_stats_overhead_pct"),
        "fault_goodput_ratio": result.get("fault_goodput_ratio"),
        "fault_p99_ms": result.get("fault_p99_ms"),
        "priority_goodput_hi_ratio":
        result.get("priority_goodput_hi_ratio"),
        "admission_overhead_pct": result.get("admission_overhead_pct"),
        "replay_fidelity_pct": result.get("replay_fidelity_pct"),
        "capture_overhead_pct": result.get("capture_overhead_pct"),
        "series_overhead_pct": result.get("series_overhead_pct"),
        # serving flight-deck headline set: throughput + TTFT from the
        # flapped bench lane, the deck's measured cost, and the
        # pre-wired prefix-cache ratio (0.0 until one exists)
        "tokens_per_s": result.get("tokens_per_s"),
        "ttft_p99_ms": result.get("ttft_p99_ms"),
        "serving_stats_overhead_pct":
        result.get("serving_stats_overhead_pct"),
        "prefill_cache_hit_ratio":
        result.get("prefill_cache_hit_ratio"),
        "device_lane": ("error" if ("error" in lane or
                                    "lane_error" in lane)
                        else ("ok" if lane else "absent")),
        # device lane headline pair: bulk GB/s and the coalescable
        # small-batch latency (4B-16KB sweep mean)
        "ici_headline_GBps": lane.get("headline_GBps"),
        "ici_small_batch_us": ici_small_batch_us,
        # device observatory headline pair (measured inside the probe
        # child next to the ici numbers they qualify): what the stage
        # spans account for, and what the cells cost
        "ici_stage_attribution_pct":
        lane.get("ici_stage_attribution_pct"),
        "device_stats_overhead_pct":
        lane.get("device_stats_overhead_pct"),
        "native": bool(result.get("native", {}).get("fastcore")),
        "partial": result.get("partial"),
    }
    print(json.dumps({k: v for k, v in summary.items() if v is not None}),
          flush=True)
    sys.stdout.flush()
    sys.stderr.flush()
    # hard-exit: teardown from live background threads can abort the
    # interpreter AFTER our output (observed: "FATAL: exception not
    # rethrown" -> rc=134 with a complete result line); everything is
    # flushed, so skip teardown entirely. A device lane that errored
    # fails the run whatever the TCP headline did.
    os._exit(0 if result["value"] > 0
             and summary["device_lane"] == "ok" else 1)


if __name__ == "__main__":
    main()
