"""Two-process ici:// smoke: proves (or loudly fails) the PjRt
pull-DMA lane across a process boundary.

The reference proves its RDMA lane with rdma_performance against a real
NIC (rdma/rdma_helper.cpp global-init + fallback story); this is the
same evidence for the PjRt fabric: a child process serves EchoDevice
over ici://, the parent drives a device-array RPC at it, and the lane
kind (pjrt-pull / staged) and the transfer-server status are printed
as one ``EVIDENCE {json}`` line.

Two processes cannot share one chip, so this smoke always runs on the
CPU platform, where cross-process pulls exercise
jax.experimental.transfer over sockets. The one-process device lane on
the chip is ``chip_smoke.py``'s job.

Usage:  python tools/ici_smoke.py            # the smoke
        python tools/ici_smoke.py --serve    # (internal) server role
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# both roles: a chip has room for one process (the child inherits this)
os.environ["JAX_PLATFORMS"] = "cpu"


def serve() -> None:
    from brpc_tpu.rpc import Server, ServerOptions, Service

    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("Smoke")

    @svc.method()
    def EchoDevice(cntl, request):
        cntl.response_device_arrays = [a * 2
                                       for a in cntl.request_device_arrays]
        return b"dev"

    server.add_service(svc)
    ep = server.start("ici://127.0.0.1:0#device=0")
    print(f"PORT {ep.port}", flush=True)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from spawn_util import parent_death_watchdog_loop
    parent_death_watchdog_loop()  # parent died: don't orphan the chip


RPC_TIMEOUT_MS = float(os.environ.get("BRPC_TPU_SMOKE_TIMEOUT_MS", "45000"))


def main() -> None:
    import numpy as np

    from brpc_tpu.rpc import Channel, ChannelOptions
    from brpc_tpu.transport import ici

    import tempfile

    evidence: dict = {"ok": False, "stage": "spawn"}
    # stderr to a FILE, not a pipe: a chatty child blocking on an
    # undrained pipe would never print PORT; the shared helper reads
    # stdout non-blocking so the 180s deadline actually fires even when
    # the child's backend bring-up hangs mid-line
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from spawn_util import spawn_port_server

    errf = tempfile.NamedTemporaryFile("w+", suffix=".log", delete=False)
    proc, port = spawn_port_server(
        [os.path.abspath(__file__), "--serve"], wall_s=180, stderr=errf)
    try:
        if not port:
            errf.seek(0)
            tail = errf.read()[-2000:]
            raise RuntimeError(
                "server never printed its port within 180s"
                + (f" (child stderr: {tail})" if tail else ""))

        evidence["stage"] = "backend_init"
        import jax
        evidence["backend"] = [str(d) for d in jax.devices()]

        evidence["stage"] = "first_rpc"
        ch = Channel(f"ici://127.0.0.1:{port}#reply_device=0",
                     ChannelOptions(timeout_ms=RPC_TIMEOUT_MS))
        arr = np.arange(65536, dtype=np.float32)          # 256KB
        t0 = time.perf_counter()
        cntl = ch.call_sync("Smoke", "EchoDevice", b"",
                            request_device_arrays=[arr])
        rtt_ms = (time.perf_counter() - t0) * 1e3
        if cntl.failed():
            raise RuntimeError(f"rpc failed: {cntl.error_text}")
        out = np.asarray(cntl.response_device_arrays[0])
        np.testing.assert_array_equal(out, arr * 2)
        evidence["lane_kind"] = ch._get_socket().conn.lane_kind
        evidence["transfer_lane"] = ici.transfer_lane_status()
        evidence["first_rtt_ms"] = round(rtt_ms, 1)

        evidence["stage"] = "steady_state"
        # a few more calls for a steady-state number — with rpcz on, so
        # the new device spans stamp the stage-resolved breakdown the
        # evidence asserts below
        from brpc_tpu.butil.flags import set_flag
        from brpc_tpu.rpc.span import global_collector
        set_flag("rpcz_enabled", True)
        # device spans ride the stage trackers: force the layer on for
        # the breakdown even when the caller priced it out via
        # BRPC_TPU_DEVICE_STATS=0 (this tool MEASURES the lane)
        set_flag("device_stats_enabled", True)
        global_collector.clear()
        lat = []
        for _ in range(5):
            t0 = time.perf_counter()
            cntl = ch.call_sync("Smoke", "EchoDevice", b"",
                                request_device_arrays=[arr])
            if cntl.failed():
                raise RuntimeError(f"rpc failed: {cntl.error_text}")
            np.asarray(cntl.response_device_arrays[0])
            lat.append((time.perf_counter() - t0) * 1e3)
        set_flag("rpcz_enabled", False)
        evidence["steady_rtt_ms"] = round(sorted(lat)[len(lat) // 2], 1)
        evidence["payload_bytes"] = arr.nbytes

        evidence["stage"] = "stage_breakdown"
        # the request's device send spans (this process is the client;
        # recv-child spans carry no write_done/first_byte stamps)
        sends = [s.to_dict() for s in global_collector.recent(200)
                 if s.side == "device" and
                 (s.write_done_us or s.first_byte_us)]
        if not sends:
            raise RuntimeError("no device spans captured — the lane "
                               "moved payloads without stage stamps")
        n = len(sends)
        bd = {
            "n": n,
            "stage_us": round(sum(d["stage_us"] for d in sends) / n, 1),
            "wire_us": round(sum(d["wire_us"] for d in sends) / n, 1),
            "ack_us": round(sum(d["ack_us"] for d in sends) / n, 1),
        }
        bd["sum_ms"] = round(
            (bd["stage_us"] + bd["wire_us"] + bd["ack_us"]) / 1e3, 2)
        evidence["stage_breakdown"] = bd
        # the send span runs issue -> peer ack (the ack piggybacks on
        # the response frame), so its stage sum must land near the
        # measured RTT — wildly off means the stamps are lying
        rtt_ms = evidence["steady_rtt_ms"]
        if rtt_ms > 0 and not (0.1 * rtt_ms <= bd["sum_ms"]
                               <= 1.7 * rtt_ms):
            raise RuntimeError(
                f"stage breakdown sum {bd['sum_ms']}ms inconsistent "
                f"with measured RTT {rtt_ms}ms")
        evidence["ok"] = True
        evidence.pop("stage", None)
        ch.close()
    except BaseException as e:  # noqa: BLE001 - evidence over crash
        evidence["error"] = f"{type(e).__name__}: {e}"[:800]
    finally:
        if proc is not None:
            try:
                proc.terminate()
                proc.wait(10)
            except Exception:
                proc.kill()
        try:
            errf.close()
            os.unlink(errf.name)
        except Exception:
            pass

    print("EVIDENCE " + json.dumps(evidence), flush=True)
    sys.stderr.flush()
    os._exit(0 if evidence["ok"] else 1)


if __name__ == "__main__":
    if "--serve" in sys.argv:
        serve()
    else:
        main()
