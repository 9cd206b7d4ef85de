"""tpud:// between two processes: a client that never loads jax (numpy
arrays in host memory) calls a jax server with a bfloat16 array. The
server's handler gets a ``jax.Array`` on its device, the client gets
numpy back, the answer agrees with the float32 reference, both ends'
``staged-dcn`` cells balance with their stage, wire and recv sums, and
the call's two spans, one in each process, join by the ids that travel
in ``RpcMeta`` on one clock (CLOCK_MONOTONIC of the host)."""

import base64
import json
import os
import subprocess
import sys

import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, COLS, FF, CALLS = 16, 64, 256, 6
ATOL = 2 ** -4      # benchmark/services/perf.py: two bf16 ulps at |y| < 8


@pytest.fixture(scope="module")
def exchange():
    import jax
    import jax.numpy as jnp

    from brpc_tpu.butil.flags import flag, set_flag
    from brpc_tpu.rpc import Server, ServerOptions, Service
    from brpc_tpu.rpc.span import global_collector
    from brpc_tpu.transport import device_stats, syscall_stats

    old = {name: flag(name) for name in ("rpcz_enabled",
                                         "device_stats_enabled")}
    set_flag("rpcz_enabled", True)
    set_flag("device_stats_enabled", True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(37))
    w_in = (jax.random.normal(k1, (COLS, FF), jnp.bfloat16) * 0.02)
    w_out = (jax.random.normal(k2, (FF, COLS), jnp.bfloat16) * 0.02)
    step = jax.jit(lambda x: jnp.maximum(x @ w_in, 0) @ w_out + x)
    seen = []

    def handler(cntl, request):
        a = cntl.request_device_arrays[0]
        seen.append((isinstance(a, jax.Array),
                     a.devices() if isinstance(a, jax.Array) else None))
        cntl.response_device_arrays = [step(a)]
        return bytes(request)

    svc = Service("Perf")
    svc.register_method("Step", handler)
    server = Server(ServerOptions(enable_builtin_services=False))
    server.add_service(svc)
    ep = server.start("tpud://127.0.0.1:0#device=0")
    before = syscall_stats.snapshot()
    try:
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("XLA_")}
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tests",
                                          "tpud_numpy_client.py"),
             str(ep.port), str(ROWS), str(COLS), str(CALLS)],
            capture_output=True, text=True, timeout=120, env=env)
        assert proc.returncode == 0, proc.stderr[-3000:]
        child = json.loads(proc.stdout.splitlines()[-1])
        after = syscall_stats.snapshot()
        yield {
            "child": child, "seen": seen, "device": jax.devices()[0],
            "w": (np.asarray(w_in), np.asarray(w_out)),
            "server_spans": [s.to_dict()
                             for s in global_collector.recent(1 << 20)
                             if s.side == "server" and s.method == "Step"],
            "server_cells": device_stats.device_page_payload(
                samples=0)["cells"],
            "server_counters": {k: after[k] - before.get(k, 0)
                                for k in after if k.startswith("tpud_")}}
    finally:
        server.stop()
        server.join(2)
        for name, value in old.items():
            set_flag(name, value)


def test_a_numpy_only_client_gets_the_reference_answer(exchange):
    from benchmark.reference.remote_caller import (step_reference_numpy,
                                                   within)
    child = exchange["child"]
    assert child["jax_loaded"] is False and child["lane"] == "staged-dcn"
    bf16 = np.dtype(ml_dtypes.bfloat16)
    x = np.frombuffer(base64.b64decode(child["x"]),
                      dtype=bf16).reshape(ROWS, COLS)
    want = step_reference_numpy(x, *exchange["w"])
    assert len(child["replies"]) == CALLS
    for i, r in enumerate(child["replies"]):
        assert r["tag"] == f"tag{i}"
        assert r["type"] == "ndarray" and r["dtype"] == "bfloat16"
        got = np.frombuffer(base64.b64decode(r["bytes"]),
                            dtype=bf16).reshape(r["shape"])
        assert within(got, want, ATOL)
        assert not within(got + bf16.type(1), want, ATOL)


def test_the_handler_saw_a_jax_array_on_its_device(exchange):
    assert len(exchange["seen"]) == CALLS
    for is_jax, devices in exchange["seen"]:
        assert is_jax and devices == {exchange["device"]}
    counters = exchange["server_counters"]
    assert counters["tpud_batches_in"] == CALLS
    assert counters["tpud_batches_out"] == CALLS
    assert counters["tpud_put_fallbacks"] == 0
    assert counters["tpud_put_us"] > 0 and counters["tpud_decode_us"] >= 0
    # the client put nothing anywhere: it has no device
    assert exchange["child"]["counters"]["tpud_put_us"] == 0
    assert exchange["child"]["counters"]["tpud_batches_out"] == CALLS


def test_both_ends_staged_dcn_cells_balance_with_their_sums(exchange):
    for cells in (exchange["server_cells"], exchange["child"]["cells"]):
        mine = [row for key, row in cells.items()
                if key.endswith("|staged-dcn") and row["transfers"] >= CALLS]
        assert mine, cells
        for row in mine:
            assert row["completed"] == row["transfers"]
            assert row["failed"] == 0 and row["leaked_bytes"] == 0
            assert row["recv_transfers"] >= CALLS
            assert row["stage_us_sum"] > 0 and row["wire_us_sum"] > 0
            assert row["recv_us_sum"] > 0
            assert row["bytes_out"] >= CALLS * ROWS * COLS * 2


def test_the_two_spans_of_a_call_join_across_the_processes(exchange):
    clients = {(s["trace_id"], s["span_id"]): s
               for s in exchange["child"]["spans"] if s["method"] == "Step"}
    assert len(clients) == CALLS
    joined = [(clients[(s["trace_id"], s["parent_span_id"])], s)
              for s in exchange["server_spans"]
              if (s["trace_id"], s["parent_span_id"]) in clients]
    assert len(joined) == CALLS
    for c, s in joined:
        assert c["pid"] != s["pid"]
        # one clock: the server's stamps lie inside the client's call
        assert c["start_us"] <= s["received_us"] <= s["handler_start_us"]
        assert s["handler_end_us"] <= c["end_us"]
        assert c["write_done_us"] and c["first_byte_us"] and s["flushed_us"]
        assert c["start_us"] <= c["write_done_us"] <= c["first_byte_us"]
