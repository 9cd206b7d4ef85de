"""The ``remote_caller`` deployment through the real command in
rehearsal: a client in its own process without jax over tpud://. What
the run prints, and what must make it ``correct`` or not. Rehearsal
numbers are no measurements."""

import json

import numpy as np

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from bench_testlib import last_line, run_cell

CELL = "remote_caller.step_2mb_d8_tpud"
SIX = {"remote_server_cpu_us_per_call", "remote_client_cpu_us_per_call",
       "staged_send_us", "staged_take_us", "remote_request_wire_us",
       "remote_response_wire_us"}
# accepted readers that find their source in this cell too: the cell was
# appended to their lists (the p99 needs ten samples beyond it)
ACCEPTED = {"fabric_overhead_us", "call_p99_traced_us"}


def _info(proc, key):
    found = [json.loads(ln)["info"][key] for ln in proc.stdout.splitlines()
             if ln.startswith('{"info"') and f'"{key}"' in ln]
    assert found, f"no info line with {key!r}"
    return found[-1]


def test_a_traced_rehearsal_prints_the_six_metrics_and_is_correct():
    proc = run_cell(CELL, seconds=3.0, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    assert res["correct"] is True and res["failed"] == 0
    assert SIX | {"fabric_overhead_us"} <= set(res["metrics"]) \
        <= SIX | ACCEPTED
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"busy_s", "window_s"} <= set(res["device"])
    client = _info(proc, "remote_client")
    assert client["jax_loaded"] is False and client["lane"] == "staged-dcn"
    assert client["client_spans"] >= 20


def test_an_untraced_rehearsal_reports_the_median_and_both_ends():
    proc = run_cell(CELL, seconds=1.0, trace=0)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = last_line(proc)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"call_p50_us", "setup_s"}
    client = _info(proc, "remote_client")
    # both ends on the staged lane, the client off the chip, every call
    # of the window verified by it, bit exact against the program's own
    assert _info(proc, "lanes") == ["staged-dcn", "staged-dcn"]
    assert client["jax_loaded"] is False
    assert client["placement_violations"] == 0
    assert client["tpud_put_fallbacks"] == {"server": 0, "client": 0}
    assert client["verified"] == _info(proc, "samples")
    assert client["verified_bit_exact"] == client["verified"]
    assert client["verified_by_tolerance_alone"] == 0
    assert client["verify_cpu_us_per_call"] > 0
    (cell,) = client["cells"].values()
    assert cell["transfers"] == cell["completed"] == res["attempted"]
    assert cell["failed"] == 0 and cell["recv_transfers"] == res["attempted"]
    # the client put nothing on a device: it has none
    assert client["counters"]["tpud_put_us"] == 0
    assert client["counters"]["tpud_batches_out"] == res["attempted"]


def test_a_corrupted_response_is_not_correct():
    proc = run_cell(CELL, "--inject", "corrupt_response")
    res = last_line(proc)
    assert res["correct"] is False and res["failed"] > 0
    assert proc.returncode != 0
    assert "differ from the reference" in proc.stdout
    assert "more than the tolerance" in proc.stdout


def test_the_reference_in_jax_and_in_numpy_agree_and_the_limit_bites():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import remote_caller as reference

    k = jax.random.split(jax.random.PRNGKey(37), 3)
    x = jax.random.normal(k[0], (16, 64), jnp.bfloat16)
    w1 = jax.random.normal(k[1], (64, 256), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(k[2], (256, 64), jnp.bfloat16) * 0.02
    want = np.asarray(reference.step_reference(x, w1, w2))
    assert want.dtype == np.float32
    host = reference.step_reference_numpy(*(np.asarray(a)
                                            for a in (x, w1, w2)))
    np.testing.assert_allclose(host, want, rtol=1e-5, atol=1e-5)
    # the timed program's bf16 answer is inside the limit, one more
    # bf16 step of every element is outside it
    got = np.asarray(jnp.maximum(x @ w1, 0) @ w2 + x)
    assert reference.within(got, want, 2 ** -4)
    assert not reference.within(got + got.dtype.type(0.125), want, 2 ** -4)
    assert not reference.within(np.full_like(got, np.nan), want, 2 ** -4)
    assert not reference.within(got[:8], want, 2 ** -4)
    # the closed loop's rule: inputs and layers in rotation
    assert [reference.expectation_of(s, 8, 8) for s in (0, 9, 4093)] == \
        [(0, 0), (1, 1), (5, 5)]
    assert reference.expectation_of(5, 8, 2) == (5, 1)
