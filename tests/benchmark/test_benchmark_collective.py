"""The lowered allreduce's cell: its plain reference and byte counts,
the precision below the configuration's as a reading that must not
pass, the join of a lowered call's span into stages, and the readers
under a program that lacks what they read. Rehearsal numbers are no
measurements."""

from types import SimpleNamespace

import numpy as np
import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path

MB4 = 2048 * 1024 * 2
V5E = {"ici_bits_per_s": 1600e9, "hbm_bytes_per_s": 819e9}


def test_reference_matches_numpy_and_the_bytes_come_from_the_shapes():
    import jax.numpy as jnp

    from benchmark.layer_metrics.collective_roofline import least_time_us
    from benchmark.reference import allreduce, collective_allreduce as ref

    big = np.arange(8 * 3, dtype=np.float32).reshape(8, 3) - 10
    want = sum(big[i * 2:(i + 1) * 2] * 2 for i in range(4))
    got = ref.allreduce_reference(jnp.asarray(big, jnp.bfloat16), 4)
    np.testing.assert_array_equal(np.asarray(got), want)
    # its own copy, and the same mathematics as the fan-out's reference
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(allreduce.allreduce_reference(
            jnp.asarray(big, jnp.bfloat16), 4)))
    assert ref.collective_bytes(4, MB4) == 6_291_456
    assert ref.scatter_bytes(4, MB4) == 12_582_912
    assert ref.hbm_bytes(4, MB4, False) == 2 * MB4
    assert ref.hbm_bytes(4, MB4, True) == 5 * MB4
    sizes = {"shard_block": [2048, 1024], "dtype": "bfloat16"}
    # the all-reduce alone would be 6.29 MB at 200 GB/s; the source also
    # sends the scatter's 12.58 MB: interconnect-bound at 18.87 MB
    assert 1e6 * ref.collective_bytes(4, MB4) / 200e9 == \
        pytest.approx(31.46, 1e-3)
    assert least_time_us(sizes, 4, V5E) == pytest.approx(94.37, 1e-3)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_the_precision_below_bf16_is_not_correct(seed):
    """The cell's limit is 0 (bit exact). bf16 reads 0 on every seed;
    the same mathematics in float8 (e4m3), the nearest precision below,
    differs, so a float8 path could not pass for correct."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.verify import DeviceVerifier
    from benchmark.reference.collective_allreduce import allreduce_reference

    req = jax.random.randint(jax.random.PRNGKey(seed), (4 * 64, 32), -8, 9)
    req = req.astype(jnp.bfloat16)
    want = allreduce_reference(req, 4)

    def in_dtype(dtype):
        blocks = req.astype(dtype).reshape(4, 64, 32)
        doubled = (blocks * jnp.asarray(2, dtype)).astype(dtype)
        total = doubled[0]
        for part in doubled[1:]:
            total = (total + part).astype(dtype)
        return total.astype(jnp.bfloat16)

    verifier = DeviceVerifier(batch=1)
    verifier.declare("allreduce", 0)
    verifier.add("allreduce", in_dtype(jnp.bfloat16), want)
    assert verifier.finish() == 0
    verifier.add("allreduce", in_dtype(jnp.float8_e4m3fn), want)
    assert verifier.finish() == 1
    err = np.abs(np.asarray(in_dtype(jnp.float8_e4m3fn)).astype(np.float32)
                 - np.asarray(want))
    assert err.max() >= 1.0


def _span(start, scatter, dispatch, ready, lowered=True, error=0,
          side="client"):
    notes = [(start, "collective lowered: scatter + sum over 4 shards, "
              "no sub call")] if lowered else []
    return SimpleNamespace(side=side, start_us=start, write_done_us=scatter,
                           dispatch_us=dispatch, first_byte_us=ready,
                           end_us=ready + 1, error_code=error,
                           annotations=notes)


def test_a_lowered_calls_span_telescopes_into_stages():
    from benchmark.lib.collective_calls import STAGES, lowered_calls

    spans = [_span(100, 130, 300, 900),
             _span(1000, 1040, 1200, 1700),
             _span(1500, 1500, 1500, 1500, lowered=False),   # a sub call
             _span(2000, 2050, 0, 2600),                     # no dispatch
             _span(3000, 3050, 3100, 3600, error=1),
             _span(4000, 4050, 4100, 4600, side="server"),
             _span(9000, 9050, 9100, 9600)]                  # after the window
    kept, dropped = lowered_calls(spans, 0, 8000)
    assert STAGES == ("scatter", "dispatch", "ready")
    assert kept == [(30, 170, 600), (40, 160, 500)] and dropped == 2
    assert [sum(k) for k in kept] == [800, 700]     # entry -> ready


def test_readers_find_nothing_under_a_program_without_the_source(
        monkeypatch):
    """The parent commit has no lowered span, no counters and no program
    of that name: every reader returns None and does not raise."""
    import sys

    from benchmark.layer_metrics import (collective_device_us,
                                         collective_fused_share,
                                         collective_issue_us,
                                         collective_roofline)
    from benchmark.lib import collective_calls, rpc_spans

    run = SimpleNamespace(trace=None, trace_devices=[0, 1, 2, 3],
                          _win_start_ns=0, window_s=1.0)
    monkeypatch.setattr(rpc_spans, "program_spans", lambda: [])
    import brpc_tpu.rpc.combo_channels as combo
    bare = SimpleNamespace(**{k: v for k, v in vars(combo).items()
                              if k != "collective_counters"})
    monkeypatch.setitem(sys.modules, "brpc_tpu.rpc.combo_channels", bare)
    collective_calls.mark_window_start()
    collective_calls.mark_window_end()
    assert collective_calls.window_counters() is None
    for reader in (collective_device_us, collective_roofline,
                   collective_issue_us, collective_fused_share):
        assert reader.read(run) is None
    # and with the program's counters: the share of the window's tries
    monkeypatch.setitem(sys.modules, "brpc_tpu.rpc.combo_channels", combo)
    collective_calls.mark_window_start()
    combo._fused_var.add(3)
    collective_calls.mark_window_end()
    assert collective_calls.window_counters()["fused"] == 3
    assert collective_fused_share.read(run) == 100.0


THE_FOUR = [
    ("collective_device_us", "us", "lower", "device_trace", "kernels"),
    ("collective_roofline", "%", "higher", "device_trace", "kernels"),
    ("collective_issue_us", "us", "lower", "program_span", "combo channel"),
    ("collective_fused_share", "%", "higher", "program_counter",
     "combo channel"),
]


def _stretch(per_layer, first_name, n):
    first = [m["name"] for m in per_layer].index(first_name)
    return first, per_layer[first:first + n]


def test_the_four_entries_stand_together_after_what_was_there():
    """New entries go at the END of ``per_layer`` (the benchmark's
    contract; one in the middle reads as a change to what was there).
    Found by name: a later PR appends after them and edits no test."""
    from test_benchmark_thread_roles import NAMES

    per_layer = bench_testlib.bench()["per_layer"]
    cell = ["collective_allreduce.collective_4mb_d1"]
    first, four = _stretch(per_layer, THE_FOUR[0][0], 4)
    assert four == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "call_p50_us", "workloads": cell}
        for name, unit, better, source, layer in THE_FOUR]
    assert per_layer[first - 1]["name"] == NAMES[-1]    # the parent's last


def test_pr29s_six_entries_stand_unchanged_and_together():
    """The six role entries as they were written, found by name, in
    their order, with nothing between them, and the role cells among the
    ``calls_per_s`` cells: a later cell may report the rate without a
    role entry."""
    from test_benchmark_thread_roles import CELLS, ENTRIES, NAMES

    _, six = _stretch(bench_testlib.bench()["per_layer"], NAMES[0], 6)
    assert six == [
        {"name": name, "unit": unit, "better": "lower",
         "source": "program_counter", "layer": layer,
         "moves": "calls_per_s", "workloads": CELLS}
        for name, unit, layer in ENTRIES]
    e2e = {e["name"]: e for e in bench_testlib.bench()["end_to_end"]}
    assert set(CELLS) <= set(e2e["calls_per_s"]["workloads"])
