"""The trace reduction on a recorded slice of a real chip window:
0.2 s of tpu_performance.step_2mb_d8 on a TPU v5 lite (PR 22, second
chip call; cut to the device plane's XLA Ops / XLA Modules lines and the
host's bench.* spans). The numbers below were read off that trace by
hand (88 runs of jit_perf_step at 225 us and 6 of jit_count_bad make
20.6 ms of device time) and must come out the same for ever."""

import os

import pytest

from bench_testlib import ROOT
from benchmark.lib.stats import median
from benchmark.lib.trace_reduce import UNATTRIBUTED, Trace, short_op

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "step_2mb_d8.slice.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    assert os.path.getsize(FIXTURE) < 1 << 20
    return Trace.from_file(FIXTURE)


def test_planes_window_and_busy_share(trace):
    assert sorted(trace.devices) == [0]
    lo, hi = trace.window()
    assert (hi - lo) / 1e9 == pytest.approx(0.199729237, abs=1e-9)
    assert trace.busy_s([0]) == pytest.approx(0.020554839, abs=1e-9)
    lo, hi = trace.window()
    assert trace.busy_s([0]) / ((hi - lo) / 1e9) == pytest.approx(
        0.1029, abs=1e-4)


def test_step_device_us_and_roofline(trace):
    durs = trace.program_durations_us("perf_step", [0])
    assert len(durs) == 88
    assert median(durs) == pytest.approx(225.463, abs=1e-3)

    from benchmark.layer_metrics.step_roofline import least_time_us
    from benchmark.lib.peaks import peaks_for

    sizes = {"step": {"batch": 512, "d_model": 2048, "d_ff": 8192}}
    least = least_time_us(sizes, peaks_for("TPU v5 lite"))
    assert least == pytest.approx(174.41, abs=0.01)    # compute-bound
    assert 100 * least / median(durs) == pytest.approx(77.36, abs=0.01)


def test_top_operations(trace):
    top = trace.top_ops([0], 3)
    assert [n for n, _s in top] == [
        "%convolution_maximum_fusion bf16[512,8192]",
        "%convolution_add_fusion bf16[512,2048]",
        "%copy-done bf16[2048,8192]"]
    assert top[0][1] == pytest.approx(0.008008232, abs=1e-9)
    assert short_op("no equals sign") == "no equals sign"


def test_gap_attribution(trace):
    gaps = dict(trace.idle_gaps([0]))
    assert gaps == {
        "bench.issue": pytest.approx(0.081692061, abs=1e-9),
        UNATTRIBUTED: pytest.approx(0.045577604, abs=1e-9),
        "bench.handler": pytest.approx(0.033828848, abs=1e-9),
        "bench.verify": pytest.approx(0.010810398, abs=1e-9),
        "bench.wait_ready": pytest.approx(0.007265487, abs=1e-9)}
    # every nanosecond of the window is busy or in exactly one gap
    lo, hi = trace.window()
    assert sum(gaps.values()) + trace.busy_s([0]) == pytest.approx(
        (hi - lo) / 1e9, abs=1e-9)
