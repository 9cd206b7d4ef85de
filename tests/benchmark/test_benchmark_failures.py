"""What must make ``correct`` false."""

import pytest

from bench_testlib import last_line, run_cell

ONE_CHIP = "tpu_performance.echo_small_d1"


@pytest.mark.parametrize("cell", [ONE_CHIP,
                                  "parallel_allreduce.fanout_4mb_d1"])
def test_corrupted_response_is_not_correct(cell):
    proc = run_cell(cell, "--inject", "corrupt_response")
    res = last_line(proc)
    assert res["correct"] is False and res["failed"] > 0
    assert proc.returncode != 0
    assert "differ from the reference" in proc.stdout


def test_device_cell_imbalance_is_not_correct():
    proc = run_cell(ONE_CHIP, "--inject", "device_imbalance")
    res = last_line(proc)
    assert res["correct"] is False and res["failed"] == 0
    assert "bench-injected" in proc.stdout
