"""The yardstick's arithmetic on inputs small enough to check by hand:
order statistics, the peaks table, the on-device verifier."""

import numpy as np
import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.lib import stats


def test_percentile_matches_numpy():
    rng = np.random.RandomState(3)
    xs = list(rng.exponential(100.0, size=1234))
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q * 100)), rel=1e-12)


def test_tail_needs_ten_samples_beyond():
    assert stats.tail(list(range(999)), 0.99) is None
    assert stats.tail(list(range(1000)), 0.99) == pytest.approx(989.01)
    assert stats.median([3, 1, 2]) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_peaks_unknown_device_is_an_error():
    from benchmark.lib.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_device_verifier():
    import jax.numpy as jnp

    from benchmark.lib.verify import DeviceVerifier

    good = jnp.arange(8, dtype=jnp.float32)
    for tolerance, bad_value, expect_bad in (
            (0, 1, True), (0, 0, False), (0.5, 0.25, False),
            (0.5, 1.0, True), (0.5, float("nan"), True)):
        v = DeviceVerifier(batch=4)
        v.declare("k", tolerance)
        v.warm("k", good, good)
        for i in range(6):          # one full batch and a padded one
            v.add("k", good + (bad_value if i == 2 else 0), good)
        assert (v.finish() > 0) is expect_bad, (tolerance, bad_value)
        assert v.checked == 6
