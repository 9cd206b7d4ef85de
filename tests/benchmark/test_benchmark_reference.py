"""The plain references against numpy, and the operation and byte
counts the roofline uses."""

import numpy as np
import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path


def test_step_reference_matches_numpy_and_costs():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import perf

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (16, 64), jnp.bfloat16)
    w1 = (jax.random.normal(k[1], (64, 256), jnp.bfloat16) * 0.02)
    w2 = (jax.random.normal(k[2], (256, 64), jnp.bfloat16) * 0.02)
    xn, w1n, w2n = (np.asarray(a).astype(np.float32) for a in (x, w1, w2))
    want = np.maximum(xn @ w1n, 0) @ w2n + xn
    np.testing.assert_allclose(np.asarray(perf.step_reference(x, w1, w2)),
                               want, rtol=1e-5, atol=1e-5)
    assert perf.step_flops(512, 2048, 8192) == pytest.approx(34.36e9,
                                                             rel=1e-3)
    assert perf.step_bytes(512, 2048, 8192) == 2 * (2 * 2048 * 8192
                                                    + 2 * 512 * 2048)


def test_allreduce_reference_matches_numpy():
    import jax.numpy as jnp

    from benchmark.reference.allreduce import allreduce_reference

    big = np.arange(8 * 3, dtype=np.float32).reshape(8, 3) - 10
    want = sum(big[i * 2:(i + 1) * 2] * 2 for i in range(4))
    got = allreduce_reference(jnp.asarray(big, jnp.bfloat16), 4)
    np.testing.assert_array_equal(np.asarray(got), want)
