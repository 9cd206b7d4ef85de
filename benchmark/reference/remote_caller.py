"""Plain reference of the ``remote_caller`` deployment, independent of
brpc_tpu and of ``benchmark/services/``: its own copy.

``Step`` is the residual ReLU MLP of the repo's flagship entry
(``__graft_entry__.entry``): y = relu(x @ w_in) @ w_out + x, in float32
with every matmul at "highest" precision (on a TPU a float32 matmul
otherwise runs as one bf16 pass). ``step_reference`` computes it in
``jax.numpy`` (the parent process, on the chip, in set-up);
``step_reference_numpy`` computes the same in numpy (a process without
jax; the tests hold the two to each other at a small size).

The client of this deployment has no accelerator runtime and no
weights: the parent computes the float32 expectation of each (input,
layer) pair once, on the chip, and hands the client its host bytes.
``within`` is the client's comparison of a response with that
expectation, in numpy. ``expectation_of`` is the closed loop's rule:
which input and which layer call ``seq`` uses."""

from __future__ import annotations


def step_reference(x, w_in, w_out):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x32 = x.astype(jnp.float32)
        h = jnp.maximum(x32 @ w_in.astype(jnp.float32), 0.0)
        return h @ w_out.astype(jnp.float32) + x32


def step_reference_numpy(x, w_in, w_out):
    import numpy as np

    x32 = np.asarray(x).astype(np.float32)
    h = np.maximum(x32 @ np.asarray(w_in).astype(np.float32), 0.0)
    return h @ np.asarray(w_out).astype(np.float32) + x32


def within(response, expected32, atol: float) -> bool:
    """Whether every element of ``response`` (any float dtype, numpy) is
    within ``atol`` of the float32 expectation; a NaN is not."""
    import numpy as np

    if response.shape != expected32.shape:
        return False
    err = np.abs(response.astype(np.float32) - expected32)
    return bool(np.all(err <= atol))      # not (err <= atol) catches NaN


def expectation_of(seq: int, pool: int, layers: int) -> tuple:
    """(input index, layer index) of call ``seq``: inputs and layers in
    rotation, so with 8 of each a call never finds the weights of the
    call before it."""
    return seq % pool, seq % layers
