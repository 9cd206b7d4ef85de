"""Plain references of the ``Perf`` service, independent of brpc_tpu.

``Echo`` is the identity: the expected response is the request, bit for
bit. ``Step`` is the residual ReLU MLP of the repo's flagship entry
(``__graft_entry__.entry``): y = relu(x @ w_in) @ w_out + x, here in
float32 with every matmul at "highest" precision (on a TPU a float32
matmul otherwise runs as one bf16 pass)."""

from __future__ import annotations


def echo_reference(x):
    return x


def step_reference(x, w_in, w_out):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x32 = x.astype(jnp.float32)
        h = jnp.maximum(x32 @ w_in.astype(jnp.float32), 0.0)
        return h @ w_out.astype(jnp.float32) + x32


def step_flops(batch: int, d_model: int, d_ff: int) -> float:
    """Operations one Step needs: two matmuls, 2*M*N*K each."""
    return 2.0 * 2.0 * batch * d_model * d_ff


def step_bytes(batch: int, d_model: int, d_ff: int, itemsize: int = 2) -> float:
    """Bytes one Step must move: both weights read once, the request
    read, the response written (the hidden layer can stay on chip)."""
    return itemsize * (2.0 * d_model * d_ff + 2.0 * batch * d_model)
