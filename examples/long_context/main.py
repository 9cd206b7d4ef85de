"""Long-context sequence parallelism: ring attention + Ulysses all-to-all
over an 8-device mesh (the framework's 'large-payload streaming' analog
— SURVEY §5: blockwise neighbor exchange over the ring of ICI links).

Runs on a virtual CPU mesh anywhere:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context/main.py
"""

import sys

sys.path.insert(0, __file__.rsplit("/examples", 1)[0])


def main(seq: int = 2048) -> None:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from brpc_tpu.ops.flash_attention import flash_attention
    from brpc_tpu.ops.ring_attention import ring_attention, ulysses_attention

    seq = int(seq)
    devs = jax.devices()
    n = len(devs)
    print(f"{n} device(s): {devs[0].platform}")
    mesh = Mesh(np.array(devs), ("shard",))

    heads, d = 8, 64
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (heads, seq, d)          # [heads, seq, head_dim]
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    ref = flash_attention(q, k, v, causal=True)

    for name, fn in (("ring", ring_attention), ("ulysses", ulysses_attention)):
        t0 = time.perf_counter()
        out = fn(mesh, q, k, v, causal=True)
        out = jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) * 1e3
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"{name:8s} seq={seq} sharded over {n}: "
              f"max|err|={err:.2e}  {dt:.1f}ms (incl. compile)")
        assert err < 2e-2, f"{name} diverged"
    print("long-context attention OK")


if __name__ == "__main__":
    main(*sys.argv[1:])
