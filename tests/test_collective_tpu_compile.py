"""The lowered call's program compiled at the benchmark's size for the
chip it runs on, without the chip (a described TPU v5e 2x2): what the
TPU's compiler makes of the in-program scatter (ISSUE 34). Nothing
runs, so nothing here is a time. The topology is described inside a
fixture, never at import (one process at a time may load the TPU's
library); where it cannot be described the tests skip.

Also Moonlight's decode program at its cell's size, whose latent cache
must stay where it is: donated, written in place, read by the kernel
without a copy."""

import os

import pytest

N = 4
ROWS, COLS = 2048, 1024         # collective_4mb_d1's shard block, bf16


@pytest.fixture(scope="module")
def mesh():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from brpc_tpu.parallel import make_rpc_mesh
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        return make_rpc_mesh(1, N, devices=list(topo.devices))
    except Exception as e:  # noqa: BLE001 - no compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("src", [0, 2])
@pytest.mark.parametrize("merge", ["sum", "concat"])
def test_the_chips_module_holds_one_all_to_all(mesh, merge, src):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from brpc_tpu.parallel import CollectiveChannel

    coll = CollectiveChannel(mesh, merge=merge)
    fn = coll._lower(lambda s: s * 2, merge, "collective_Mesh_Shard", src)
    placed = jax.ShapeDtypeStruct(
        (N * N * ROWS, COLS), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("shard")))
    compiled = fn.lower(placed).compile()
    text = compiled.as_text()

    def count(op):
        return sum(1 for line in text.splitlines()
                   if f" {op}(" in line or f" {op}-start(" in line)

    assert count("all-to-all") == 1
    assert "collective-permute" not in text
    assert count("all-reduce") == (1 if merge == "sum" else 0)
    # the blocks stay bf16 on the wire
    a2a = next(line for line in text.splitlines() if " all-to-all(" in line)
    assert f"bf16[{N},{ROWS},{COLS}]" in a2a.split("all-to-all(")[0]
    # every chip is handed a request-sized argument (16 MB)
    assert compiled.memory_analysis().argument_size_in_bytes \
        == N * ROWS * COLS * 2


@pytest.fixture(scope="module")
def chip(mesh):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(mesh.devices.flat[0])


def test_moonlight_decode_keeps_its_cache_in_place(chip, monkeypatch):
    """The decode step at ``moonlight_16b_a3b.decode_d192_ctx4k``'s size
    (192 slots x 4,096 positions, 9 layers): the 9.06 GB cache is
    aliased to the output, the temporaries are a small fraction of one
    layer's cache (a layout copy of the cache would be 1 GB), and the
    attention is the Pallas kernel."""
    import functools

    import jax
    import jax.numpy as jnp

    from brpc_tpu.serving import moonlight as ml

    # the program asks the backend to choose the kernel over its
    # interpreter; here the backend is the CPU, the target the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ml.MoonlightConfig(num_hidden_layers=9, vocab_size=20480,
                             experts_held=8)
    slots, positions = 192, 4096
    state = {name: jax.ShapeDtypeStruct(
                 shape, jnp.float32 if name.endswith("bias")
                 else ml.WEIGHT_DTYPE)
             for name, shape in ml.published_shapes(cfg).items()}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        jax.eval_shape(functools.partial(ml._to_params, cfg=cfg), state))
    layer = jax.ShapeDtypeStruct((slots, positions, cfg.latent_row),
                                 ml.LATENT_DTYPE, sharding=chip)
    cache = (layer,) * cfg.num_hidden_layers
    ids = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    decode = jax.jit(functools.partial(ml.decode_program, cfg=cfg),
                     donate_argnums=(1,))
    compiled = decode.lower(params, cache, ids, ids).compile()
    mem = compiled.memory_analysis()
    layer_bytes = slots * positions * cfg.latent_row * 2
    assert mem.alias_size_in_bytes == cfg.num_hidden_layers * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes // 8
    assert "tpu_custom_call" in compiled.as_text()
