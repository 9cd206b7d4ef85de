"""The lowered call's program compiled at the benchmark's size for the
chip it runs on, without the chip (a described TPU v5e 2x2): what the
TPU's compiler makes of the in-program scatter (ISSUE 34). Nothing
runs, so nothing here is a time. The topology is described inside a
fixture, never at import (one process at a time may load the TPU's
library); where it cannot be described the tests skip."""

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
