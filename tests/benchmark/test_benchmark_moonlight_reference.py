"""The benchmark's copy of the Moonlight reference
(``benchmark/reference/moonlight_16b_a3b.py``) and the program's
(``brpc_tpu/serving/reference.py``) compute the same forward pass, and
the copy's counts of parameters and bytes match the configuration the
cell runs; the cell's router check, at the configuration's widths,
passes the program's float32 router and fails it in bfloat16.

The files of this directory hold at most four tests each (see
test_benchmark_contract.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT, read_json
from benchmark.reference import moonlight_16b_a3b as copy
from benchmark.services import moonlight_16b_a3b as cell
from benchmark.services.moonlight_16b_a3b import model_config
from brpc_tpu.serving import moonlight as ml
from brpc_tpu.serving import reference as program_ref

SMALL = ml.MoonlightConfig(
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=3, n_shared_experts=1, num_hidden_layers=3,
    vocab_size=512, experts_held=4, first_expert_held=8)
CONF = read_json(ROOT, "benchmark", "configs", "moonlight_16b_a3b.json")
# the cell's widths, two expert layers and 512 ids
WIDE = dataclasses.replace(model_config(CONF, CONF["sizes"]),
                           num_hidden_layers=3, vocab_size=512)


def test_the_two_references_agree():
    """On the weights the cell draws (the published layout), the two
    references give the same logits and routing verdicts, and the
    program loads those weights."""
    state = copy.draw_state(SMALL, 2**31 + 9)
    assert set(state) == set(ml.published_shapes(SMALL))
    assert {k: v.shape for k, v in state.items()} == copy.state_shapes(SMALL)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 512, 40),
                         jnp.int32)
    a = program_ref.forward(state, SMALL, tokens, block=16)
    b = copy.forward(state, SMALL, tokens, block=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                               atol=1e-5)
    chosen = jnp.zeros((2, 40, 3), jnp.int32)
    ra = program_ref.forward(state, SMALL, tokens, valid=30, chosen=chosen,
                             tie_eps=0.01, block=16)
    rb = copy.forward(state, SMALL, tokens, valid=30, chosen=chosen,
                      tie_eps=0.01, block=16)
    np.testing.assert_allclose(np.asarray(ra[0]), np.asarray(rb[0]),
                               rtol=0, atol=1e-5)
    assert [int(x) for x in ra[1:3]] == [int(x) for x in rb[1:3]]
    x = jax.random.normal(jax.random.key(2), (2, 40, SMALL.hidden_size))
    assert [float(v) for v in program_ref.router_check(
        state, SMALL, x, chosen, 30, 0.01)] == [float(v) for v in
                                                copy.router_check(
        state, SMALL, x, chosen, 30, 0.01)]
    # the copy in float8 operands is the same pass in a lower precision
    low = copy.forward(state, SMALL, tokens, block=16,
                       operand_dtype=jnp.float8_e4m3fn)
    err = float(jnp.max(jnp.abs(low - b)))
    assert 0.05 < err < 5.0, err
    assert ml.load_state(state, SMALL)["layers"][1]["experts"][
        "gate_up"].shape == (4, 64, 64)


def test_counts_match_the_cell_configuration():
    """At the cell's widths and share: 970,063,872 parameters held, of
    which the embedding's 41,943,040 are not read by a step; 10,368 B of
    latent cache a token; the program holds the weights the benchmark
    draws, name for name."""
    cfg = model_config(CONF, CONF["sizes"])
    assert (cfg.hidden_size, cfg.n_routed_experts, cfg.experts_held,
            cfg.num_hidden_layers, cfg.vocab_size) == (2048, 64, 8, 9, 20480)
    shapes = copy.state_shapes(cfg)
    assert shapes == ml.published_shapes(cfg)
    matrices = sum(int(np.prod(s)) for s in shapes.values() if len(s) > 1)
    assert matrices == 970_063_872
    vectors = sum(int(np.prod(s)) for s in shapes.values() if len(s) == 1)
    bias = cfg.moe_layers * cfg.n_routed_experts
    assert copy.weight_bytes(cfg) == 2 * (matrices - 41_943_040
                                          + vectors - bias) + 4 * bias
    assert copy.latent_bytes_per_token(cfg) == 10_368


@pytest.mark.parametrize("router", ["float32", "bfloat16"])
def test_the_router_check_at_the_cell_widths(router):
    """The program's router (64 outputs over a 2,048-wide input, top 6)
    against the reference router on the same inputs with the cell's
    margin: the float32 router agrees at every position; a router that
    reads its input in bfloat16 chooses otherwise and is caught."""
    gates = {k: v for k, v in copy.state_shapes(WIDE).items()
             if ".mlp.gate." in k}
    state = copy._draw(jax.random.key(77), gates)
    x = jax.random.normal(jax.random.key(5),
                          (WIDE.moe_layers, 4096, WIDE.hidden_size))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    if router == "bfloat16":
        x_in = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        x_in = x
    chosen = []
    for j in range(WIDE.moe_layers):
        n = copy.layer_prefix(WIDE.first_k_dense_replace + j) + "mlp.gate."
        chosen.append(ml.route(x_in[j], state[n + "weight"].T,
                               state[n + "e_score_correction_bias"],
                               WIDE)[1])
    chosen = jnp.stack(chosen)
    mism, _ties, _split = cell._jitted("router_check", WIDE,
                                       eps=cell.ROUTER_EPS)(
        state, router_in=x, chosen=chosen, valid=np.int32(4096))
    if router == "float32":
        assert int(mism) == 0
    else:
        assert int(mism) > 0
