"""The Moonlight cell's comparison (``check_probe`` of
``benchmark/services/moonlight_16b_a3b.py``, with the cell's own
margins) at the configuration's widths, on the weights the cell draws:
it passes the program as it is and fails it with the latent cache or
the operands of its matrix products in float8. Depth, vocabulary and
lengths are cut to fit the CPU (two expert layers, 512 ids, prompts of
24-40 tokens).

The files of this directory hold at most four tests each (see
test_benchmark_contract.py)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT, read_json
from benchmark.reference import moonlight_16b_a3b as reference
from benchmark.services import moonlight_16b_a3b as cell
from brpc_tpu.serving import ContinuousBatcher, GenRequest
from brpc_tpu.serving import moonlight as ml

CONF = read_json(ROOT, "benchmark", "configs", "moonlight_16b_a3b.json")
WIDE = dataclasses.replace(cell.model_config(CONF, CONF["sizes"]),
                           num_hidden_layers=3, vocab_size=512)
SEED = 2**31 + 77
PROMPTS = [24, 40, 31]
BUDGETS = [9, 6, 12]


def _serve(state):
    """Three probed generations through a batcher of two slots, the
    third admitted when a slot frees."""
    model = cell.ProbedModel(ml.MoonlightDecoder(
        WIDE, state, cache_len=64, max_prompt=40, min_bucket=32))
    b = ContinuousBatcher(model, max_batch=2, max_waiting=4)
    b.warm_up()
    rng = np.random.default_rng(SEED)
    probes, reqs = [], []
    for i, (n, budget) in enumerate(zip(PROMPTS, BUDGETS)):
        prompt = rng.integers(0, WIDE.vocab_size, n).tolist()
        probes.append(cell.Probe(i, prompt, budget, every=4))
        model.arm(probes[-1])
        reqs.append(GenRequest(prompt, budget))
        assert b.submit(reqs[-1])
    steps = 0
    while b.has_work() and steps < 100:
        b.step(0)
        steps += 1
    for p, r in zip(probes, reqs):
        assert r.state == "completed"
        p.reply = list(r.tokens)
    return probes


@pytest.fixture(scope="module")
def state():
    return reference.draw_state(WIDE, SEED)


def _float8_operands(x, w):
    f8 = jnp.float8_e4m3fn
    return jnp.matmul(x.astype(f8).astype(jnp.float32),
                      w.astype(f8).astype(jnp.float32))


@pytest.mark.parametrize("program", ["as it is", "cache in float8",
                                     "operands in float8"])
def test_the_cell_check_at_its_widths(state, program, monkeypatch):
    """The program as it is passes every probe. With its latent cache in
    float8 (4 significant bits) every probe fails; with every matrix
    product's operands in float8 every kept row leaves LOGIT_ATOL."""
    if program == "cache in float8":
        monkeypatch.setattr(ml, "LATENT_DTYPE", jnp.float8_e4m3fn)
    elif program == "operands in float8":
        monkeypatch.setattr(ml, "_mm", _float8_operands)
    probes = _serve(state)
    report = [cell.check_probe(WIDE, state, p, padded=64, block=64,
                               decodes=16) for p in probes]
    for r in report:
        assert r["fp8_reference_max_err"] > cell.LOGIT_ATOL, r
    if program == "as it is":
        assert all(r["ok"] for r in report), report
    else:
        assert not any(r["ok"] for r in report), report
    if program == "operands in float8":
        assert all(r["max_err"] > cell.LOGIT_ATOL for r in report), report
