"""What the Moonlight deployment (``services/moonlight_16b_a3b.py``)
leaves for the readers, and the operations and bytes of its programs
from the step records (the batcher's step ring: ``batch``, ``admitted``,
``tokens``, ``prefill_tokens``, ``kv_used``, ``routed``,
``prefill_routed``) and the counts of
``benchmark/reference/moonlight_16b_a3b.py``. Nothing where the
deployment did not run."""

from __future__ import annotations

import sys

from benchmark.lib.stats import median
from benchmark.reference import moonlight_16b_a3b as counts

SERVICE = "benchmark.services.moonlight_16b_a3b"


def last() -> dict:
    return getattr(sys.modules.get(SERVICE), "LAST", {})


def peaks_or_none(run):
    """The device's peaks, or None where the table has no row for it (a
    rehearsal on the CPU): no reading, never a default."""
    try:
        return run.peaks()
    except KeyError:
        return None


def decode_steps(steps: list) -> list:
    """The records of steps that ran the decode program, with the
    tokens it made (the first tokens of admissions come from prefill)."""
    out = []
    for r in steps:
        made = r["tokens"] - r["admitted"]
        if made > 0 and r.get("routed") is not None:
            out.append((r, made))
    return out


def routed_per_prefill_token(cfg, steps: list) -> float:
    """(token, held expert) pairs a prompt token made over all expert
    layers in the window; the routing's expectation where it saw none."""
    pairs = sum(sum(r["prefill_routed"]) for r in steps
                if r.get("prefill_routed"))
    toks = sum(r["prefill_tokens"] for r in steps if r.get("prefill_routed"))
    if toks:
        return pairs / toks
    return (cfg.moe_layers * cfg.num_experts_per_tok * cfg.experts_held
            / cfg.n_routed_experts)


def decode_least_us(cfg, record: dict, made: int, peaks: dict):
    """(least time of one decode step in us, its FLOP time, its byte
    time): the step's operations (absorbed form, over the live rows)
    and bytes (every weight once, the live latent rows once)."""
    flops = (made * counts.token_flops(cfg, sum(record["routed"]) / made)
             + counts.decode_attention_flops(cfg, record["kv_used"]))
    nbytes = (counts.weight_bytes(cfg)
              + record["kv_used"] * counts.latent_bytes_per_token(cfg))
    f_us = 1e6 * flops / peaks["bf16_flops_per_s"]
    b_us = 1e6 * nbytes / peaks["hbm_bytes_per_s"]
    return max(f_us, b_us), f_us, b_us


def prefill_run_costs(cfg, model, steps: list) -> dict:
    """{bucket: (operations, bytes)} of the mean prefill run in that
    bucket (the model's own counts of runs, prompt tokens and their
    squares; routing from the window's records)."""
    routed = routed_per_prefill_token(cfg, steps)
    out = {}
    for b, (runs, n_sum, n_sq) in model.prefill_counts.items():
        if not runs:
            continue
        n, n2 = n_sum / runs, n_sq / runs
        flops = (n * counts.token_flops(cfg, routed, head=False)
                 + 2.0 * cfg.hidden_size * cfg.vocab_size
                 + counts.prefill_attention_flops(cfg, n, n2))
        nbytes = (counts.weight_bytes(cfg)
                  + n * counts.latent_bytes_per_token(cfg))
        out[b] = (flops, nbytes)
    return out


def model_flops(cfg, model, steps: list) -> float:
    """The model's operations for the tokens the window's steps made:
    each decoded token through the layers with its routed pairs and its
    context's attended pairs, each prompt token through the layers, the
    head once a prompt, and the prompts' causal pairs."""
    total = 0.0
    for r, made in decode_steps(steps):
        total += (made * counts.token_flops(cfg, sum(r["routed"]) / made)
                  + counts.pair_flops(cfg) * r["kv_used"])
    runs = sum(c[0] for c in model.prefill_counts.values())
    n_sq = sum(c[2] for c in model.prefill_counts.values())
    mean_sq = n_sq / runs if runs else 0.0
    routed = routed_per_prefill_token(cfg, steps)
    for r in steps:
        if r["admitted"] and r["prefill_tokens"]:
            n = r["prefill_tokens"]
            total += (n * counts.token_flops(cfg, routed, head=False)
                      + r["admitted"] * 2.0 * cfg.hidden_size
                      * cfg.vocab_size
                      + counts.pair_flops(cfg)
                      * (r["admitted"] * mean_sq + n) / 2.0)
    return total


def median_or_none(values):
    return median(values) if values else None
