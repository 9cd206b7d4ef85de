"""Plain float32 reference of the Moonlight-16B-A3B forward pass
(``serving/moonlight.py``): the whole sequence at once, no cache, no
kernels, no batching, every matrix product at precision "highest".

It reads the weights as the published checkpoint holds them: a state
dict of its names (``model.layers.<i>.self_attn.kv_b_proj.weight``
...), ``[out, in]`` matrices, ``kv_b_proj`` per head as [nope ; v], the
rope columns of ``q_proj`` and ``kv_a_proj_with_mqa`` in interleaved
pairs, each routed expert under its published id (bfloat16 weights,
computed here in float32). The program loads the same dict into its
own layout (``moonlight.load_state``).

It follows the published DeepSeek-V3 description (the modelling code
of the published checkpoint), with these departures:

  * attention in the EXPANDED form the DeepSeek-V2 paper writes: per
    head ``k = [c W_UK ; k_rope]`` and ``v = c W_UV`` from the normed
    latent ``c``, then causal softmax attention with scale
    ``(qk_nope + qk_rope)^-0.5``. No YaRN: the published config lists
    no ``rope_scaling``;
  * weights, norms and ``e_score_correction_bias`` are drawn at random
    from a seed by the caller, not read from the checkpoint;
  * the expert layer is one chip's share: routing is over all
    ``n_routed_experts`` (sigmoid scores in float32, the bias in the
    choice only, top ``num_experts_per_tok``, chosen scores normalised
    and scaled by ``routed_scaling_factor``), and the output is the
    held experts' part, computed DENSELY (every held expert on every
    token, weighted by its routing weight, 0 where not chosen), plus
    the shared experts. What experts held elsewhere add is absent, in
    the program alike;
  * the vocabulary is the slice held: embedding rows and head columns.

``forward`` can be handed the experts a program chose at every
position (``chosen``). Where the program's set differs from the
reference's only between experts whose choice scores lie within
``tie_eps`` (a near-tie broken the other way by rounding), the
program's choice is taken; any other difference is counted.
``router_check`` holds the program's router to the reference's on the
program's own router inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(w):
    return w.astype(jnp.float32)


def layer_prefix(i: int) -> str:
    return f"model.layers.{i}."


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, pos, theta):
    """Rotary embedding as published: dimensions 2i and 2i + 1 a pair."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _linear(x, w):
    """``x W^T`` of a published ``[out, in]`` matrix."""
    return x @ _f32(w).T


def _swiglu(x, s, pre):
    g = _linear(x, s[pre + "gate_proj.weight"])
    u = _linear(x, s[pre + "up_proj.weight"])
    return _linear(jax.nn.silu(g) * u, s[pre + "down_proj.weight"])


def attention(x, s, i, cfg, block: int):
    """Causal MLA of layer ``i`` over the whole sequence, queries in
    blocks of ``block`` (keys and values whole)."""
    n = layer_prefix(i) + "self_attn."
    t = x.shape[0]
    nh, dn, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.v_head_dim)
    kvr, theta = cfg.kv_lora_rank, cfg.rope_theta
    pos = jnp.arange(t)
    q = _linear(x, s[n + "q_proj.weight"]).reshape(t, nh, -1)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos[:, None], theta)
    kv = _linear(x, s[n + "kv_a_proj_with_mqa.weight"])
    c = _rms(kv[:, :kvr], s[n + "kv_a_layernorm.weight"], cfg.rms_norm_eps)
    k_rope = _rope(kv[:, kvr:], pos, theta)
    kv_b = _f32(s[n + "kv_b_proj.weight"]).reshape(nh, dn + dv, kvr)
    k_nope = jnp.einsum("tc,hdc->thd", c, kv_b[:, :dn])
    v = jnp.einsum("tc,hdc->thd", c, kv_b[:, dn:])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (t, nh, k_rope.shape[-1]))],
        -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    outs = []
    for s0 in range(0, t, block):
        sc = jnp.einsum("qhd,khd->hqk", qf[s0:s0 + block], k) * scale
        causal = pos[None, :] <= pos[s0:s0 + block, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    o = jnp.concatenate(outs, 0).reshape(t, -1)
    return _linear(o, s[n + "o_proj.weight"])


def routing(x, s, i, cfg):
    """(choice scores [T, E]: sigmoid scores plus the correction bias,
    the top k+1 experts by them [T, k+1], descending) of layer ``i``."""
    n = layer_prefix(i) + "mlp.gate."
    choice = jax.nn.sigmoid(x @ _f32(s[n + "weight"]).T) \
        + s[n + "e_score_correction_bias"]
    _, top = jax.lax.top_k(choice, cfg.num_experts_per_tok + 1)
    return choice, top


def expert_layer(x, s, i, cfg, experts, held_range=None, shared=True):
    """Layer ``i``'s held experts' part for the tokens that chose them
    (``experts`` [T, k]), dense, plus the shared experts. ``held_range``
    (first, count) defaults to the configuration's share."""
    n = layer_prefix(i) + "mlp."
    first, held = held_range or (cfg.first_expert_held, cfg.experts_held)
    scores = jax.nn.sigmoid(x @ _f32(s[n + "gate.weight"]).T)
    w = jnp.take_along_axis(scores, experts, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    out = _swiglu(x, s, n + "shared_experts.") if shared \
        else jnp.zeros_like(x)
    for e in range(first, first + held):
        gate = jnp.sum(jnp.where(experts == e, w, 0.0), -1)
        out = out + gate[:, None] * _swiglu(x, s, f"{n}experts.{e}.")
    return out


def compare_choice(choice, top, theirs, real, eps: float):
    """The program's experts ``theirs`` [T, k] against the reference's
    choice scores ``choice`` [T, E] (``top`` [T, k+1]: the reference's
    top k+1). A set that differs is accepted where it is the top k of
    the reference's scores moved by under ``eps`` / 2 each: every expert
    the program chose scores within ``eps`` of every expert it left out
    (a near-tie broken the other way by rounding). Returns (mismatches,
    near-ties: the k-th and (k+1)-th scores within ``eps``, the widest
    such inversion where the sets differ, accepted or not, the experts
    to go on with)."""
    k = theirs.shape[-1]
    mine = top[:, :k]
    ranked = jnp.take_along_axis(choice, top, -1)
    tie = ranked[:, k - 1] - ranked[:, k] < eps
    chose = jnp.any(jnp.arange(choice.shape[-1])[None, :, None]
                    == theirs[:, None, :], -1)
    inversion = (jnp.max(jnp.where(chose, -jnp.inf, choice), -1)
                 - jnp.min(jnp.where(chose, choice, jnp.inf), -1))
    same = jnp.all(jnp.sort(mine, -1) == jnp.sort(theirs, -1), -1)
    took = ~same & (inversion < eps)
    return (jnp.sum(real & ~same & ~took), jnp.sum(real & tie),
            jnp.max(jnp.where(real & ~same, inversion, 0.0)),
            jnp.where(took[:, None], theirs, mine))


def forward(state, cfg, tokens, *, valid=None, chosen=None,
            tie_eps: float = 0.0, block: int = 512):
    """Logits [T, vocab] of every position of ``tokens`` [T].

    ``valid`` (how many leading positions are real; the rest pad the
    sequence to a fixed shape) and ``chosen`` ([moe_layers, T, k], the
    program's experts) make it also return, over real positions and
    expert layers, the count of positions whose experts differ beyond a
    near-tie, the count of near-ties (k-th and (k+1)-th choice scores
    within ``tie_eps``), and the widest inversion of the reference's
    scores where the program chose otherwise (``compare_choice``):
    (logits, mismatches, near_ties, widest_split)."""
    s = state
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        real = jnp.arange(t) < (t if valid is None else valid)
        h = _f32(s["model.embed_tokens.weight"][tokens])
        mismatches = jnp.int32(0)
        near_ties = jnp.int32(0)
        widest = jnp.float32(0.0)
        eps = cfg.rms_norm_eps
        for i in range(cfg.num_hidden_layers):
            n = layer_prefix(i)
            h = h + attention(_rms(h, s[n + "input_layernorm.weight"], eps),
                              s, i, cfg, block)
            m = _rms(h, s[n + "post_attention_layernorm.weight"], eps)
            if i < cfg.first_k_dense_replace:
                h = h + _swiglu(m, s, n + "mlp.")
                continue
            choice, top = routing(m, s, i, cfg)
            mine = top[:, :cfg.num_experts_per_tok]
            if chosen is not None:
                mi, ti, wi, mine = compare_choice(
                    choice, top, chosen[i - cfg.first_k_dense_replace],
                    real, tie_eps)
                mismatches += mi
                near_ties += ti
                widest = jnp.maximum(widest, wi)
            h = h + expert_layer(m, s, i, cfg, mine)
        logits = _linear(_rms(h, s["model.norm.weight"], eps),
                         s["lm_head.weight"])
    if chosen is None and valid is None:
        return logits
    return logits, mismatches, near_ties, widest


def router_check(state, cfg, router_in, chosen, valid, eps: float):
    """The program's router on its own inputs: ``router_in``
    [moe_layers, T, hidden] (what each expert layer's router read, the
    first ``valid`` positions real) and the experts the program chose
    there, ``chosen`` [moe_layers, T, k], against the reference's
    routing of the same inputs. Returns (mismatches, near-ties, widest
    inversion) as ``compare_choice`` counts them with margin ``eps``."""
    real = jnp.arange(router_in.shape[1]) < valid
    out = [jnp.int32(0), jnp.int32(0), jnp.float32(0.0)]
    with jax.default_matmul_precision("highest"):
        for j in range(cfg.moe_layers):
            i = cfg.first_k_dense_replace + j
            choice, top = routing(router_in[j], state, i, cfg)
            mi, ti, wi, _ = compare_choice(choice, top, chosen[j], real, eps)
            out = [out[0] + mi, out[1] + ti, jnp.maximum(out[2], wi)]
    return tuple(out)
