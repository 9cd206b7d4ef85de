"""Moonlight-16B-A3B behind the serving lane: the DeepSeek-V3 block
(latent attention over a latent cache, sigmoid-routed experts with
shared experts) on one chip's share of an expert-parallel deployment.

What one layer computes (``MoonlightConfig`` holds the widths):

  * **latent attention (MLA).** ``q = x W_q`` per head splits into a
    128-wide part without position and a 64-wide rotated part. The
    keys and values of every head come from ONE 512-wide latent
    ``c = RMSNorm(x W_kv_a[:512])`` and one rotated 64-wide key
    ``k_rope = RoPE(x W_kv_a[512:])`` shared by the heads. The cache
    holds those 576 numbers a token a layer (``LATENT_DTYPE``), never
    the expanded per-head keys and values;
  * **decode in the absorbed form.** ``q_nope`` goes through ``W_UK``
    into the latent (``q_lat = q_nope W_UK^T``), so a score is
    ``q_lat . c + q_rope . k_rope``: one contraction over the cached
    576-wide row. The softmax weights the cached latents and ``W_UV``
    takes the result out to the 128-wide value of each head. It is the
    same algebra as expanding K and V (``brpc_tpu/serving/reference.py``
    does that), in another order;
  * **prefill in the expanded form**: K and V are expanded from the
    latents of the prompt, then causal attention as usual;
  * **experts.** The router scores all ``n_routed_experts`` in float32
    (``sigmoid``; the correction bias steers the choice only), keeps the
    top ``num_experts_per_tok``, normalises the chosen scores and scales
    them by ``routed_scaling_factor``. The layer is told which experts
    it holds (``first_expert_held``, ``experts_held``): it computes
    their part for the tokens routed to them, sorted by expert and
    multiplied group by group (``jax.lax.ragged_dot``), and leaves out
    what experts held elsewhere would add. The shared experts (one
    SiLU-gated MLP ``n_shared_experts`` times as wide) see every token.
    Layers below ``first_k_dense_replace`` are one dense SiLU-gated MLP.

Weights are bfloat16, loaded from the published checkpoint's layout
(``load_state``). The residual stream stays float32; every matrix product takes bfloat16
operands and accumulates in float32.

The model interface the batcher drives (``serving/model.py`` has the
contract): ``new_cache``, ``prefill`` (one admitted request, on the
device, compiled once a prompt-length bucket: ``jit_moonlight_prefill_
<bucket>``) and ``decode`` (one token for every slot, compiled once:
``jit_moonlight_decode``). Both take the cache donated and write its
new rows in place. Each returns, beside the cache and the token(s),
the tokens each held expert received in each expert layer (int32
``[moe_layers, experts_held]``) and, as device arrays the batcher never
reads, the logits that chose the token(s) and the experts every token
chose, and from decode what each expert layer's router read (for
checks against the reference). Decode's aux also carries
``attn_fetched``, the cached rows its attention kernel fetched a layer
(``ops/latent_attention.fetched_rows``), which the batcher keeps in
the step record of that decode.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from brpc_tpu.ops.latent_attention import fetched_rows, latent_attention

WEIGHT_DTYPE = jnp.bfloat16
LATENT_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class MoonlightConfig:
    """Published widths (moonshotai/Moonlight-16B-A3B config.json) and
    this chip's share: ``num_hidden_layers`` held, ``vocab_size`` rows
    held, ``experts_held`` experts from ``first_expert_held`` on. The
    router keeps ``n_routed_experts`` outputs whatever is held."""

    hidden_size: int = 2048
    num_attention_heads: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.446
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 27
    vocab_size: int = 163840
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    experts_held: int = 64
    first_expert_held: int = 0

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """A cached row as stored: the latent and the rope key, zeros
        after them up to a multiple of 128. The TPU tiles the minor
        dimension by 128 lanes, so a 576-wide row is 640 wide in any
        row-major layout; left at 576, XLA gives the array a layout
        with the positions minor instead, and every program then copies
        each layer's cache into row-major and back."""
        return -(-self.latent_dim // 128) * 128

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


# ------------------------------------------------------------- weights
# Weights arrive as the published checkpoint holds them: a state dict of
# its names, ``[out, in]`` matrices, ``kv_b_proj`` per head as
# [nope ; v], the rope columns of ``q_proj`` and ``kv_a_proj_with_mqa``
# in interleaved pairs (2i, 2i + 1), each routed expert under its id.
# ``load_state`` turns them into the program's tree.

def published_shapes(cfg: MoonlightConfig) -> dict:
    """{published name: shape} of the weights this share holds."""
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    out = {"model.embed_tokens.weight": (cfg.vocab_size, h),
           "model.norm.weight": (h,), "lm_head.weight": (cfg.vocab_size, h)}
    for i in range(cfg.num_hidden_layers):
        n = f"model.layers.{i}."
        out.update({
            n + "input_layernorm.weight": (h,),
            n + "self_attn.q_proj.weight": (nh * (dn + dr), h),
            n + "self_attn.kv_a_proj_with_mqa.weight": (kvr + dr, h),
            n + "self_attn.kv_a_layernorm.weight": (kvr,),
            n + "self_attn.kv_b_proj.weight": (nh * (dn + dv), kvr),
            n + "self_attn.o_proj.weight": (h, nh * dv),
            n + "post_attention_layernorm.weight": (h,)})
        if i < cfg.first_k_dense_replace:
            mlps = {"mlp.": cfg.intermediate_size}
        else:
            out[n + "mlp.gate.weight"] = (cfg.n_routed_experts, h)
            out[n + "mlp.gate.e_score_correction_bias"] = (
                cfg.n_routed_experts,)
            mlps = {"mlp.shared_experts.":
                    cfg.moe_intermediate_size * cfg.n_shared_experts}
            for e in range(cfg.first_expert_held,
                           cfg.first_expert_held + cfg.experts_held):
                mlps[f"mlp.experts.{e}."] = cfg.moe_intermediate_size
        for pre, width in mlps.items():
            out[n + pre + "gate_proj.weight"] = (width, h)
            out[n + pre + "up_proj.weight"] = (width, h)
            out[n + pre + "down_proj.weight"] = (h, width)
    return out


def _rope_halves(w, axis: int):
    """Rope rows in interleaved pairs (2i, 2i + 1) reordered as the
    program pairs them (i, i + d/2): the evens, then the odds."""
    d = w.shape[axis]
    return jnp.concatenate([jax.lax.slice_in_dim(w, 0, d, 2, axis),
                            jax.lax.slice_in_dim(w, 1, d, 2, axis)], axis)


def _to_params(state: dict, cfg: MoonlightConfig) -> dict:
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    dn, dv, kvr = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

    def swiglu(pre):
        return {"gate_up": jnp.concatenate(
                    [state[pre + "gate_proj.weight"].T,
                     state[pre + "up_proj.weight"].T], -1),
                "down": state[pre + "down_proj.weight"].T}

    layers = []
    for i in range(cfg.num_hidden_layers):
        n = f"model.layers.{i}."
        a = n + "self_attn."
        q = state[a + "q_proj.weight"].reshape(nh, -1, h)
        q = jnp.concatenate([q[:, :dn], _rope_halves(q[:, dn:], 1)], 1)
        kv_a = state[a + "kv_a_proj_with_mqa.weight"]
        kv_a = jnp.concatenate([kv_a[:kvr], _rope_halves(kv_a[kvr:], 0)], 0)
        kv_b = state[a + "kv_b_proj.weight"].reshape(nh, dn + dv, kvr)
        p = {"attn_norm": state[n + "input_layernorm.weight"],
             "q_proj": q.reshape(-1, h).T,
             "kv_a": kv_a.T,
             "kv_norm": state[a + "kv_a_layernorm.weight"],
             # kv_b_proj's rows split by what they make: per head the
             # key without position (W_UK) and the value (W_UV)
             "w_uk": kv_b[:, :dn].transpose(2, 0, 1),
             "w_uv": kv_b[:, dn:].transpose(2, 0, 1),
             "o_proj": state[a + "o_proj.weight"].T,
             "mlp_norm": state[n + "post_attention_layernorm.weight"]}
        if i < cfg.first_k_dense_replace:
            p["dense"] = swiglu(n + "mlp.")
        else:
            p["router"] = state[n + "mlp.gate.weight"].T
            p["router_bias"] = state[n + "mlp.gate.e_score_correction_bias"]
            held = [swiglu(f"{n}mlp.experts.{e}.")
                    for e in range(cfg.first_expert_held,
                                   cfg.first_expert_held + cfg.experts_held)]
            p["experts"] = {k: jnp.stack([e[k] for e in held])
                            for k in ("gate_up", "down")}
            p["shared"] = swiglu(n + "mlp.shared_experts.")
        layers.append(p)
    return {"embed": state["model.embed_tokens.weight"], "layers": layers,
            "final_norm": state["model.norm.weight"],
            "head": state["lm_head.weight"].T}


def load_state(state: dict, cfg: MoonlightConfig) -> dict:
    """The program's parameter tree from weights in the published layout
    (``published_shapes`` names them), on the device they are on."""
    missing = set(published_shapes(cfg)) - set(state)
    if missing:
        raise KeyError(f"the state lacks {len(missing)} weights, e.g. "
                       f"{sorted(missing)[0]}")
    return jax.jit(functools.partial(_to_params, cfg=cfg))(
        {k: state[k] for k in published_shapes(cfg)})


def param_count(params) -> int:
    """Parameters held, norms and the router bias included."""
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))


# ------------------------------------------------------------ the block

def _mm(x, w):
    """bfloat16 operands, float32 accumulation."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotary embedding over the last axis, dimension i paired with
    i + d/2; ``pos`` broadcasts against ``x``'s leading axes."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w):
    gu = _mm(x, w["gate_up"])
    g, u = jnp.split(gu, 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, w["down"])


def _latent_rows(a, p, cfg, pos):
    """The cache rows of tokens ``a`` (normed, [T, H]) at ``pos``: the
    normed latent and the rotated shared key, [T, latent_row]."""
    kv = _mm(a, p["kv_a"])
    c = _rms(kv[:, :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
    kr = rope(kv[:, cfg.kv_lora_rank:], pos, cfg.rope_theta)
    pad = jnp.zeros(c.shape[:-1] + (cfg.latent_row - cfg.latent_dim,),
                    c.dtype)
    return jnp.concatenate([c, kr, pad], -1).astype(LATENT_DTYPE)


def _queries(a, p, cfg, pos):
    t = a.shape[0]
    q = _mm(a, p["q_proj"]).reshape(t, cfg.num_attention_heads, -1)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = rope(q[..., cfg.qk_nope_head_dim:], pos[:, None],
                  cfg.rope_theta)
    return q_nope, q_rope


def _latent_query(q_lat, q_rope, width):
    """A query in the latent as one row against the stored rows:
    [q_lat ; q_rope ; zeros] (the padding meets zeros), so a score is
    q_lat . c + q_rope . k_rope in one contraction."""
    pad = width - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (pad,), q_rope.dtype)],
        -1).astype(LATENT_DTYPE)


def _attend_decode(a, p, cfg, cache_l, pos, live):
    """One query a slot over its cached rows [0, pos] after its own new
    row is written at ``pos`` (idle slots write nothing), absorbed form.
    Returns (attention output [B, H], the cache with the rows)."""
    row = _latent_rows(a, p, cfg, pos)                       # [B, 640]
    slots = jnp.arange(row.shape[0])
    at = jnp.where(live, pos, cache_l.shape[1])              # idle: dropped
    cache_l = cache_l.at[slots, at].set(row, mode="drop")
    q_nope, q_rope = _queries(a, p, cfg, pos)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope.astype(WEIGHT_DTYPE),
                       p["w_uk"], preferred_element_type=jnp.float32)
    o = latent_attention(_latent_query(q_lat, q_rope, cache_l.shape[-1]),
                         cache_l, jnp.where(live, pos + 1, 0),
                         scale=cfg.softmax_scale, kvr=cfg.kv_lora_rank)
    out = jnp.einsum("bhc,chd->bhd", o.astype(WEIGHT_DTYPE), p["w_uv"],
                     preferred_element_type=jnp.float32)
    out = jnp.where(live[:, None, None], out, 0.0)
    return _mm(out.reshape(out.shape[0], -1), p["o_proj"]), cache_l


def _expanded_scores(q_nope, k_nope, q_rope, k_rope):
    """Scores of prompt queries against the expanded keys: per head
    q_nope . k_nope plus q_rope . k_rope (the rope key shared)."""
    return (jnp.einsum("qhd,khd->hqk", q_nope.astype(WEIGHT_DTYPE),
                       k_nope.astype(WEIGHT_DTYPE),
                       preferred_element_type=jnp.float32)
            + jnp.einsum("qhd,kd->hqk", q_rope.astype(WEIGHT_DTYPE),
                         k_rope.astype(WEIGHT_DTYPE),
                         preferred_element_type=jnp.float32))


def _attend_prefill(a, p, cfg, pos):
    """Causal attention over the prompt, keys and values expanded per
    head from the latent rows as stored. Returns (output, rows)."""
    kvr = cfg.kv_lora_rank
    rows = _latent_rows(a, p, cfg, pos)                      # [T, 640]
    q_nope, q_rope = _queries(a, p, cfg, pos)
    c, kr = rows[:, :kvr], rows[:, kvr:cfg.latent_dim]
    k_nope = jnp.einsum("tc,chd->thd", c.astype(WEIGHT_DTYPE), p["w_uk"],
                        preferred_element_type=jnp.float32)
    v = jnp.einsum("tc,chd->thd", c.astype(WEIGHT_DTYPE), p["w_uv"],
                   preferred_element_type=jnp.float32)
    s = _expanded_scores(q_nope, k_nope, q_rope, kr) * cfg.softmax_scale
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w.astype(WEIGHT_DTYPE),
                   v.astype(WEIGHT_DTYPE),
                   preferred_element_type=jnp.float32)
    return _mm(o.reshape(o.shape[0], -1), p["o_proj"]), rows


def route(x, router, bias, cfg):
    """The router over all ``n_routed_experts``, in float32 as the
    published gate computes it: (weights [T, k], experts [T, k])."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, experts.astype(jnp.int32)


def held_experts(x, weights, experts, valid, w, first: int, held: int):
    """What the held experts ``first .. first + held - 1`` add for the
    tokens routed to them, and how many tokens each got.

    The (token, choice) pairs are sorted by expert, those of experts
    held elsewhere (and of tokens not ``valid``) last; each held
    expert's rows are multiplied by its own weights with
    ``ragged_dot``, and the pairs held elsewhere add nothing."""
    t, k = experts.shape
    local = experts - first
    mine = (local >= 0) & (local < held) & valid[:, None]
    flat = jnp.where(mine, local, held).reshape(-1)
    counts = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
    order = jnp.argsort(flat, stable=True)
    tok = order // k
    gu = jax.lax.ragged_dot(x.astype(WEIGHT_DTYPE)[tok], w["gate_up"],
                            counts, preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    hid = (jax.nn.silu(g) * u).astype(WEIGHT_DTYPE)
    y = jax.lax.ragged_dot(hid, w["down"], counts,
                           preferred_element_type=jnp.float32)
    keep = jnp.arange(t * k) < jnp.sum(counts)
    y = jnp.where(keep[:, None], y * weights.reshape(-1)[order][:, None], 0.0)
    return jnp.zeros((t, x.shape[-1]), jnp.float32).at[tok].add(y), counts


def _mlp(m, p, cfg, valid):
    """(output, tokens per held expert or None, experts chosen or None)."""
    if "dense" in p:
        return _swiglu(m, p["dense"]), None, None
    weights, experts = route(m, p["router"], p["router_bias"], cfg)
    out, counts = held_experts(m, weights, experts, valid, p["experts"],
                               cfg.first_expert_held, cfg.experts_held)
    return out + _swiglu(m, p["shared"]), counts, experts


def _logits(h, params, cfg):
    return _mm(_rms(h, params["final_norm"], cfg.rms_norm_eps),
               params["head"])


def prefill_program(params, cache, slot, tokens, n, *, cfg):
    """One prompt (``tokens`` padded to the bucket, ``n`` real) into
    slot ``slot`` of the donated cache. Returns (cache, first token,
    tokens per held expert [moe_layers, held], {"logits": [vocab] of the
    last prompt position, "experts": [moe_layers, bucket, k]})."""
    eps = cfg.rms_norm_eps
    pos = jnp.arange(tokens.shape[0])
    valid = pos < n
    h = params["embed"][tokens].astype(jnp.float32)
    cache = list(cache)
    counts, chosen = [], []
    for i, p in enumerate(params["layers"]):
        o, rows = _attend_prefill(_rms(h, p["attn_norm"], eps), p, cfg, pos)
        cache[i] = jax.lax.dynamic_update_slice(
            cache[i], rows[None], (slot, jnp.int32(0), jnp.int32(0)))
        h = h + o
        y, c, e = _mlp(_rms(h, p["mlp_norm"], eps), p, cfg, valid)
        h = h + y
        if c is not None:
            counts.append(c)
            chosen.append(e)
    last = jax.lax.dynamic_index_in_dim(h, n - 1, keepdims=False)
    logits = _logits(last, params, cfg)
    return (tuple(cache), jnp.argmax(logits).astype(jnp.int32),
            _stack(counts, (0, cfg.experts_held)),
            {"logits": logits,
             "experts": _stack(chosen, (0,) + tokens.shape
                               + (cfg.num_experts_per_tok,))})


def decode_program(params, cache, last_tokens, lengths, *, cfg):
    """One token for every slot. ``lengths[b]`` rows are cached for slot
    b and ``last_tokens[b]`` sits at position ``lengths[b]``; its row is
    written there. A slot with ``lengths < 0`` is idle: nothing is
    written or routed for it and its outputs are garbage."""
    eps = cfg.rms_norm_eps
    live = lengths >= 0
    pos = jnp.maximum(lengths, 0)
    h = params["embed"][last_tokens].astype(jnp.float32)
    cache = list(cache)
    counts, chosen, router_in = [], [], []
    for i, p in enumerate(params["layers"]):
        o, cache[i] = _attend_decode(_rms(h, p["attn_norm"], eps), p, cfg,
                                     cache[i], pos, live)
        h = h + o
        m = _rms(h, p["mlp_norm"], eps)
        y, c, e = _mlp(m, p, cfg, live)
        h = h + y
        if c is not None:
            counts.append(c)
            chosen.append(e)
            router_in.append(m)
    logits = _logits(h, params, cfg)
    return (tuple(cache), jnp.argmax(logits, axis=-1).astype(jnp.int32),
            _stack(counts, (0, cfg.experts_held)),
            {"logits": logits,
             "experts": _stack(chosen, (0,) + last_tokens.shape
                               + (cfg.num_experts_per_tok,)),
             "router_in": _stack(router_in, (0,) + last_tokens.shape
                                 + (cfg.hidden_size,), jnp.float32),
             "attn_fetched": fetched_rows(jnp.where(live, pos + 1, 0),
                                          cache[0].shape[1])})


def _stack(arrays, empty_shape, dtype=jnp.int32):
    if arrays:
        return jnp.stack(arrays)
    return jnp.zeros(empty_shape, dtype)


def _named(fn, name: str):
    """``fn`` under ``name``: jit names the program ``jit_<name>``, which
    is what a profiler trace is searched for."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


def prompt_buckets(min_bucket: int, max_prompt: int,
                   cache_len: int) -> Tuple[int, ...]:
    """Powers of two from ``min_bucket`` until one holds ``max_prompt``,
    none past ``cache_len``."""
    out, b = [], int(min_bucket)
    while True:
        out.append(min(b, cache_len))
        if b >= max_prompt or b >= cache_len:
            return tuple(out)
        b *= 2


class MoonlightDecoder:
    """The model the batcher drives: weights on the device, one decode
    program, one prefill program a bucket."""

    def __init__(self, config: MoonlightConfig, state: dict, *,
                 cache_len: int = 4096,
                 max_prompt: Optional[int] = None, min_bucket: int = 256,
                 device=None):
        self.config = cfg = config
        self.vocab = cfg.vocab_size
        self.cache_len = int(cache_len)
        self.max_prompt = int(max_prompt if max_prompt is not None
                              else cache_len // 2)
        if not 0 < self.max_prompt < self.cache_len:
            raise ValueError(f"max_prompt {self.max_prompt} must lie in "
                             f"(0, cache_len {self.cache_len})")
        self.device = device
        # ``state``: the weights in the published layout (``load_state``)
        self.params = load_state(state, cfg)
        self.buckets = prompt_buckets(min_bucket, self.max_prompt,
                                      self.cache_len)
        self._decode = jax.jit(
            _named(functools.partial(decode_program, cfg=cfg),
                   "moonlight_decode"), donate_argnums=(1,))
        self._prefill = {
            b: jax.jit(_named(functools.partial(prefill_program, cfg=cfg),
                              f"moonlight_prefill_{b}"),
                       donate_argnums=(1,))
            for b in self.buckets}
        # prefill runs, prompt tokens and their squares, by bucket (the
        # prefill roofline's weights; host ints, known at the call)
        self.prefill_counts = {b: [0, 0, 0] for b in self.buckets}

    # --------------------------------------------------- model interface
    def new_cache(self, max_batch: int, cache_len: int):
        """Per layer one [max_batch, cache_len, latent_row] array, placed
        on the weights' device as the programs' outputs are, so that
        each program is compiled once (an array merely made there would
        be a new signature to the first program it meets)."""
        shape = (int(max_batch), int(cache_len), self.config.latent_row)
        device = self.device or next(iter(self.params["embed"].devices()))
        return jax.jit(lambda: tuple(
            jnp.zeros(shape, LATENT_DTYPE)
            for _ in range(self.config.num_hidden_layers)),
            out_shardings=jax.sharding.SingleDeviceSharding(device))()

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens is longer than the "
                         f"largest bucket {self.buckets[-1]}")

    def prefill(self, cache, slot: int, tokens: Sequence[int]):
        n = len(tokens)
        b = self.bucket_for(n)
        padded = np.zeros((b,), np.int32)
        padded[:n] = tokens
        row = self.prefill_counts[b]
        row[0] += 1
        row[1] += n
        row[2] += n * n
        return self._prefill[b](self.params, cache, np.int32(slot), padded,
                                np.int32(n))

    def decode(self, cache, last_tokens, lengths):
        return self._decode(self.params, cache,
                            np.asarray(last_tokens, np.int32),
                            np.asarray(lengths, np.int32))

    def warm_up(self, cache):
        """Compile every program the batcher will run (each bucket's
        prefill into slot 0, one decode with every slot idle)."""
        batch = cache[0].shape[0]
        out = None
        for b in self.buckets:
            cache, *out = self._prefill[b](
                self.params, cache, np.int32(0), np.zeros((b,), np.int32),
                np.int32(1))
        cache, *out = self.decode(cache, np.zeros((batch,), np.int32),
                                  np.full((batch,), -1, np.int32))
        jax.block_until_ready(out)
        return cache
