"""sarvam-105b (HF ``model_type: sarvam_mla``; the DeepSeek-V2/V3 block shape)
in plain float32, one chip's share of the experts. A pre-norm residual block
of RMSNorm, multi-head latent attention and a feed-forward; the leading
layer's feed-forward is a dense SwiGLU, the others' is sparse; no biases but
the router's, an untied output head.

Written out here and read from no flag:

- **Latent attention, expanded form only.** ``q = W_Q x`` per head is a
  no-position part (128) and a rotary part (64); ``[c ‖ k_r] = W_DKV x``
  (512 + 64), ``c`` through an RMSNorm with a learned scale, ``k_r`` — one
  rotary key shared by all heads — rotated; per head ``[k_nope ‖ v] = W_UKV
  c``. ``score = σ · (q_nope · k_nope + q_rope · k_r)``, causal softmax in
  float32. No cache, no absorbed form: those are the program's.
- **YaRN** (``deepseek_yarn``): each rotary frequency is blended between
  ``θ_i`` and ``θ_i / factor`` by a linear ramp between the dimension that
  turns ``beta_fast`` times in the original context (rounded down) and the one
  that turns ``beta_slow`` times (rounded up); the tables are scaled by
  ``m(mscale) / m(mscale_all_dim)`` and ``σ = 192^-1/2 · m(mscale_all_dim)²``
  with ``m(a) = 0.1 · a · ln(factor) + 1``. Rotary dimensions pair as halves.
- **The router**: ``s = sigmoid(W_r x)`` in float32; the 8 experts are the
  largest of ``s + b`` (``b`` the learned bias: it chooses, it never weighs);
  gates ``2.5 · s_i / (Σ_chosen s_j + 1e-20)``.
- **The share.** The chip holds experts ``first_held_expert ..
  first_held_expert + experts_held - 1``; the layer's output is the gated sum
  over the chosen experts *it holds* plus the shared expert, and that goes on
  to the next layer. Nothing stands in for the absent chips.

Each held expert is applied to every token and weighted by its gate (zero
where the router did not choose it), one at a time by a scan so only one
expert's float32 copy is alive."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import F32, head_kernel, next_token_loss, rotate_half


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _m(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_tables(cfg, seq: int):
    """(sin, cos) of shape (seq, qk_rope_head_dim), rotate-half layout."""
    d, theta, y = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    def dim_that_turns(n):      # the rotary dimension that turns n times in the original context
        return d * math.log(y["original_max_position_embeddings"] / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_that_turns(y["beta_fast"])), 0)
    high = min(math.ceil(dim_that_turns(y["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / y["factor"] * ramp
    freqs = jnp.outer(jnp.arange(seq, dtype=F32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    scale = _m(y["factor"], y["mscale"]) / _m(y["factor"], y["mscale_all_dim"])
    return jnp.sin(emb) * scale, jnp.cos(emb) * scale


def _attention(h, attn, cfg, sin, cos):
    b, s, _ = h.shape
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    y = cfg["rope_scaling"]
    sigma = (dn + dr) ** -0.5 * _m(y["factor"], y["mscale_all_dim"]) ** 2
    q = (h @ attn["q"]["kernel"].astype(F32)).reshape(b, s, n, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate_half(q[..., dn:], sin, cos)
    ckr = h @ attn["kv_a"]["kernel"].astype(F32)
    c = _rms_norm(ckr[..., :r], attn["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_rope = rotate_half(ckr[:, :, None, r:], sin, cos)[:, :, 0]           # (B, S, dr)
    kv = jnp.einsum("bsr,rnd->bsnd", c, attn["kv_b"]["kernel"].astype(F32))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = sigma * (
        jnp.einsum("bqnd,bknd->bnqk", q_nope, k_nope) + jnp.einsum("bqnd,bkd->bnqk", q_rope, k_rope)
    )
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, s, -1) @ attn["o"]["kernel"].astype(F32)


def _swiglu(x, gate_up, down):
    """x (T, H) through gate_up (H, 2, I) and down (I, H)."""
    gate_up, down = gate_up.astype(F32), down.astype(F32)
    return (jax.nn.silu(x @ gate_up[:, 0]) * (x @ gate_up[:, 1])) @ down


def _layer(x, lp, cfg, sin, cos):
    """One block; (x, routing margin (B, S)) — margin 1 for a dense layer."""
    b, s, hdim = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["attn_norm"]["scale"], eps), lp["attn"], cfg, sin, cos)
    flat = _rms_norm(x, lp["mlp_norm"]["scale"], eps).reshape(b * s, hdim)
    if "mlp" in lp:
        y = _swiglu(flat, lp["mlp"]["gate_up"], lp["mlp"]["down"]["kernel"])
        return x + y.reshape(b, s, hdim), jnp.ones((b, s), F32)

    moe, k = lp["moe"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(flat @ moe["router"]["kernel"].astype(F32))
    ranked, top_i = lax.top_k(scores + moe["router"]["bias"].astype(F32), k + 1)
    # how clearly the router chose: the gap between the last expert taken and
    # the first one left out, of score + bias, relative to the former
    margin = ((ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]).reshape(b, s)
    chosen = jnp.sum(jax.nn.one_hot(top_i[:, :k], cfg["num_experts"], dtype=F32), axis=1)  # (T, E) 0/1
    gates = chosen * scores
    gates = cfg["routed_scaling_factor"] * gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    first = cfg["first_held_expert"]
    held = gates[:, first:first + cfg["experts_held"]]      # the chosen experts held here

    def one_expert(acc, xs):
        gate_up, down, g = xs           # (H, 2, I), (I, H), (T,)
        return acc + g[:, None] * _swiglu(flat, gate_up, down), None

    y, _ = lax.scan(
        one_expert, _swiglu(flat, moe["shared"]["gate_up"], moe["shared"]["down"]),
        (moe["experts"]["gate_up"], moe["experts"]["down"], held.T),
    )
    return x + y.reshape(b, s, hdim), margin


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: ``dense_layers`` and ``layers`` leaves carry a leading
    layer axis."""
    return forward_with_margin(params, cfg, ids)[0]


def forward_with_margin(params, cfg, ids):
    """(logits (B, S, V), routing margin (B, S)): the margin is the smallest
    over the expert layers of each token's relative gap between the last
    chosen of ``score + bias`` (the eighth of 128 as published) and the best
    one left out."""
    sin, cos = yarn_tables(cfg, ids.shape[1])
    x = params["embed"]["embedding"][ids].astype(F32)
    margins = []
    for stack in ("dense_layers", "layers"):
        if stack in params:
            x, m = lax.scan(lambda x, lp: _layer(x, lp, cfg, sin, cos), x, params[stack])
            margins.append(m)
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ head_kernel(params), jnp.min(jnp.concatenate(margins), axis=0)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
