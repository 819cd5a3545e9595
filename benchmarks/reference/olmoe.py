"""OLMoE (Muennighoff et al., arXiv:2409.02060; HF ``OlmoeForCausalLM``) in
plain float32: a pre-norm block of RMSNorm, multi-head causal attention with
rotary embeddings on full heads, and a sparse SwiGLU feed-forward; no biases,
an untied output head.

What sets it apart from Mixtral, written out here and read from no flag:

- **QK-norm**: the projected query (all heads side by side, ``heads x
  head_dim`` wide) and the projected key each pass through an RMSNorm with a
  learned scale over that *whole* vector — every head jointly, not head by
  head — before they are split into heads and rotated. What attention (and a
  cache) sees is the normed, rotated key.
- **The router** is a softmax over all the experts' logits in float32; the
  ``num_experts_per_tok`` largest probabilities are the gates *as they are*
  (``norm_topk_prob: false``): they are not renormalised and sum to less
  than one.

One departure from the published sparse evaluation, with the same result: each
expert is applied to every token and weighted by its gate (zero where the
router did not choose it). Experts are visited one at a time by a scan so only
one expert's float32 copy is alive."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import (
    F32, causal_attention, head_kernel, next_token_loss, rope_tables, rotate_half,
)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _layer(x, lp, cfg, sin, cos):
    b, s, hdim = x.shape
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    attn = lp["attn"]
    h = _rms_norm(x, lp["attn_norm"]["scale"], eps)
    # the norm runs over the projection's whole output, all heads jointly
    q = _rms_norm(h @ attn["qkv"]["q_kernel"].astype(F32), attn["q_norm"]["scale"], eps)
    k = _rms_norm(h @ attn["qkv"]["k_kernel"].astype(F32), attn["k_norm"]["scale"], eps)
    v = h @ attn["qkv"]["v_kernel"].astype(F32)
    q = rotate_half(q.reshape(b, s, n, d), sin, cos)
    k = rotate_half(k.reshape(b, s, nkv, d), sin, cos)
    x = x + causal_attention(q, k, v.reshape(b, s, nkv, d)) @ attn["o"]["kernel"].astype(F32)

    h = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
    flat = h.reshape(b * s, hdim)
    probs = jax.nn.softmax(flat @ lp["moe"]["router"]["kernel"].astype(F32), axis=-1)
    k = cfg["num_experts_per_tok"]
    ranked, top_i = lax.top_k(probs, k + 1)
    # how clearly the router chose: the gap between the last expert taken and
    # the first one left out, relative to the former (for the check: a token
    # within a rounding of a tie is routed differently in bf16, legitimately)
    margin = ((ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]).reshape(b, s)
    gates = jnp.sum(
        jax.nn.one_hot(top_i[:, :k], cfg["num_experts"], dtype=F32) * ranked[:, :k, None], axis=1
    )  # (T, E): the chosen experts' probabilities as they are, 0 elsewhere

    def one_expert(acc, xs):
        gate_up, down, g = xs           # (H, 2, I), (I, H), (T,)
        gate_up, down = gate_up.astype(F32), down.astype(F32)
        act = jax.nn.silu(flat @ gate_up[:, 0]) * (flat @ gate_up[:, 1])
        return acc + g[:, None] * (act @ down), None

    experts = lp["moe"]["experts"]
    y, _ = lax.scan(
        one_expert, jnp.zeros_like(flat),
        (experts["gate_up"], experts["down"], gates.T),
    )
    return x + y.reshape(b, s, hdim), margin


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: ``layers`` leaves carry a leading layer axis."""
    return forward_with_margin(params, cfg, ids)[0]


def forward_with_margin(params, cfg, ids):
    """(logits (B, S, V), routing margin (B, S)): the margin is the smallest
    over the layers of each token's relative gap between its last chosen
    expert (the eighth of 64 as published) and the best one left out."""
    s = ids.shape[1]
    sin, cos = rope_tables(cfg["head_dim"], s, cfg["rope_theta"])
    x = params["embed"]["embedding"][ids].astype(F32)

    def body(x, lp):
        return _layer(x, lp, cfg, sin, cos)

    x, margins = lax.scan(body, x, params["layers"])
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ head_kernel(params), jnp.min(margins, axis=0)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
