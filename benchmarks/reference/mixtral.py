"""Mixtral (Jiang et al., 2024; HF ``MixtralForCausalLM``) in plain float32:
RMSNorm, rotary embeddings on full heads, grouped-query causal attention, a
top-k softmax router whose chosen probabilities are renormalised, SwiGLU
experts, no biases.

One departure from the published sparse evaluation, with the same result: each
expert is applied to every token and weighted by its gate (zero where the
router did not choose it). Experts are visited one at a time by a scan so only
one expert's float32 copy is alive."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import (
    F32, causal_attention, f32, head_kernel, next_token_loss, rope_tables, rotate_half,
)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer(x, lp, cfg, sin, cos):
    b, s, hdim = x.shape
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    attn = f32(lp["attn"])
    h = _rms_norm(x, lp["attn_norm"]["scale"].astype(F32), eps)
    q = (h @ attn["qkv"]["q_kernel"]).reshape(b, s, n, d)
    k = (h @ attn["qkv"]["k_kernel"]).reshape(b, s, nkv, d)
    v = (h @ attn["qkv"]["v_kernel"]).reshape(b, s, nkv, d)
    q, k = rotate_half(q, sin, cos), rotate_half(k, sin, cos)
    x = x + causal_attention(q, k, v) @ attn["o"]["kernel"]

    h = _rms_norm(x, lp["mlp_norm"]["scale"].astype(F32), eps)
    flat = h.reshape(b * s, hdim)
    probs = jax.nn.softmax(flat @ lp["moe"]["router"]["kernel"].astype(F32), axis=-1)
    k = cfg["num_experts_per_tok"]
    ranked, top_i = lax.top_k(probs, k + 1)
    # how clearly the router chose: the gap between the last expert taken and
    # the first one left out, relative to the former (for the check: a token
    # within a rounding of a tie is routed differently in bf16, legitimately)
    margin = ((ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]).reshape(b, s)
    top_p, top_i = ranked[:, :k], top_i[:, :k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_exp = cfg["num_local_experts"]
    gates = jnp.sum(
        jax.nn.one_hot(top_i, n_exp, dtype=F32) * top_p[..., None], axis=1
    )  # (T, E): renormalised gate of chosen experts, 0 elsewhere

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
    expert and the best one left out."""
    s = ids.shape[1]
    sin, cos = rope_tables(cfg["head_dim"], s, cfg["rope_theta"])
    x = params["embed"]["embedding"][ids].astype(F32)

    def body(x, lp):
        return _layer(x, lp, cfg, sin, cos)

    x, margins = lax.scan(body, x, params["layers"])
    x = _rms_norm(x, params["final_norm"]["scale"].astype(F32), cfg["rms_norm_eps"])
    return x @ head_kernel(params), jnp.min(margins, axis=0)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
