"""GPT-NeoX / Pythia (Black et al., 2022; Biderman et al., 2023; HF
``GPTNeoXForCausalLM``) in plain float32: LayerNorm with bias, biased
projections, rotary embeddings (rotate-half) on the first ``rotary_pct`` of
each head, multi-head causal attention, exact GELU, and the parallel residual
``x + attn(ln1(x)) + mlp(ln2(x))``. The LM head has no bias."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import (
    F32, causal_attention, f32, head_kernel, next_token_loss, rope_tables, rotate_half,
)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rotary_dims(cfg) -> int:
    d = int(cfg["head_dim"] * cfg["rotary_pct"])
    return d - d % 2


def _layer(x, lp, cfg, sin, cos):
    b, s, _ = x.shape
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    eps = cfg["layer_norm_eps"]
    lp = f32(lp)
    rot = sin.shape[-1]
    h1 = _layer_norm(x, lp["attn_norm"], eps)
    qkv = lp["attn"]["qkv"]
    q = (h1 @ qkv["q_kernel"] + qkv["q_bias"]).reshape(b, s, n, d)
    k = (h1 @ qkv["k_kernel"] + qkv["k_bias"]).reshape(b, s, n, d)
    v = (h1 @ qkv["v_kernel"] + qkv["v_bias"]).reshape(b, s, n, d)
    q = jnp.concatenate([rotate_half(q[..., :rot], sin, cos), q[..., rot:]], axis=-1)
    k = jnp.concatenate([rotate_half(k[..., :rot], sin, cos), k[..., rot:]], axis=-1)
    attn = causal_attention(q, k, v) @ lp["attn"]["o"]["kernel"] + lp["attn"]["o"]["bias"]
    h2 = _layer_norm(x, lp["mlp_norm"], eps)
    up = jax.nn.gelu(h2 @ lp["mlp"]["up"]["kernel"] + lp["mlp"]["up"]["bias"], approximate=False)
    mlp = up @ lp["mlp"]["down"]["kernel"] + lp["mlp"]["down"]["bias"]
    if not cfg["use_parallel_residual"]:
        raise NotImplementedError("the sequential-residual variant has no reference here")
    return x + attn + mlp


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: ``layers`` leaves carry a leading layer axis."""
    s = ids.shape[1]
    sin, cos = rope_tables(rotary_dims(cfg), s, cfg["rotary_emb_base"])
    x = params["embed"]["embedding"][ids].astype(F32)

    def body(x, lp):
        return _layer(x, lp, cfg, sin, cos), None

    x, _ = lax.scan(body, x, params["layers"])
    x = _layer_norm(x, f32(params["final_norm"]), cfg["layer_norm_eps"])
    return x @ head_kernel(params)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
