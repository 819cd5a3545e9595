"""Brumby (``manifestai/Brumby-14B-Base``, power retention, arXiv:2507.04239)
in plain float32: a pre-norm block of RMSNorm, **power retention in its
attention form**, and a dense SwiGLU feed-forward; no biases, an untied head.

The layer, written out here and read from no flag (``h`` the normed input,
``d`` the head width):

- ``q``, ``k``, ``v`` projections with grouped queries; RMSNorm with a learned
  scale over *each head* of q and of k; rotary (rotate-half) on both;
- a gate: ``log g = log sigmoid(h W_g)``, one scalar a kv head a token;
- ``A_ij = (q_i . k_j / sqrt(d))^2 * exp(sum_{m=j+1..i} log g_m)`` for j <= i,
  0 above the diagonal; ``y_i = sum_j A_ij v_j / (sum_j A_ij + eps)``.

This is the quadratic form alone: every query row against every earlier row.
There is no feature map, no state and no chunk here, so it shares nothing
with the two forms the program runs (a recurrent state in decode, chunks with
a carried state in prefill); a state that lost or kept something it should not
have shows as a difference. Queries are taken 512 rows at a time so that a
long check fits; the rows of one block still see every earlier row."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import (
    F32, head_kernel, next_token_loss, rope_tables, rotate_half,
)

QUERY_ROWS = 512


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _retention(q, k, v, log_g, eps):
    """q (B, S, n, d); k, v (B, S, n_kv, d); log_g (B, S, n_kv) -> (B, S, n*d)."""
    b, s, n, d = q.shape
    group = n // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    decay = jnp.repeat(jnp.cumsum(log_g, axis=1), group, axis=2)         # (B, S, n)
    cols = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(min(QUERY_ROWS, s))
        qb, db = jnp.take(q, rows, axis=1), jnp.take(decay, rows, axis=1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) / jnp.sqrt(F32(d))
        gap = db.transpose(0, 2, 1)[..., None] - decay.transpose(0, 2, 1)[:, :, None, :]
        seen = cols[None, :] <= rows[:, None]
        weights = jnp.where(seen, jnp.square(scores) * jnp.exp(jnp.where(seen, gap, 0.0)), 0.0)
        out = jnp.einsum("bnqk,bknd->bqnd", weights, v)
        return out / (jnp.sum(weights, axis=-1).transpose(0, 2, 1)[..., None] + eps)

    if s <= QUERY_ROWS:
        return block(0).reshape(b, s, n * d)
    # whole blocks; the last one starts early enough to end on the last row
    starts = jnp.minimum(jnp.arange(0, s, QUERY_ROWS), s - QUERY_ROWS)
    out = lax.map(block, starts)                                          # (blocks, B, Q, n, d)
    pieces = [out[i] for i in range(out.shape[0] - 1)]
    tail = s - QUERY_ROWS * (out.shape[0] - 1)
    pieces.append(out[-1][:, QUERY_ROWS - tail:])
    return jnp.concatenate(pieces, axis=1).reshape(b, s, n * d)


def _layer(x, lp, cfg, sin, cos):
    b, s, _ = x.shape
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    attn = lp["attn"]
    h = _rms_norm(x, lp["attn_norm"]["scale"], eps)
    q = (h @ attn["qkv"]["q_kernel"].astype(F32)).reshape(b, s, n, d)
    k = (h @ attn["qkv"]["k_kernel"].astype(F32)).reshape(b, s, nkv, d)
    v = (h @ attn["qkv"]["v_kernel"].astype(F32)).reshape(b, s, nkv, d)
    # the norm runs over each head on its own; the scale is one head wide
    q = rotate_half(_rms_norm(q, attn["q_norm"]["scale"], eps), sin, cos)
    k = rotate_half(_rms_norm(k, attn["k_norm"]["scale"], eps), sin, cos)
    log_g = jax.nn.log_sigmoid(h @ attn["gate"]["kernel"].astype(F32))
    y = _retention(q, k, v, log_g, cfg["retention_eps"])
    x = x + y @ attn["o"]["kernel"].astype(F32)

    h = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
    gate_up = lp["mlp"]["gate_up"].astype(F32)                            # (H, 2, I)
    act = jax.nn.silu(h @ gate_up[:, 0]) * (h @ gate_up[:, 1])
    return x + act @ lp["mlp"]["down"]["kernel"].astype(F32)


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: ``layers`` leaves carry a leading layer axis."""
    sin, cos = rope_tables(cfg["head_dim"], ids.shape[1], cfg["rope_theta"])
    x = params["embed"]["embedding"][ids].astype(F32)
    x, _ = lax.scan(lambda x, lp: (_layer(x, lp, cfg, sin, cos), None), x, params["layers"])
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ head_kernel(params)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
