"""Pieces both references share, written out plainly."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rope_tables(rotary_dims: int, seq: int, theta: float):
    """(sin, cos) of shape (seq, rotary_dims), rotate-half layout."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dims, 2, dtype=F32) / rotary_dims))
    freqs = jnp.outer(jnp.arange(seq, dtype=F32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb), jnp.cos(emb)


def rotate_half(x, sin, cos):
    """x (B, S, n, d) rotated by position; sin/cos (S, d)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def causal_attention(q, k, v):
    """q (B, S, n, d); k, v (B, S, n_kv, d) with n a multiple of n_kv.
    Softmax in float32 over the causal triangle."""
    b, s, n, d = q.shape
    group = n // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(F32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, n * d)


def next_token_loss(logits, ids):
    """Mean cross-entropy of token t+1 given the prefix up to t."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def head_kernel(params):
    """(H, V) output projection; tied models reuse the embedding table."""
    if "lm_head" in params:
        return params["lm_head"]["kernel"].astype(F32)
    return params["embed"]["embedding"].astype(F32).T
