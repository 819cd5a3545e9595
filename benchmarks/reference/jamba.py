"""Jamba (``ai21labs/AI21-Jamba2-3B``, ``model_type: jamba``) in plain
float32: Mamba-1 state-space blocks with an attention block where
``i mod attn_layer_period == attn_layer_offset``, a dense SwiGLU after every
mixer, RMSNorm before each, a tied head, no bias but the convolution's and
``dt_proj``'s, and **no positional term anywhere**.

Written out here and read from no flag (``x`` the normed input):

- *Mamba*: ``[u ‖ g] = x W_in``; ``c_t = SiLU(b + Σ_j w_j ⊙ u_{t−K+1+j})``
  (zeros before the first token); ``[δ ‖ B ‖ C] = c W_x``, each RMS-normed
  with a learned scale; ``Δ = softplus(δ W_dt + b_dt)``; ``A = −exp(A_log)``;
  ``h_t = exp(Δ_t ⊗ A) ⊙ h_{t−1} + (Δ_t ⊙ c_t) ⊗ B_t``; ``y_t = h_t C_t +
  D ⊙ c_t``; out ``(y ⊙ SiLU(g)) W_out``.
- *Attention*: grouped queries on ``num_key_value_heads`` heads, causal
  softmax at ``1/√d``, q and k exactly as projected.

One sequence from its first token to its last: one row after another from the
zero state (``lax.scan`` over time, ``h`` held (B, D, N) as the published
tensors are), no cache, no chunk, no padded row, no tail handed on — so it
shares nothing with the forms the program runs, and a state that lost or kept
something shows as a difference."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import F32, causal_attention, head_kernel, next_token_loss


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _mamba(x, p, cfg):
    """x (B, S, H) normed -> the mixer's output (B, S, H)."""
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    u, g = jnp.split(x @ p["in_proj"]["kernel"].astype(F32), 2, axis=-1)
    s = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    w = p["conv"]["kernel"].astype(F32)                                  # (K, D)
    c = jax.nn.silu(p["conv"]["bias"] + sum(w[j] * padded[:, j:j + s] for j in range(k)))
    dbc = c @ p["x_proj"]["kernel"].astype(F32)
    delta = _rms_norm(dbc[..., :r], p["dt_norm"]["scale"], eps)
    b_t = _rms_norm(dbc[..., r:r + n], p["b_norm"]["scale"], eps)
    c_t = _rms_norm(dbc[..., r + n:], p["c_norm"]["scale"], eps)
    delta = jax.nn.softplus(delta @ p["dt_proj"]["kernel"].astype(F32) + p["dt_proj"]["bias"])
    a = -jnp.exp(p["a_log"].astype(F32)).T                               # (D, N)

    def row(h, xs):                                                      # h (B, D, N)
        d_t, x_t, bb, cc = xs
        h = jnp.exp(d_t[..., None] * a) * h + (d_t * x_t)[..., None] * bb[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, cc)

    h0 = jnp.zeros(u.shape[:1] + a.shape, F32)
    _, y = lax.scan(row, h0, tuple(jnp.swapaxes(t, 0, 1) for t in (delta, c, b_t, c_t)))
    y = jnp.swapaxes(y, 0, 1) + p["d_skip"] * c
    return (y * jax.nn.silu(g)) @ p["out_proj"]["kernel"].astype(F32)


def _attention(x, p, cfg):
    b, s, _ = x.shape
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ p["qkv"]["q_kernel"].astype(F32)).reshape(b, s, n, d)
    k = (x @ p["qkv"]["k_kernel"].astype(F32)).reshape(b, s, nkv, d)
    v = (x @ p["qkv"]["v_kernel"].astype(F32)).reshape(b, s, nkv, d)
    return causal_attention(q, k, v) @ p["o"]["kernel"].astype(F32)


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: one stack of layers a kind, a leading layer axis."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["embedding"][ids].astype(F32)
    seen = {"mamba": 0, "attention": 0}
    for kind in cfg["layer_kinds"]:
        lp = jax.tree.map(lambda a: a[seen[kind]], params[kind + "_layers"])
        seen[kind] += 1
        h = _rms_norm(x, lp["attn_norm"]["scale"], eps)
        x = x + (_mamba(h, lp["mamba"], cfg) if kind == "mamba" else _attention(h, lp["attention"], cfg))
        h = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
        gate_up = lp["mlp"]["gate_up"].astype(F32)                       # (H, 2, I)
        x = x + (jax.nn.silu(h @ gate_up[:, 0]) * (h @ gate_up[:, 1])) @ lp["mlp"]["down"]["kernel"].astype(F32)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ head_kernel(params)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
