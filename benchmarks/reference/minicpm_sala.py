"""MiniCPM-SALA (``openbmb/MiniCPM-SALA``, ``model_type: minicpm_sala``) in
plain float32: block-sparse softmax attention layers (``minicpm4``) and
Lightning linear-attention layers (``lightning-attn``) in the order
``mixer_types`` names, a dense SwiGLU after every mixer, RMSNorm before each,
an untied head, no bias, muP's three scalings.

Written out here and read from no flag (``x`` the normed input, ``d`` the head
size, ε ``rms_norm_eps``):

- *Common*: ``h0 = scale_emb · Embed(ids)``; each sub-layer ``h ← h +
  (scale_depth / √mup_denominator) · f(RMSNorm(h))``; logits ``=
  lm_head(RMSNorm(h) / (hidden / dim_model_base))``.
- *Lightning*: ``q = RoPE(RMSNorm_d(W_q x))``, ``k = RoPE(RMSNorm_d(W_k x))``,
  ``v = W_v x`` (rotate-half, θ ``rope_theta``); ``S_t = λ_h S_{t−1} + k_tᵀ
  v_t``, ``o_t = q_t S_t / √d``; ``y = W_o (RMSNorm(o) ⊙ σ(W_g x))``, the
  output norm over all heads' values at once. ``λ_h = exp(−2^{−8(h+1)/n})``.
- *Sparse*: ``q = RMSNorm_d(W_q x)``, ``k = RMSNorm_d(W_k x)``, ``v = W_v x``,
  no rotary. ``K̄_j = mean(k[stride·j : stride·j + kernel])``. For the query at
  position ``p`` and kv group ``g``: ``s_h = softmax_j(q_h · K̄_j / √d)`` over
  the kernels with ``stride·j + kernel − 1 ≤ p``; ``P_g = Σ_{h∈g} s_h``;
  ``B_g(b) = max`` of ``P_g(j)`` over the kernels that share a row with block
  ``b``; blocks ``< init_blocks`` and the blocks that hold rows ``p − window + 1
  … p`` are taken, then the highest ``B_g`` until ``topk`` blocks in all; ``o =
  softmax(q K_selᵀ / √d) V_sel`` over the rows ``≤ p`` of the taken blocks;
  ``y = W_o (o ⊙ σ(W_g x))``.

Departures from the family's published code, both stated in the
configuration file: the decay ``λ_h`` and the selection's sizes are not keys of
the config (``assumed``), and the selection rule holds at *every* row — the
family's serving code attends densely while a call's sequence is at most
``dense_len``, which is a property of a call and not of a row.

One sequence from its first token to its last: the recurrence one row after
another from the zero state (``lax.scan`` over time — not the chunk form the
program runs), the selection by an explicit sort of a dense score matrix, a
block of query rows at a time so that ten thousand rows fit; no cache, no
pooled-key pool, no tile, no padded row."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import F32, head_kernel, next_token_loss, rope_tables, rotate_half

QUERY_ROWS = 256        # query rows a trip of the sparse layer's loop
STACKS = {"minicpm4": "sparse_layers", "lightning-attn": "lightning_layers"}


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _project(x, p, heads, kv_heads, d, eps):
    b, s, _ = x.shape
    q = (x @ p["qkv"]["q_kernel"].astype(F32)).reshape(b, s, heads, d)
    k = (x @ p["qkv"]["k_kernel"].astype(F32)).reshape(b, s, kv_heads, d)
    v = (x @ p["qkv"]["v_kernel"].astype(F32)).reshape(b, s, kv_heads, d)
    return _rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps), v


def _gated(x, o, p):
    return (o * jax.nn.sigmoid(x @ p["gate"]["kernel"].astype(F32))) @ p["o"]["kernel"].astype(F32)


def _lightning(x, p, cfg):
    """x (B, S, H) normed -> the mixer's output (B, S, H)."""
    n, d, eps = cfg["lightning_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    b, s, _ = x.shape
    q, k, v = _project(x, p, n, n, d, eps)
    sin, cos = rope_tables(d, s, cfg["rope_theta"])
    q, k = rotate_half(q, sin, cos), rotate_half(k, sin, cos)
    lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, n + 1, dtype=F32) / n))     # (n,)

    def row(state, qkv):                                                      # state (B, n, d, d)
        q_t, k_t, v_t = qkv
        state = lam[None, :, None, None] * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bnd,bnde->bne", q_t, state)

    _, o = lax.scan(row, jnp.zeros((b, n, d, d), F32), tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    o = jnp.swapaxes(o, 0, 1).reshape(b, s, n * d) / jnp.sqrt(F32(d))
    return _gated(x, _rms_norm(o, p["out_norm"]["scale"], eps), p)


def _sparse(x, p, cfg):
    n, nkv, d, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
                      cfg["rms_norm_eps"])
    size, stride, block = cfg["kernel_size"], cfg["kernel_stride"], cfg["block_size"]
    topk, init, window = cfg["topk"], cfg["init_blocks"], cfg["window_size"]
    b, s, _ = x.shape
    g = n // nkv
    q, k, v = _project(x, p, n, nkv, d, eps)
    # every complete kernel of the sequence, and which blocks each shares a row with
    kernels = max((s - size) // stride + 1, 0)
    blocks = -(-s // block)
    starts = stride * jnp.arange(kernels)
    pooled = jnp.mean(k[:, starts[:, None] + jnp.arange(size)[None, :]], axis=2)   # (B, J, nkv, d)
    first_row = block * jnp.arange(blocks)
    shares = (starts[:, None] + size - 1 >= first_row[None, :]) & (
        starts[:, None] <= first_row[None, :] + block - 1)                   # (J, blocks)
    key_block = jnp.arange(s) // block

    def rows(start):
        pos = start + jnp.arange(QUERY_ROWS)                                  # positions past s are padding
        qb = lax.dynamic_slice_in_dim(q_pad, start, QUERY_ROWS, axis=1)
        qg = qb.reshape(b, QUERY_ROWS, nkv, g, d)
        ended = (starts + size - 1)[None, :] <= pos[:, None]                  # (Q, J)
        if kernels:
            sc = jnp.einsum("bqkgd,bjkd->bkgqj", qg, pooled) / jnp.sqrt(F32(d))
            sc = jnp.where(ended, sc, -jnp.inf)
            pr = jnp.where(ended, jax.nn.softmax(jnp.where(ended.any(-1, keepdims=True), sc, 0.0), axis=-1), 0.0)
            group = jnp.where(ended, pr.sum(axis=2), -1.0)                    # (B, nkv, Q, J)
            score = jnp.max(jnp.where(shares, group[..., None], -1.0), axis=-2)   # (B, nkv, Q, blocks)
        else:
            score = jnp.full((b, nkv, QUERY_ROWS, blocks), -1.0, F32)
        own = pos // block
        causal = jnp.arange(blocks)[None, :] <= own[:, None]                  # (Q, blocks)
        forced = (jnp.arange(blocks)[None, :] < init) | (
            jnp.arange(blocks)[None, :] >= (jnp.maximum(pos - window + 1, 0) // block)[:, None])
        rank = jnp.where(forced & causal, jnp.inf, jnp.where(causal, score, -jnp.inf))
        order = jnp.argsort(-rank, axis=-1, stable=True)[..., :topk]          # (B, nkv, Q, <= topk)
        taken = jnp.any(order[..., None] == jnp.arange(blocks), axis=-2) & causal
        sees = taken[..., key_block] & (jnp.arange(s)[None, :] <= pos[:, None])   # (B, nkv, Q, S)
        att = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(F32(d))
        att = jnp.where(sees[:, :, None], att, -jnp.inf)
        att = jax.nn.softmax(jnp.where(sees.any(-1)[:, :, None, :, None], att, 0.0), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", att, v).reshape(b, QUERY_ROWS, n * d)

    trips = -(-s // QUERY_ROWS)
    q_pad = jnp.pad(q, ((0, 0), (0, trips * QUERY_ROWS - s), (0, 0), (0, 0)))
    o = lax.map(rows, QUERY_ROWS * jnp.arange(trips))                         # (trips, B, Q, n·d)
    o = jnp.swapaxes(o, 0, 1).reshape(b, trips * QUERY_ROWS, n * d)[:, :s]
    return _gated(x, o, p)


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: one stack of layers a mixer kind, a leading layer axis."""
    eps = cfg["rms_norm_eps"]
    residual = cfg["scale_depth"] / jnp.sqrt(F32(cfg["mup_denominator"]))
    x = cfg["scale_emb"] * params["embed"]["embedding"][ids].astype(F32)
    seen = {kind: 0 for kind in STACKS}
    for kind in cfg["mixer_types"]:
        lp = jax.tree.map(lambda a: a[seen[kind]], params[STACKS[kind]])
        seen[kind] += 1
        h = _rms_norm(x, lp["attn_norm"]["scale"], eps)
        x = x + residual * (_sparse if kind == "minicpm4" else _lightning)(h, lp["attn"], cfg)
        h = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
        gate_up = lp["mlp"]["gate_up"].astype(F32)                            # (H, 2, I)
        x = x + residual * (
            (jax.nn.silu(h @ gate_up[:, 0]) * (h @ gate_up[:, 1])) @ lp["mlp"]["down"]["kernel"].astype(F32))
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return (x / (cfg["hidden_size"] / cfg["dim_model_base"])) @ head_kernel(params)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
