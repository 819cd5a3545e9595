"""Xing4.0-29B-A4B (HF ``model_type: xing4_0``) in plain float32: the
DeepSeek-V3 block shape — multi-head latent attention with a query latent, a
sparse feed-forward with a shared expert behind a leading dense stack, YaRN —
under a four-stream residual whose mixing matrix is made per token and
projected by Sinkhorn rounds (manifold-constrained hyper-connections,
arXiv:2512.24880). No biases but the router's selection bias, an untied head.

Written out here and read from no flag:

- **The residual.** A token's state between sub-layers is ``X`` (n, C), n =
  ``hc_mult`` streams. Around each sub-layer ``F`` (attention, the
  feed-forward; each with its RMSNorm inside), with that sub-layer's own
  ``Φ`` (nC, 2n + n²) = ``[Φ_pre ‖ Φ_post ‖ Φ_res]``, gates ``α`` (3,) and
  biases ``b_pre``, ``b_post`` (n,), ``b_res`` (n, n):

      x̂ = vec(X) / rms(vec X)                     (no learned scale)
      H_pre  = sigmoid(α₀ · x̂ Φ_pre + b_pre)
      H_post = 2 · sigmoid(α₁ · x̂ Φ_post + b_post)
      H_res  = SK(exp(clip(α₂ · mat(x̂ Φ_res) + b_res, clamp)))
      X ← H_res X + H_postᵀ ⊗ F(H_pre X)

  ``SK``: ``hc_sinkhorn_iters`` rounds of (rows / (row sums + ``hc_eps``),
  then columns / (column sums + ``hc_eps``)). The streams start as n copies
  of the embedding; the final norm reads their sum.
- **Latent attention, expanded form only.** ``c_q = RMSNorm(W_DQ x)``
  (learned scale), ``q = W_UQ c_q`` per head a no-position part and a rotary
  part; ``[c ‖ k_r] = W_DKV x``, ``c`` through an RMSNorm with a learned
  scale, ``k_r`` — one rotary key for all heads — rotated; per head
  ``[k_nope ‖ v] = W_UKV c``; ``score = σ (q_nope · k_nope + q_rope · k_r)``,
  causal softmax. No cache, no absorbed form: those are the program's.
- **YaRN** (the catalog's ``rope_scaling.type`` is ``"yarn"`` with DeepSeek's
  ``mscale`` / ``mscale_all_dim`` keys, read as ``deepseek_yarn``): each
  rotary frequency is blended between ``θ_i`` and ``θ_i / factor`` by a
  linear ramp between the dimension that turns ``beta_fast`` times in the
  original context (rounded down) and the one that turns ``beta_slow`` times
  (rounded up); tables scaled by ``m(mscale) / m(mscale_all_dim)``,
  ``σ = (d_nope + d_rope)^-1/2 · m(mscale_all_dim)²``,
  ``m(a) = 0.1 a ln(factor) + 1``. Rotary dimensions pair as halves.
- **The router** (``noaux_tc``, one group): ``s = sigmoid(W_r x)``; the
  experts taken are the ``num_experts_per_tok`` largest of ``s + b``; gates
  ``routed_scaling_factor · s_i / (Σ_taken s_j + 1e-20)``; one shared expert
  beside them.

For memory, not for speed — a check over thousands of rows has to fit beside
a serving engine on one chip: attention runs over blocks of ``ROW_BLOCK``
queries, the experts one at a time (each over every token, weighted by its
gate, zero where the router did not take it), indexed where they lie in the
layer-stacked arrays, and the head over blocks of ``VOCAB_BLOCK`` columns
written into the one logits array."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import F32, next_token_loss, rotate_half

ROW_BLOCK = 512
VOCAB_BLOCK = 8192


def _rms_norm(x, eps, scale=None):
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x if scale is None else x * scale.astype(F32)


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


def sinkhorn(m, rounds: int, eps: float):
    for _ in range(rounds):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)      # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)      # columns
    return m


def connection_coefficients(streams, hc, cfg):
    """streams (B, S, n, C) -> H_pre (B, S, n), H_post (B, S, n), H_res (B, S, n, n)."""
    b, s, n, width = streams.shape
    flat = _rms_norm(streams.reshape(b, s, n * width), cfg["rms_norm_eps"])
    raw = flat @ hc["phi"].astype(F32)
    alpha = hc["alpha"].astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * raw[..., :n] + hc["b_pre"])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[..., n:2 * n] + hc["b_post"])
    res = alpha[2] * raw[..., 2 * n:].reshape(b, s, n, n) + hc["b_res"]
    res = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    return pre, post, sinkhorn(res, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])


def _connected(streams, hc, cfg, sublayer):
    """``X ← H_res X + H_postᵀ ⊗ F(H_pre X)``; ``sublayer`` returns (y, extra)."""
    pre, post, res = connection_coefficients(streams, hc, cfg)
    y, extra = sublayer(jnp.einsum("bsn,bsnc->bsc", pre, streams))
    return jnp.einsum("bsij,bsjc->bsic", res, streams) + post[..., None] * y[:, :, None, :], extra


def _attention(h, attn, cfg, sin, cos):
    b, s, _ = h.shape
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    y = cfg["rope_scaling"]
    sigma = (dn + dr) ** -0.5 * _m(y["factor"], y["mscale_all_dim"]) ** 2
    c_q = _rms_norm(h @ attn["q_a"]["kernel"].astype(F32), cfg["rms_norm_eps"], attn["q_norm"]["scale"])
    q = (c_q @ attn["q_b"]["kernel"].astype(F32)).reshape(b, s, n, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate_half(q[..., dn:], sin, cos)
    ckr = h @ attn["kv_a"]["kernel"].astype(F32)
    c = _rms_norm(ckr[..., :r], cfg["rms_norm_eps"], attn["kv_norm"]["scale"])
    k_rope = rotate_half(ckr[:, :, None, r:], sin, cos)[:, :, 0]           # (B, S, dr)
    kv = jnp.einsum("bsr,rnd->bsnd", c, attn["kv_b"]["kernel"].astype(F32))
    k_nope, v = kv[..., :dn], kv[..., dn:]

    # blocks of at most ROW_BLOCK queries against every key; the rows that
    # pad the last block lie past every key, see them all and are dropped
    rb = min(ROW_BLOCK, s)
    pad = -s % rb

    def blocked(x):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(b, -1, rb, *x.shape[2:]).swapaxes(0, 1)

    def block(xs):
        qn, qr, first = xs
        scores = sigma * (
            jnp.einsum("bqnd,bknd->bnqk", qn, k_nope) + jnp.einsum("bqnd,bkd->bnqk", qr, k_rope))
        seen = (first + jnp.arange(rb))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)

    out = lax.map(block, (blocked(q_nope), blocked(q_rope), jnp.arange(0, s + pad, rb)))
    out = out.swapaxes(0, 1).reshape(b, s + pad, n, -1)[:, :s]
    return out.reshape(b, s, -1) @ attn["o"]["kernel"].astype(F32)


def _swiglu(x, gate_up, down):
    """x (T, H) through gate_up (H, 2, I) and down (I, H)."""
    gate_up, down = gate_up.astype(F32), down.astype(F32)
    return (jax.nn.silu(x @ gate_up[:, 0]) * (x @ gate_up[:, 1])) @ down


def _experts(h, moe, experts, layer, cfg):
    """The sparse feed-forward of normed h (B, S, H): (y, routing margin
    (B, S)). ``experts`` are the stack's expert weights whole, (L, E, ...):
    one expert at a time is read where it lies, at ``[layer, e]``."""
    b, s, hdim = h.shape
    flat = h.reshape(b * s, hdim)
    k, n_experts = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    scores = jax.nn.sigmoid(flat @ moe["router"]["kernel"].astype(F32))
    ranked, top_i = lax.top_k(scores + moe["router"]["bias"].astype(F32), k + 1)
    # how clearly the router chose: the gap between the last expert taken and
    # the first one left out, of score + bias, relative to the former
    margin = ((ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]).reshape(b, s)
    taken = jnp.sum(jax.nn.one_hot(top_i[:, :k], n_experts, dtype=F32), axis=1)     # (T, E) 0/1
    gates = taken * scores
    gates = cfg["routed_scaling_factor"] * gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)

    def one_expert(e, acc):
        gate_up = experts["gate_up"][layer, e]
        down = experts["down"][layer, e]
        return acc + gates[:, e, None] * _swiglu(flat, gate_up, down)

    y = lax.fori_loop(
        0, n_experts, one_expert, _swiglu(flat, moe["shared"]["gate_up"], moe["shared"]["down"]))
    return y.reshape(b, s, hdim), margin


def _layer(streams, lp, experts, layer, cfg, sin, cos):
    """One block over the streams (B, S, n, C); (streams, routing margin
    (B, S)) — margin 1 for a dense layer."""
    eps = cfg["rms_norm_eps"]
    streams, _ = _connected(
        streams, lp["attn_hc"], cfg,
        lambda u: (_attention(_rms_norm(u, eps, lp["attn_norm"]["scale"]), lp["attn"], cfg, sin, cos), None))

    def feed_forward(u):
        h = _rms_norm(u, eps, lp["mlp_norm"]["scale"])
        if experts is None:
            b, s, hdim = h.shape
            y = _swiglu(h.reshape(b * s, hdim), lp["mlp"]["gate_up"], lp["mlp"]["down"]["kernel"])
            return y.reshape(b, s, hdim), jnp.ones((b, s), F32)
        return _experts(h, lp["moe"], experts, layer, cfg)

    return _connected(streams, lp["mlp_hc"], cfg, feed_forward)


def _head(x, params):
    """x (B, S, H) -> logits (B, S, V), the head a block of columns at a time."""
    kernel = params["lm_head"]["kernel"]       # tie_word_embeddings: false
    b, s, hdim = x.shape
    vocab = kernel.shape[1]
    flat = x.reshape(b * s, hdim)
    if vocab <= VOCAB_BLOCK or vocab % VOCAB_BLOCK:
        return (flat @ kernel.astype(F32)).reshape(b, s, vocab)

    def columns(i, logits):
        part = flat @ lax.dynamic_slice_in_dim(kernel, i * VOCAB_BLOCK, VOCAB_BLOCK, axis=1).astype(F32)
        return lax.dynamic_update_slice_in_dim(logits, part, i * VOCAB_BLOCK, axis=1)

    logits = lax.fori_loop(0, vocab // VOCAB_BLOCK, columns, jnp.zeros((b * s, vocab), F32))
    return logits.reshape(b, s, vocab)


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32. ``params`` in the
    program's layout: ``dense_layers`` and ``layers`` leaves carry a leading
    layer axis."""
    return forward_with_margin(params, cfg, ids)[0]


def forward_with_margin(params, cfg, ids, rows=None):
    """(logits (B, S, V), routing margin (B, S)): the margin is the smallest
    over the expert layers of each token's relative gap between the last
    taken of ``score + bias`` and the best one left out. ``rows`` (R,) keeps
    the head to those positions — logits (B, R, V), margin (B, R) — where a
    16k-row sequence's 131,072-wide logits would not fit
    (``tools/check_long_rows.py``); every layer still runs over every row."""
    sin, cos = yarn_tables(cfg, ids.shape[1])
    x = params["embed"]["embedding"][ids].astype(F32)
    streams = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (cfg["hc_mult"], x.shape[-1]))
    margins = []
    for stack in ("dense_layers", "layers"):
        if stack not in params:
            continue
        rest = {k: v for k, v in params[stack].items() if k != "moe"}
        experts = None
        if "moe" in params[stack]:
            # the experts' weights stay where they are, whole; a layer's
            # scan slice would be a copy of 64 experts
            experts = params[stack]["moe"]["experts"]
            rest["moe"] = {k: v for k, v in params[stack]["moe"].items() if k != "experts"}
        count = jax.tree.leaves(rest)[0].shape[0]

        def body(streams, xs, experts=experts):
            lp, layer = xs
            return _layer(streams, lp, experts, layer, cfg, sin, cos)

        streams, m = lax.scan(body, streams, (rest, jnp.arange(count)))
        margins.append(m)
    x, margin = jnp.sum(streams, axis=2), jnp.min(jnp.concatenate(margins), axis=0)
    if rows is not None:
        x, margin = x[:, rows], margin[:, rows]
    return _head(_rms_norm(x, cfg["rms_norm_eps"], params["final_norm"]["scale"]), params), margin


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
