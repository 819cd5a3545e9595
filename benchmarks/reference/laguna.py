"""Laguna-XS.2 (HF ``model_type: laguna``) in plain float32: one causal pass
over the whole sequence, no cache, no ring, no chunks. A pre-norm residual
block of RMSNorm (eps 1e-6), attention and a feed-forward; no bias anywhere,
an untied output head.

Written out here and read from no flag:

- **Two kinds of layer, by the published list** ``layer_types``. Layer l has
  ``num_attention_heads_per_layer[l]`` query heads of 128 over 8 kv heads
  (query head m reads kv head ``m // (n / 8)``). Scores ``q_i . k_j /
  sqrt(128)``, softmax in float32 over the visible j — *full*: ``j <= i``;
  *window*: ``i - sliding_window < j <= i`` (512 keys, the token itself among
  them: ``kv_idx > q_idx - sliding_window``).
- **A rotary table a kind** (``rope_parameters``), dimensions paired as
  halves, the rotated half first. *window*: plain, theta 1e4, all 128
  dimensions. *full*: theta 5e5 on the first ``partial_rotary_factor`` (64) of
  each head, the rest pass through; YaRN as ``transformers`` computes
  ``rope_type: yarn``: frequency i is blended between ``theta_i`` and
  ``theta_i / factor`` by a linear ramp between the dimension that turns
  ``beta_fast`` times in the original context (rounded down) and the one that
  turns ``beta_slow`` times (rounded up); cos and sin both times
  ``attention_factor``.
- **The output gate**: ``y_m <- sigmoid(h . W_g)_m * y_m``, one scalar a query
  head a token from the layer's normed input h, before ``W_o``.
- **The feed-forward by** ``mlp_layer_types``: *dense* — SwiGLU of
  ``intermediate_size``; *sparse* — ``p = softmax(h W_r)`` over all experts in
  float32, the ``num_experts_per_tok`` largest, gates ``p_i / sum_chosen p_j *
  moe_routed_scaling_factor`` on the experts' outputs, plus one shared SwiGLU
  on every token, ungated.

``params`` come in the program's layout — one stack of weights a layer shape
(``full_dense_layers``, ``window_layers``, ``full_layers``), the layers of a
stack in their published order — and the published lists say which stack's
next layer comes next. Each expert is applied to every token and weighted by
its gate (zero where the router did not choose it), one at a time by a scan so
only one expert's float32 copy is alive; attention runs over blocks of 512
queries so no (heads, S, S) tensor is held."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import F32, head_kernel, next_token_loss, rope_tables, rotate_half

QUERY_ROWS = 512
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def yarn_tables(rope, dims: int, seq: int):
    """(sin, cos) of shape (seq, dims) under ``rope_type: yarn``."""
    theta = rope["rope_theta"]
    inv = 1.0 / (theta ** (jnp.arange(0, dims, 2, dtype=F32) / dims))

    def dim_that_turns(n):      # the rotary dimension that turns n times in the original context
        return dims * math.log(rope["original_max_position_embeddings"] / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_that_turns(rope["beta_slow"])), dims - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dims // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / rope["factor"] * ramp
    freqs = jnp.outer(jnp.arange(seq, dtype=F32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb) * rope["attention_factor"], jnp.cos(emb) * rope["attention_factor"]


def tables(cfg, seq: int):
    """kind -> (sin, cos) over the kind's rotated dimensions."""
    out = {}
    for kind, key in (("full", "full_attention"), ("window", "sliding_attention")):
        rope = cfg["rope_parameters"][key]
        dims = int(cfg["head_dim"] * rope["partial_rotary_factor"])
        if rope["rope_type"] == "yarn":
            out[kind] = yarn_tables(rope, dims, seq)
        else:
            out[kind] = rope_tables(dims, seq, rope["rope_theta"])
    return out


def _rotate_leading(x, sin, cos):
    """Rotate the first ``sin.shape[-1]`` dimensions of each head."""
    r = sin.shape[-1]
    return jnp.concatenate([rotate_half(x[..., :r], sin, cos), x[..., r:]], axis=-1)


def _attention(h, attn, cfg, kind, sin, cos):
    b, s, _ = h.shape
    d, nkv = cfg["head_dim"], cfg["num_key_value_heads"]
    q = h @ attn["qkv"]["q_kernel"].astype(F32)
    n = q.shape[-1] // d
    q = _rotate_leading(q.reshape(b, s, n, d), sin, cos)
    k = _rotate_leading((h @ attn["qkv"]["k_kernel"].astype(F32)).reshape(b, s, nkv, d), sin, cos)
    v = (h @ attn["qkv"]["v_kernel"].astype(F32)).reshape(b, s, nkv, d)
    k, v = jnp.repeat(k, n // nkv, axis=2), jnp.repeat(v, n // nkv, axis=2)
    j = jnp.arange(s)[None, :]
    blocks = []
    for first in range(0, s, QUERY_ROWS):
        i = jnp.arange(first, min(first + QUERY_ROWS, s))[:, None]
        seen = j <= i
        if kind == "window":
            seen &= j > i - cfg["sliding_window"]
        scores = jnp.einsum("bqnd,bknd->bnqk", q[:, first:first + QUERY_ROWS], k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bnqk,bknd->bqnd", probs, v))
    y = jnp.concatenate(blocks, axis=1)
    gate = jax.nn.sigmoid(h @ attn["out_gate"]["kernel"].astype(F32))      # (B, S, n)
    return (y * gate[..., None]).reshape(b, s, n * d) @ attn["o"]["kernel"].astype(F32)


def _swiglu(x, gate_up, down):
    """x (T, H) through gate_up (H, 2, I) and down (I, H)."""
    gate_up, down = gate_up.astype(F32), down.astype(F32)
    return (jax.nn.silu(x @ gate_up[:, 0]) * (x @ gate_up[:, 1])) @ down


def _layer(x, stack, index, cfg, kind, sin, cos):
    """Layer ``index`` of the weights ``stack``; (x, routing margin (B, S)) —
    margin 1 for a dense layer. The experts' weights are read out of the stack
    one expert at a time: a layer's slice of them would be a copy of it."""
    lp = jax.tree.map(lambda a: a[index], {k: v for k, v in stack.items() if k != "moe"})
    b, s, hdim = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["attn_norm"]["scale"], eps), lp["attn"], cfg, kind, sin, cos)
    flat = _rms_norm(x, lp["mlp_norm"]["scale"], eps).reshape(b * s, hdim)
    if "mlp" in lp:
        y = _swiglu(flat, lp["mlp"]["gate_up"], lp["mlp"]["down"]["kernel"])
        return x + y.reshape(b, s, hdim), jnp.ones((b, s), F32)

    moe, k = stack["moe"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(flat @ moe["router"]["kernel"][index].astype(F32), axis=-1)
    ranked, top_i = lax.top_k(probs, k + 1)
    # how clearly the router chose: the gap between the last expert taken and
    # the first one left out, relative to the former
    margin = ((ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]).reshape(b, s)
    chosen = jnp.sum(jax.nn.one_hot(top_i[:, :k], cfg["num_experts"], dtype=F32), axis=1)  # (T, E) 0/1
    gates = chosen * probs
    gates = cfg["moe_routed_scaling_factor"] * gates / jnp.sum(gates, axis=-1, keepdims=True)

    def one_expert(acc, xs):
        e, g = xs                       # the expert's number, its gates (T,)
        gate_up = lax.dynamic_index_in_dim(moe["experts"]["gate_up"][index], e, 0, keepdims=False)  # (H, 2, I)
        down = lax.dynamic_index_in_dim(moe["experts"]["down"][index], e, 0, keepdims=False)        # (I, H)
        return acc + g[:, None] * _swiglu(flat, gate_up, down), None

    y, _ = lax.scan(
        one_expert, _swiglu(flat, moe["shared"]["gate_up"][index], moe["shared"]["down"][index]),
        (jnp.arange(cfg["num_experts"]), gates.T),
    )
    return x + y.reshape(b, s, hdim), margin


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32."""
    return forward_with_margin(params, cfg, ids)[0]


def forward_with_margin(params, cfg, ids):
    """(logits (B, S, V), routing margin (B, S)): the margin is the smallest
    over the expert layers of each token's relative gap between the last
    chosen probability (the eighth of 256 as published) and the best one left
    out."""
    rope = tables(cfg, ids.shape[1])
    x = params["embed"]["embedding"][ids].astype(F32)
    margins, taken = [], {}
    for layer_type, mlp in zip(cfg["layer_types"], cfg["mlp_layer_types"]):
        kind = KINDS[layer_type]
        stack = f"{kind}_layers" if mlp == "sparse" else f"{kind}_dense_layers"
        index = taken.get(stack, 0)
        taken[stack] = index + 1
        x, m = _layer(x, params[stack], index, cfg, kind, *rope[kind])
        margins.append(m)
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ head_kernel(params), jnp.min(jnp.stack(margins), axis=0)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
