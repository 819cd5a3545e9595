"""SmallThinker-21BA3B (``model_name: smallthinker_21b_instruct``) in plain
float32: one causal pass over the whole sequence, no cache, no ring, no
chunks. A pre-norm residual block of RMSNorm (eps 1e-6), attention and routed
experts; no bias anywhere, no QK-norm, an untied output head.

Written out here and read from no flag (x: (T, H); layer l):

    h   = RMSNorm(x; g1)
    r   = h . W_r                     -- routed HERE, from the layer's normed input
    p   = softmax(r) over all experts, float32; S = the k largest
    gate_e = p_e / sum_{S} p          (e in S)
    q, k, v = h . W_q, h . W_k, h . W_v        (n heads over n_kv kv heads of d)
    rope_layout[l] == 1:  q, k <- rotary (theta, the whole head, dimensions
                          paired as halves, no scaling);  == 0: no position
    sliding_window_layout[l] == 1:  i - window < j <= i   (window keys, the
                          token itself among them);  == 0:  j <= i
    a   = softmax(q k^T / sqrt(d) + mask) v;   x' = x + concat(a) . W_o
    h2  = RMSNorm(x'; g2)
    y   = sum_{e in S} gate_e . W_down,e ( relu(h2 . W_gate,e) * (h2 . W_up,e) )
    out = x' + y

The router reads h — the state *before* attention — and its routes weigh the
experts of h2, the state after it. Query head m reads kv head ``m // (n /
n_kv)`` (7 query heads a kv head as published).

``params`` come in the program's layout — one stack of weights a layer kind
(``full_layers``, ``window_layers``), the layers of a stack in their published
order — and ``sliding_window_layout`` says which stack's next layer comes next.
Each expert is applied to every token and weighted by its gate (zero where the
router did not choose it), one at a time by a scan so only one expert's float32
copy is alive; attention runs over blocks of 512 queries so no (heads, S, S)
tensor is held."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import F32, head_kernel, next_token_loss, rope_tables, rotate_half

QUERY_ROWS = 512


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _attention(h, attn, cfg, window: bool, rope):
    b, s, _ = h.shape
    d, nkv = cfg["head_dim"], cfg["num_key_value_heads"]
    q = h @ attn["qkv"]["q_kernel"].astype(F32)
    n = q.shape[-1] // d
    q = q.reshape(b, s, n, d)
    k = (h @ attn["qkv"]["k_kernel"].astype(F32)).reshape(b, s, nkv, d)
    v = (h @ attn["qkv"]["v_kernel"].astype(F32)).reshape(b, s, nkv, d)
    if rope is not None:
        q, k = rotate_half(q, *rope), rotate_half(k, *rope)
    k, v = jnp.repeat(k, n // nkv, axis=2), jnp.repeat(v, n // nkv, axis=2)
    j = jnp.arange(s)[None, :]
    blocks = []
    for first in range(0, s, QUERY_ROWS):
        i = jnp.arange(first, min(first + QUERY_ROWS, s))[:, None]
        seen = j <= i
        if window:
            seen &= j > i - cfg["sliding_window_size"]
        scores = jnp.einsum("bqnd,bknd->bnqk", q[:, first:first + QUERY_ROWS], k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bnqk,bknd->bqnd", probs, v))
    return jnp.concatenate(blocks, axis=1).reshape(b, s, n * d) @ attn["o"]["kernel"].astype(F32)


def _reglu(x, gate_up, down):
    """x (T, H) through gate_up (H, 2, I) and down (I, H)."""
    gate_up, down = gate_up.astype(F32), down.astype(F32)
    return (jax.nn.relu(x @ gate_up[:, 0]) * (x @ gate_up[:, 1])) @ down


def _layer(x, stack, index, cfg, window: bool, rope):
    """Layer ``index`` of the weights ``stack``; (x, routing margin (B, S)).
    The experts' weights are read out of the stack one expert at a time: a
    layer's slice of them would be a copy of it."""
    lp = jax.tree.map(lambda a: a[index], {k: v for k, v in stack.items() if k != "moe"})
    moe, k = stack["moe"], cfg["moe_num_active_primary_experts"]
    b, s, hdim = x.shape
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, lp["attn_norm"]["scale"], eps)

    # the routes, from the layer's normed input
    probs = jax.nn.softmax(h.reshape(b * s, hdim) @ moe["router"]["kernel"][index].astype(F32), axis=-1)
    ranked, top_i = lax.top_k(probs, k + 1)
    # how clearly the router chose: the gap between the last expert taken and
    # the first one left out, relative to the former
    margin = ((ranked[:, k - 1] - ranked[:, k]) / ranked[:, k - 1]).reshape(b, s)
    chosen = jnp.sum(jax.nn.one_hot(top_i[:, :k], probs.shape[-1], dtype=F32), axis=1)    # (T, E) 0/1
    gates = chosen * probs
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    x = x + _attention(h, lp["attn"], cfg, window, rope)
    flat = _rms_norm(x, lp["mlp_norm"]["scale"], eps).reshape(b * s, hdim)

    def one_expert(acc, xs):
        e, g = xs                       # the expert's number, its gates (T,)
        gate_up = lax.dynamic_index_in_dim(moe["experts"]["gate_up"][index], e, 0, keepdims=False)  # (H, 2, I)
        down = lax.dynamic_index_in_dim(moe["experts"]["down"][index], e, 0, keepdims=False)        # (I, H)
        return acc + g[:, None] * _reglu(flat, gate_up, down), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(flat), (jnp.arange(probs.shape[-1]), gates.T))
    return x + y.reshape(b, s, hdim), margin


def forward_logits(params, cfg, ids):
    """ids (B, S) int32 -> logits (B, S, V) float32."""
    return _forward(params, cfg, ids)[0]


def forward_with_margin(params, cfg, ids):
    """(logits, routing margin) as tuples of one (S, V) and one (S,) array a
    sequence of the batch: ``check.py`` takes ``logits[0]``, which on a (1, S,
    V) device array is an eager slice and an eager squeeze — two more copies of
    S x 151,936 float32 beside this one (chip run, PR 57: the check's peak read
    the engine + 3.0 copies; 7,184 rows are 4.37 GB a copy) — and on a tuple is
    no device work at all. The margin is the smallest over the layers of each
    token's relative gap between the last chosen probability (the sixth of 64
    as published) and the best one left out."""
    logits, margin = _forward(params, cfg, ids)
    return tuple(logits), tuple(margin)


def _forward(params, cfg, ids):
    """(logits (B, S, V), routing margin (B, S))."""
    rope = rope_tables(cfg["head_dim"], ids.shape[1], cfg["rope_theta"])
    x = params["embed"]["embedding"][ids].astype(F32)
    margins, taken = [], {}
    for window, rotary in zip(cfg["sliding_window_layout"], cfg["rope_layout"]):
        stack = "window_layers" if window else "full_layers"
        index = taken.get(stack, 0)
        taken[stack] = index + 1
        x, m = _layer(x, params[stack], index, cfg, bool(window), rope if rotary else None)
        margins.append(m)
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ head_kernel(params), jnp.min(jnp.stack(margins), axis=0)


def loss(params, cfg, ids):
    return next_token_loss(forward_logits(params, cfg, ids), ids)
