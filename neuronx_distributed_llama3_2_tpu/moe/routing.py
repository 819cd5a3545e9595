"""MoE routers: linear router + TopK / Sinkhorn assignment.

TPU-native replacement for the reference's ``modules/moe/routing.py``
(``RouterBase`` :9, ``RouterTopK`` :89, ``RouterSinkhorn`` :123 with the
fixed-iteration Sinkhorn :186-218 that keeps the graph static). The
reference computes router activations in fp64 (:56-63) for determinism;
TPU has no fast fp64, so everything here is fp32 (the substitution VERDICT/
SURVEY §7 prescribe) — parity tests budget for it.

The router weight is replicated; its gradient is summed over tp by GSPMD
(the reference needs ``LinearWithWeightGradAR`` moe_parallel_layers.py:319
because it defers the down-proj all-reduce; no deferral exists here).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Router:
    """Linear router producing fp32 logits (reference LinearRouter,
    moe_parallel_layers.py:348)."""

    hidden_size: int
    num_experts: int
    dtype: Any = jnp.float32
    # a learned per-expert selection bias beside the kernel
    # (:func:`sigmoid_bias_routing`); seeded small and non-zero, so that a
    # seeded model's choice of experts depends on it
    selection_bias: bool = False

    def init(self, key: jax.Array) -> Params:
        scale = self.hidden_size ** -0.5
        kernel = jax.random.normal(
            key, (self.hidden_size, self.num_experts), jnp.float32
        ) * scale
        if not self.selection_bias:
            return {"kernel": kernel}
        bias = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 1), (self.num_experts,), jnp.float32
        )
        return {"kernel": kernel, "bias": bias}

    def specs(self) -> Params:
        from jax.sharding import PartitionSpec as P

        specs = {"kernel": P(None, None)}
        if self.selection_bias:
            specs["bias"] = P(None)
        return specs

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        """x (T, H) -> logits (T, E) fp32 (router math always fp32;
        reference casts to fp64 at routing.py:56-63)."""
        return x.astype(jnp.float32) @ params["kernel"].astype(jnp.float32)


def top_k_routing(
    logits: jax.Array, top_k: int, normalize: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Softmax-then-top-k assignment (reference RouterTopK routing.py:89).

    Returns (gates (T, k) fp32, expert_idx (T, k) int32). ``normalize``
    renormalizes the selected affinities to sum to 1 (Mixtral convention,
    reference normalize_top_k_affinities)."""
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    if normalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates.astype(jnp.float32), idx.astype(jnp.int32)


def sigmoid_bias_routing(
    logits: jax.Array, bias: jax.Array, top_k: int, scale: float = 1.0,
    normalize: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores with a selection bias (the aux-loss-free router of the
    DeepSeek-V3 lineage): ``s = sigmoid(logits)``; the ``top_k`` experts are
    the largest of ``s + bias`` — the learned per-expert ``bias`` chooses and
    never weighs — and the gates are the chosen experts' *unbiased* scores,
    renormalised to sum to one (``normalize``, HF ``norm_topk_prob``) and
    multiplied by ``scale`` (``routed_scaling_factor``). Returns (gates
    (T, k) fp32, idx (T, k))."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return (scale * gates).astype(jnp.float32), idx.astype(jnp.int32)


def sinkhorn(cost: jax.Array, n_iters: int = 3) -> jax.Array:
    """Fixed-iteration Sinkhorn normalization in log space (reference
    routing.py:186-218 — fixed iterations so the compiled graph is static;
    the reference's convergence tolerance is dropped for the same reason the
    iteration count is fixed)."""
    log_p = cost
    for _ in range(n_iters):
        log_p = log_p - jax.scipy.special.logsumexp(log_p, axis=1, keepdims=True)
        log_p = log_p - jax.scipy.special.logsumexp(log_p, axis=0, keepdims=True)
    return jnp.exp(log_p)


def sinkhorn_routing(
    logits: jax.Array, top_k: int, n_iters: int = 3, normalize: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Sinkhorn-balanced assignment (reference RouterSinkhorn routing.py:123):
    expert choice comes from the Sinkhorn-normalized matrix (balanced), gate
    values from the raw logits (differentiable).

    For top_k == 1 the gate is ``sigmoid(logit)`` (the reference's sinkhorn
    activation, routing.py:56-63) — a normalized softmax gate would be the
    constant 1.0 and starve the router of task-loss gradient."""
    balanced = sinkhorn(logits, n_iters)
    _, idx = jax.lax.top_k(balanced, top_k)
    if top_k == 1:
        gates = jax.nn.sigmoid(jnp.take_along_axis(logits, idx, axis=-1))
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
        if normalize:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates.astype(jnp.float32), idx.astype(jnp.int32)
