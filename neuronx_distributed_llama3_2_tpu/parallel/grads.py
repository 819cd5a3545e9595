"""Gradient norm and clipping.

TPU-native replacement for the reference's ``parallel_layers/grads.py``. Most
of that file's complexity disappears under GSPMD:

- ``get_grad_norm`` (grads.py:33) needs TP-duplicate awareness and reductions
  over EDP/EMP/TP/PP groups (:62-105) because each torch rank holds a *local*
  grad shard. Here gradients are logically global arrays (physically sharded
  by GSPMD), so the global norm is a plain reduction — XLA inserts the
  cross-device psums from the sharding.
- ``bucket_allreduce_gradients`` (grads.py:243, 512MB buckets) is the DP
  gradient sync; under GSPMD the psum over the dp axes appears automatically
  when differentiating a dp-sharded-batch loss, scheduled/overlapped by XLA.
- ``allreduce_sequence_parallel_gradients`` (grads.py:313) synced grads of
  SP-tagged LayerNorm weights; GSPMD accounts those through the same
  mechanism.

What remains is the clipping policy itself (reference ``clip_grad_norm``
grads.py:180).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("grad_clip")
def global_norm(tree: Any) -> jax.Array:
    """L2 norm over a gradient pytree (reference get_grad_norm grads.py:33,
    minus the duplicate-grad bookkeeping GSPMD makes unnecessary)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


@jax.named_scope("grad_clip")
def clip_grad_norm(tree: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    """Scale the pytree so its global norm is at most ``max_norm``
    (reference clip_grad_norm grads.py:180). Returns (clipped, norm)."""
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    clipped = jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), tree)
    return clipped, norm
