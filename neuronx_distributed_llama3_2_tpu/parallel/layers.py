"""Tensor-parallel layers: column/row linear, embedding, GQA QKV.

TPU-native replacement for the reference's ``parallel_layers/layers.py`` and
``modules/qkv_linear.py``. The reference implements TP as per-rank shards with
hand-inserted collectives and autograd functions (``ColumnParallelLinear``
layers.py:460, ``RowParallelLinear`` :637, ``ParallelEmbedding`` :101,
``LinearWithAsyncCommunication`` :288, ``GQAQKVColumnParallelLinear``
qkv_linear.py:454). Under GSPMD the same layers are *global* math plus
PartitionSpecs: parameters are annotated (not sliced), XLA inserts the
all-gathers/reduce-scatters/all-reduces the reference hand-codes — including
the Megatron-SP placement (all-gather before column, reduce-scatter after row,
layers.py:312-318,793-797), which we pin with activation sharding constraints.

Each layer is a frozen dataclass with three methods:
  ``init(key) -> params``        global-shape parameter pytree
  ``specs() -> spec pytree``     PartitionSpecs, same structure as params
  ``__call__(params, x) -> y``   global math (+ sharding constraints)

The spec tree is the analogue of the reference's parameter tagging
(``set_tensor_model_parallel_attributes`` utils.py:48): it is what the
optimizer/checkpoint layers consume to know how a parameter is distributed.

Weight init follows the reference's determinism recipe (build the full master
weight from one seed, then shard — ``create_local_weight`` layers.py:58):
we init global arrays from a single key, so results are independent of tp.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.state import DP_AXIS, EP_AXIS, TP_AXIS

Params = Dict[str, Any]

# Batch (data-parallel) mesh axes for activations: dp and ep combined
# (reference DP group = dp_exp * ep, parallel_state.py:86-95).
BATCH_AXES = (DP_AXIS, EP_AXIS)


def _activation_spec(y: jax.Array, last_axis) -> P:
    """Spec for an activation (batch..., feature): batch dims over the DP
    axes (first dim only), middle dims unsharded — except the sequence dim
    of (B, S, F) activations, which rides the cp axis under context
    parallelism (ring attention, kernels/ring_attention.py) — and last dim
    ``last_axis``."""
    if y.ndim < 2:
        return P(last_axis)
    middle = [None] * (y.ndim - 2)
    if (
        y.ndim == 3
        and middle
        and parallel_state.model_parallel_is_initialized()
        and parallel_state.get_parallel_state().context_parallel_size > 1
    ):
        middle[0] = parallel_state.CP_AXIS
    return P(BATCH_AXES, *middle, last_axis)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """Apply a sharding constraint if parallel state is initialized (no-op
    otherwise, so layers also run un-meshed in pure single-device tests).

    Inside a partial-manual ``shard_map`` (e.g. the pipeline executor, manual
    over pp only) the constraint must be built against the *ambient abstract
    mesh* — whose manual axes are marked — not the concrete mesh; auto axes
    (tp/dp/ep) keep working there."""
    if not parallel_state.model_parallel_is_initialized():
        return x
    mesh = parallel_state.get_parallel_state().mesh
    ambient = jax.sharding.get_abstract_mesh()
    if not ambient.empty:
        mesh = ambient
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _normal_init(stddev: float) -> Callable:
    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)

    return init


default_kernel_init = _normal_init(0.02)


@dataclasses.dataclass(frozen=True)
class ColumnParallelLinear:
    """Y = X·A + b with A (in, out) sharded along *out* (reference
    layers.py:460; weight stored transposed there as (out/tp, in)).

    ``gather_output`` replicates Y over tp (reference ``gather_output`` arg);
    otherwise Y's last dim stays tp-sharded for a following RowParallel.
    When ``sequence_parallel`` is on, the input is sequence-sharded and XLA
    materializes the all-gather the reference embeds in
    ``LinearWithAsyncCommunication.forward`` (layers.py:312-318).
    """

    in_features: int
    out_features: int
    use_bias: bool = False
    gather_output: bool = False
    dtype: Any = jnp.float32
    kernel_init: Callable = default_kernel_init

    def init(self, key: jax.Array) -> Params:
        params = {
            "kernel": self.kernel_init(
                key, (self.in_features, self.out_features), self.dtype
            )
        }
        if self.use_bias:
            params["bias"] = jnp.zeros((self.out_features,), self.dtype)
        return params

    def specs(self) -> Params:
        s = {"kernel": P(None, TP_AXIS)}
        if self.use_bias:
            s["bias"] = P(TP_AXIS)
        return s

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        y = x @ params["kernel"]
        if self.use_bias:
            y = y + params["bias"]
        return constrain(
            y, _activation_spec(y, None if self.gather_output else TP_AXIS)
        )


@dataclasses.dataclass(frozen=True)
class RowParallelLinear:
    """Y = X·A + b with A (in, out) sharded along *in* (reference
    layers.py:637, weight (out, in/tp)). The input's last dim is expected
    tp-sharded (``input_is_parallel``); the contraction produces partial sums
    that XLA all-reduces — or reduce-scatters along the sequence dim when
    ``sequence_parallel`` (reference layers.py:793-797)."""

    in_features: int
    out_features: int
    use_bias: bool = False
    sequence_parallel: bool = False
    dtype: Any = jnp.float32
    kernel_init: Callable = default_kernel_init

    def init(self, key: jax.Array) -> Params:
        params = {
            "kernel": self.kernel_init(
                key, (self.in_features, self.out_features), self.dtype
            )
        }
        if self.use_bias:
            params["bias"] = jnp.zeros((self.out_features,), self.dtype)
        return params

    def specs(self) -> Params:
        s = {"kernel": P(TP_AXIS, None)}
        if self.use_bias:
            s["bias"] = P(None)
        return s

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        y = x @ params["kernel"]
        if self.use_bias:
            y = y + params["bias"]
        if self.sequence_parallel:
            # Output sequence-sharded over tp — the reference's
            # reduce-scatter-to-SP output mode (layers.py:793-797).
            # Supported layouts: (B, S, H) and token-flattened (S, H).
            if y.ndim == 3:
                y = constrain(y, P(BATCH_AXES, TP_AXIS, None))
            elif y.ndim == 2:
                y = constrain(y, P(TP_AXIS, None))
            else:
                raise ValueError(
                    f"sequence_parallel RowParallelLinear expects rank 2 or 3 "
                    f"activations, got shape {y.shape}"
                )
        else:
            y = constrain(y, _activation_spec(y, None))
        return y


@dataclasses.dataclass(frozen=True)
class ParallelEmbedding:
    """Embedding table sharded along the vocab dim (reference
    ``ParallelEmbedding`` layers.py:101: mask + local lookup + all-reduce,
    :215-238). Under GSPMD a plain ``take`` on the vocab-sharded table lowers
    to the same masked-lookup + all-reduce."""

    num_embeddings: int
    embedding_dim: int
    dtype: Any = jnp.float32
    embedding_init: Callable = default_kernel_init
    # "vocab" (default, reference shard_across_embedding=False) or "embed"
    shard_dim: str = "vocab"

    def __post_init__(self):
        if self.shard_dim not in ("vocab", "embed"):
            raise ValueError(
                f"shard_dim must be 'vocab' or 'embed', got {self.shard_dim!r}"
            )

    def init(self, key: jax.Array) -> Params:
        return {
            "embedding": self.embedding_init(
                key, (self.num_embeddings, self.embedding_dim), self.dtype
            )
        }

    def specs(self) -> Params:
        if self.shard_dim == "vocab":
            return {"embedding": P(TP_AXIS, None)}
        return {"embedding": P(None, TP_AXIS)}

    @jax.named_scope("embed")
    def __call__(self, params: Params, ids: jax.Array) -> jax.Array:
        y = jnp.take(params["embedding"], ids, axis=0)
        # vocab-sharded: output replicated over tp (post-all-reduce, reference
        # layers.py:215-238); embed-sharded: output stays tp-sharded.
        last = None if self.shard_dim == "vocab" else TP_AXIS
        return constrain(y, _activation_spec(y, last))


@dataclasses.dataclass(frozen=True)
class GQAQKVColumnParallelLinear:
    """Fused grouped-query Q/K/V projection (reference
    ``GQAQKVColumnParallelLinear`` qkv_linear.py:454).

    The reference replicates KV heads ``kv_size_multiplier`` times so that tp
    divides the KV head count, with KV-replica process groups summing KV grads
    (qkv_linear.py:34,250-256). Under GSPMD no replica groups are needed: when
    tp > num_kv_heads we keep the K/V kernels *replicated* over tp (each
    device computes all KV heads — the logical equivalent of full replication)
    and XLA sums their gradient contributions automatically. When tp divides
    num_kv_heads, K/V shard like Q.
    """

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    use_bias: bool = False
    dtype: Any = jnp.float32
    kernel_init: Callable = default_kernel_init
    # Explicit override for tests; None = read the live parallel state. The
    # lookup is deliberately lazy (per specs()/__call__ invocation) so a layer
    # constructed before initialize_model_parallel() still resolves the
    # correct sharded-vs-replicated KV layout once the mesh is up — specs()
    # and __call__ can't disagree because re-initializing the mesh requires
    # destroy_model_parallel() + re-placing the params anyway.
    tensor_parallel_size: Optional[int] = None
    # shardlint SL002: the lazy _tp() lookup above reads the live parallel
    # state, so the traced layout depends on it
    __layout_deps__ = ("tensor_parallel_size_or",)

    def _tp(self) -> int:
        if self.tensor_parallel_size is not None:
            return self.tensor_parallel_size
        return parallel_state.tensor_parallel_size_or(1)

    def _kv_sharded(self) -> bool:
        return self.num_kv_heads % self._tp() == 0

    def _kv_flat_sharded(self) -> bool:
        """tp > kv_heads but tp divides the flat kv projection width: the
        K/V kernels shard over the flat (kv·head_dim) output dim — every
        device stores 1/tp of the weight instead of a full replica (the
        GSPMD analogue of the reference's kv_size_multiplier resharding,
        qkv_linear.py:454; the consumer re-shards the activation by
        repeating heads, see LlamaAttention)."""
        tp = self._tp()
        return (
            not self._kv_sharded()
            and tp % self.num_kv_heads == 0
            and (self.num_kv_heads * self.head_dim) % tp == 0
            # the consumer repeats KV heads to exactly tp, so Q heads must
            # also shard over tp or the GQA group count collapses to zero
            and self.num_heads % tp == 0
        )

    def kv_repeat_factor(self) -> int:
        """How many times the consumer must repeat KV heads so the attention
        activations shard 1 head/device (1 = no repeat needed). The public
        face of the flat-sharding decision — keeps all sharding arithmetic
        inside this layer."""
        return self._tp() // self.num_kv_heads if self._kv_flat_sharded() else 1

    def init(self, key: jax.Array) -> Params:
        kq, kk, kv = jax.random.split(key, 3)
        q_out = self.num_heads * self.head_dim
        kv_out = self.num_kv_heads * self.head_dim
        params = {
            "q_kernel": self.kernel_init(kq, (self.hidden_size, q_out), self.dtype),
            "k_kernel": self.kernel_init(kk, (self.hidden_size, kv_out), self.dtype),
            "v_kernel": self.kernel_init(kv, (self.hidden_size, kv_out), self.dtype),
        }
        if self.use_bias:
            params["q_bias"] = jnp.zeros((q_out,), self.dtype)
            params["k_bias"] = jnp.zeros((kv_out,), self.dtype)
            params["v_bias"] = jnp.zeros((kv_out,), self.dtype)
        return params

    def specs(self) -> Params:
        if self._kv_sharded() or self._kv_flat_sharded():
            kv_spec, kv_bias = P(None, TP_AXIS), P(TP_AXIS)
        else:
            kv_spec, kv_bias = P(None, None), P(None)
        s = {
            "q_kernel": P(None, TP_AXIS),
            "k_kernel": kv_spec,
            "v_kernel": kv_spec,
        }
        if self.use_bias:
            s["q_bias"] = P(TP_AXIS)
            s["k_bias"] = kv_bias
            s["v_bias"] = kv_bias
        return s

    def __call__(self, params: Params, x: jax.Array):
        q = x @ params["q_kernel"]
        k = x @ params["k_kernel"]
        v = x @ params["v_kernel"]
        if self.use_bias:
            q = q + params["q_bias"]
            k = k + params["k_bias"]
            v = v + params["v_bias"]
        q = constrain(q, _activation_spec(q, TP_AXIS))
        # flat-sharded kv (tp > kv_heads) deliberately leaves the activation
        # unconstrained: the flat shard boundary (kv_out/tp) falls mid-head,
        # and pinning that layout miscompiles in older CPU SPMD partitioners
        # (~5e-3 error) while buying nothing — the consumer repeats heads and
        # re-constrains to 1 head/device right after (see LlamaAttention).
        # Only the *kernel* needs the flat sharding (1/tp weight per device).
        kv_axis = TP_AXIS if self._kv_sharded() else None
        k = constrain(k, _activation_spec(k, kv_axis))
        v = constrain(v, _activation_spec(v, kv_axis))
        return q, k, v


def psum_cpu_bf16_safe(v, axis_name: str):
    """``lax.psum`` that round-trips bf16 through fp32 on XLA:CPU — the
    same "Invalid binary instruction opcode copy" abort class as
    :func:`shardmap_cpu_bf16_workaround` (boundary leaves), applied to
    in-region psums. The backend-sensitive predicate lives HERE only."""
    import jax.numpy as jnp
    from jax import lax

    if jax.default_backend() == "cpu" and v.dtype == jnp.bfloat16:
        return lax.psum(v.astype(jnp.float32), axis_name).astype(v.dtype)
    return lax.psum(v, axis_name)


def shardmap_cpu_bf16_workaround(tree: Any):
    """Returns ``(boundary_tree, restore_fn)`` for passing ``tree`` across a
    (partial-)manual ``shard_map`` boundary.

    XLA:CPU — the virtual test mesh — aborts compiling the gradient psum of
    bf16 leaves that cross such a boundary ("Invalid binary instruction
    opcode copy", hlo_instruction.cc). The workaround: round-trip bf16
    leaves through fp32 at the boundary (exact: bf16→f32→bf16) and restore
    each leaf's original dtype inside the body with ``restore_fn``. On TPU
    (or for bf16-free trees) both returns are identities. One shared
    implementation for every executor that hits this (MoE EP a2a,
    interleaved VPP) so the backend-sensitive condition lives in one place.
    """
    active = jax.default_backend() == "cpu" and any(
        getattr(leaf, "dtype", None) == jnp.bfloat16
        for leaf in jax.tree.leaves(tree)
    )
    if not active:
        return tree, lambda t: t
    dtypes = jax.tree.map(lambda leaf: leaf.dtype, tree)
    up = jax.tree.map(
        lambda leaf: leaf.astype(jnp.float32)
        if leaf.dtype == jnp.bfloat16
        else leaf,
        tree,
    )

    def restore(t):
        return jax.tree.map(lambda leaf, d: leaf.astype(d), t, dtypes)

    return up, restore


def shard_pytree(tree: Any, specs: Any, mesh=None) -> Any:
    """Place a parameter pytree on the mesh per its spec tree (the runtime
    counterpart of the reference's ``set_tensor_model_parallel_attributes``
    tagging + per-rank slicing, utils.py:48 / layers.py:58 — here placement is
    a device_put of the *global* array with a NamedSharding)."""
    if mesh is None:
        mesh = parallel_state.get_parallel_state().mesh
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), tree, specs
    )


def divide(numerator: int, denominator: int) -> int:
    """reference utils.py:78-87."""
    if numerator % denominator != 0:
        raise ValueError(f"{numerator} is not divisible by {denominator}")
    return numerator // denominator
