"""Collective mappings for shard_map bodies.

TPU-native replacement for the reference's ``parallel_layers/mappings.py``.
The reference implements each mapping as a hand-written torch
autograd.Function pair (``_CopyToModelParallelRegion`` mappings.py:165,
``_ReduceFromModelParallelRegion`` :183, ``_ScatterToModelParallelRegion``
:201, ``_GatherFromModelParallelRegion`` :219, the sequence-parallel variants
:237-308, and the expert-parallel all-to-all :311) because torch autograd
cannot differentiate through xm.* collectives.

JAX can. Every collective primitive used here carries its transpose rule —
``all_gather`` ↔ ``psum_scatter``, ``all_to_all`` ↔ ``all_to_all``,
``dynamic_slice`` ↔ scatter-add — and ``shard_map`` tracks replication
(varying-mesh-axes) so gradients of replicated inputs/outputs are accounted
exactly once. The reference's fwd/bwd pair table therefore collapses to thin
wrappers; differentiation produces the same collective pairs the reference
hand-codes (e.g. grad of the SP all-gather is exactly the reference's
reduce-scatter, mappings.py:255-290).

These functions are meant to run *inside* ``jax.shard_map`` over the mesh
built by :mod:`.state`. Under pure GSPMD (sharding-constraint) execution they
are not needed — XLA inserts equivalent collectives from annotations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from neuronx_distributed_llama3_2_tpu.parallel.state import EP_AXIS, TP_AXIS


# ---------------------------------------------------------------------------
# TP region entry/exit (reference mappings.py:165-235)
# ---------------------------------------------------------------------------

def copy_to_tensor_model_parallel_region(x: jax.Array) -> jax.Array:
    """Identity fwd; grad accumulates over tp via shard_map's replication
    accounting (reference _CopyToModelParallelRegion mappings.py:165)."""
    return x


def reduce_from_tensor_model_parallel_region(x: jax.Array) -> jax.Array:
    """All-reduce partial sums over tp (reference mappings.py:183)."""
    return lax.psum(x, TP_AXIS)


def gather_from_tensor_model_parallel_region(x: jax.Array, dim: int = -1) -> jax.Array:
    """All-gather shards along ``dim`` (reference mappings.py:219); grad is
    the split back to the local shard."""
    return _all_gather(x, TP_AXIS, dim)


def scatter_to_tensor_model_parallel_region(x: jax.Array, dim: int = -1) -> jax.Array:
    """Keep this rank's shard of ``dim`` (reference mappings.py:201)."""
    return _split_local(x, TP_AXIS, dim)


# ---------------------------------------------------------------------------
# Sequence-parallel region (reference mappings.py:237-308). The sequence dim
# is sharded over the *tp* axis — the reference has no separate SP group
# (SURVEY.md §5 long-context).
# ---------------------------------------------------------------------------

def scatter_to_sequence_parallel_region(x: jax.Array, dim: int = 0) -> jax.Array:
    """Enter SP region (reference _ScatterToSequenceParallelRegion :237)."""
    return _split_local(x, TP_AXIS, dim)


def gather_from_sequence_parallel_region(x: jax.Array, dim: int = 0) -> jax.Array:
    """Exit SP region; JAX's all_gather transpose is psum_scatter — exactly
    the reference's bwd reduce-scatter (_GatherFromSequenceParallelRegion
    :255)."""
    return _all_gather(x, TP_AXIS, dim)


def reduce_scatter_to_sequence_parallel_region(x: jax.Array, dim: int = 0) -> jax.Array:
    """Reduce partial sums and scatter along seq dim; transpose is all-gather
    (reference _ReduceScatterToSequenceParallelRegion :292)."""
    return _reduce_scatter(x, TP_AXIS, dim)


# ---------------------------------------------------------------------------
# Raw collectives (reference mappings.py:42-163)
# ---------------------------------------------------------------------------

def _all_gather(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    dim = dim % x.ndim
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)


def _reduce_scatter(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    dim = dim % x.ndim
    return lax.psum_scatter(x, axis_name, scatter_dimension=dim, tiled=True)


def _split_local(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    dim = dim % x.ndim
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if x.shape[dim] % size != 0:
        raise ValueError(
            f"dim {dim} of shape {x.shape} not divisible by axis {axis_name} size {size}"
        )
    shard = x.shape[dim] // size
    return lax.dynamic_slice_in_dim(x, idx * shard, shard, axis=dim)


# ---------------------------------------------------------------------------
# Expert parallelism (reference mappings.py:311-486)
# ---------------------------------------------------------------------------

def all_to_all_expert_parallel(
    x: jax.Array, split_dim: int, concat_dim: int
) -> jax.Array:
    """All-to-all over the ep axis (reference
    _AllToAllInExpertParallelRegion mappings.py:311; raw op :149).

    XLA:CPU (the virtual test mesh) crashes compiling the *gradient* of a
    bf16 all-to-all ("Invalid binary instruction opcode copy"), so on the cpu
    backend sub-fp32 payloads ride the wire as fp32. TPU is unaffected and
    keeps the narrow dtype (half the ICI bytes)."""
    if jax.default_backend() == "cpu" and x.dtype in (
        jnp.bfloat16,
        jnp.float16,
    ):
        orig = x.dtype
        return lax.all_to_all(
            x.astype(jnp.float32), EP_AXIS, split_axis=split_dim,
            concat_axis=concat_dim, tiled=True,
        ).astype(orig)
    return lax.all_to_all(
        x, EP_AXIS, split_axis=split_dim, concat_axis=concat_dim, tiled=True
    )


def enter_expert_parallel_region(x: jax.Array) -> jax.Array:
    """(e, c, h) -> (e/ep, ep*c, h): each ep rank receives every rank's tokens
    for its local experts (reference enter_expert_parallel_region
    mappings.py:412)."""
    e, _, _ = x.shape
    ep = lax.axis_size(EP_AXIS)
    if e % ep != 0:
        raise ValueError(f"num experts {e} not divisible by ep {ep}")
    return all_to_all_expert_parallel(x, 0, 1)


def exit_expert_parallel_region(x: jax.Array) -> jax.Array:
    """Inverse of :func:`enter_expert_parallel_region`
    (reference mappings.py:452)."""
    return all_to_all_expert_parallel(x, 1, 0)
