"""Flash attention: memory-efficient causal attention.

TPU-native replacement for the reference's NKI flash-attention binding
(``kernels/flash_attn.py``: ``nki_flash_attn_func`` :151 wrapping the NKI
``flash_fwd``/``flash_attn_bwd`` device kernels :20, seq-multiple-of-2048
constraint :178). Two implementations behind one API:

- ``flash_attention_reference``: blockwise online-softmax in pure jax
  (lax.scan over KV blocks). Never materializes the (S, S) score matrix, so
  long-context memory is O(S·block); works on any backend; its backward is
  JAX autodiff through the scan (recomputes per-block, flash-style).
- ``pallas_flash_attention``: the hand-written TPU kernel (fwd + dq + dkv
  with custom VJP); :func:`flash_attention` dispatches to it unless the
  kernel mode is ``"reference"``.

GQA is handled *inside* the kernel path by folding query-head groups into the
batch rather than repeating K/V (the reference replicates KV heads instead,
qkv_linear.py:454).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.kernels.mode import prefer_pallas

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 512


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
) -> jax.Array:
    """Causal (or full) attention over (B, S, N, D) q and (B, S, Nkv, D) k/v
    with Nkv | N. Returns (B, S, N, D). ``segment_ids`` (B, S) int32 masks
    attention across document boundaries (the segment-aware mode the NKI
    kernel lacks — long-context packing support).

    Dispatch follows the kernel mode (:mod:`.mode`): the Pallas kernel
    (incl. segment-ids masking in-kernel; custom fwd+bwd kernels) unless
    the caller asked for the ``"reference"`` mode, which takes the
    pure-jax blockwise implementation.

    On a multi-device mesh the kernel runs per device inside a manual
    (``shard_map``) region — a Mosaic call is opaque to the SPMD
    partitioner, which refuses it outright ("Mosaic kernels cannot be
    automatically partitioned") — with the batch split over the data axes
    and the heads over tp wherever they divide, and whole otherwise.
    Attention mixes neither batch rows nor heads, so the region holds no
    collective."""
    if not prefer_pallas():
        return flash_attention_reference(
            q, k, v, causal=causal, segment_ids=segment_ids, block_kv=block_kv
        )
    from neuronx_distributed_llama3_2_tpu.kernels.pallas_flash_attention import (
        pallas_flash_attention,
    )
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

    def kernel(q, k, v, *seg):
        return pallas_flash_attention(
            q, k, v, causal=causal, segment_ids=seg[0] if seg else None,
            block_q=block_q, block_kv=block_kv,
        )

    seg = () if segment_ids is None else (segment_ids,)
    if (
        not parallel_state.model_parallel_is_initialized()
        or parallel_state.get_parallel_state().mesh.size == 1
    ):
        return kernel(q, k, v, *seg)

    mesh, axes, spec = manual_attention_region(
        parallel_state.get_parallel_state().mesh, q.shape, k.shape[2]
    )
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec,) * 3 + (P(spec[0], None),) * len(seg),
        out_specs=spec,
        axis_names=axes,
        check_vma=False,
    )(q, k, v, *seg)


def manual_attention_region(mesh, q_shape, num_kv_heads, seq_axis=None):
    """``(mesh, axis_names, spec)`` for running an attention kernel per
    device in a ``shard_map`` that leaves NO axis of ``mesh`` to the
    partitioner — what a Mosaic call needs, since the partitioner will not
    touch it. ``spec`` lays (B, S, heads, D) operands out with the batch
    over the data axes and the heads over tp wherever they divide (whole
    otherwise) and the sequence over ``seq_axis`` (the ring's cp axis; None
    for plain flash).

    Inside a region that is already manual over some axes — the pp
    pipeline executors — the returned mesh is the ambient abstract mesh
    and ``axis_names`` holds only the axes still auto: operands there
    differ from stage to stage, and a nested region that listed pp again
    without naming it in a spec would take them for replicated over pp and
    sum their cotangents across stages in the backward pass."""
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        DP_AXIS,
        EP_AXIS,
        TP_AXIS,
    )

    tp = mesh.shape[TP_AXIS]
    batch = (
        (DP_AXIS, EP_AXIS)
        if q_shape[0] % (mesh.shape[DP_AXIS] * mesh.shape[EP_AXIS]) == 0
        else None
    )
    heads = TP_AXIS if q_shape[2] % tp == 0 and num_kv_heads % tp == 0 else None
    axes = set(mesh.axis_names)
    ambient, manual = ambient_manual_axes()
    if manual:
        mesh, axes = ambient, axes - manual
    return mesh, axes, P(batch, seq_axis, heads, None)


def ambient_manual_axes():
    """``(abstract mesh, names of its axes that are already manual)`` at this
    point of the trace — non-empty inside a ``shard_map`` region."""
    ambient = jax.sharding.get_abstract_mesh()
    return ambient, {
        n for n, t in zip(ambient.axis_names, ambient.axis_types)
        if t == jax.sharding.AxisType.Manual
    }


NEG = jnp.float32(-1e30)


def blockwise_attention_stats(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    q_segment_ids: Optional[jax.Array] = None,
    q_off=0,
    kv_off=0,
    kv_len: Optional[jax.Array] = None,
    block_kv: int = DEFAULT_BLOCK_KV,
):
    """Online-softmax block loop returning the combinable triple
    ``(acc, m, l)`` with acc (B, Sq, Nkv, G, D), m/l (B, Sq, Nkv, G) fp32.

    The single source of truth for blockwise attention numerics — both
    :func:`flash_attention_reference` (normalize of these stats) and the
    ring-attention executor (merging stats across visiting chunks,
    kernels/ring_attention.py) build on it. ``q_off``/``kv_off`` are the
    global positions of q[.,0] / k[.,0] (the ring's chunks live at
    different global offsets); ``kv_len`` optionally masks positions >= it.
    Each block step is ``jax.checkpoint``-ed so the backward recomputes the
    (Sq, block) score tile instead of storing every block's softmax —
    keeping training memory at O(Sq·block_kv), not O(Sq·Skv).
    """
    b, sq, n, d = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    group = n // nkv
    scale = d ** -0.5

    # fold GQA groups into the kv-head dim: (B, S, Nkv, G, D)
    qg = q.reshape(b, sq, nkv, group, d).astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    block_kv = min(block_kv, skv)
    nblk = -(-skv // block_kv)  # ceil
    pad = nblk * block_kv - skv
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kf.reshape(b, nblk, block_kv, nkv, d)
    vb = vf.reshape(b, nblk, block_kv, nkv, d)

    q_pos = q_off + lax.iota(jnp.int32, sq)  # (Sq,) global
    kv_pos_all = kv_off + lax.iota(jnp.int32, nblk * block_kv)
    valid_all = lax.iota(jnp.int32, nblk * block_kv) < skv
    kv_seg_all = None
    if segment_ids is not None:
        kv_seg_all = jnp.pad(
            segment_ids, ((0, 0), (0, pad)), constant_values=-1
        ).reshape(b, nblk, block_kv)
        if q_segment_ids is None:
            q_segment_ids = segment_ids

    def body(carry, blk):
        acc, m, l = carry  # (B,Sq,Nkv,G,D), (B,Sq,Nkv,G), (B,Sq,Nkv,G)
        kblk, vblk, kv_pos, valid, kv_seg = blk
        # scores: (B, Sq, Nkv, G, block)
        s = jnp.einsum("bsngd,btnd->bsngt", qg, kblk)
        mask = valid[None, :]  # padded tail positions
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if kv_len is not None:
            mask = mask & (kv_pos < kv_len)[None, :]
        mask = mask[None, :, None, None, :]
        if kv_seg is not None:
            seg_ok = kv_seg[:, None, :] == q_segment_ids[:, :, None]
            mask = mask & seg_ok[:, :, None, None, :]
        s = jnp.where(mask, s, NEG)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # renormalize the running accumulator
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bsngt,btnd->bsngd", p, vblk)
        return (acc, m_new, l_new), None

    init = (
        jnp.zeros((b, sq, nkv, group, d), jnp.float32),
        jnp.full((b, sq, nkv, group), NEG),
        jnp.zeros((b, sq, nkv, group), jnp.float32),
    )
    blks = (
        jnp.moveaxis(kb, 1, 0),
        jnp.moveaxis(vb, 1, 0),
        kv_pos_all.reshape(nblk, block_kv),
        valid_all.reshape(nblk, block_kv),
        jnp.moveaxis(kv_seg_all, 1, 0)
        if kv_seg_all is not None
        else jnp.zeros((nblk, 1)),
    )

    def step(carry, blk):
        kblk, vblk, kv_pos, valid, kv_seg = blk
        return body(
            carry,
            (kblk, vblk, kv_pos, valid, kv_seg if kv_seg_all is not None else None),
        )

    (acc, m, l), _ = lax.scan(jax.checkpoint(step), init, blks)
    return acc, m, l


def flash_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    block_kv: int = DEFAULT_BLOCK_KV,
) -> jax.Array:
    b, sq, n, d = q.shape
    acc, m, l = blockwise_attention_stats(
        q, k, v, causal=causal, segment_ids=segment_ids, block_kv=block_kv
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, n, d).astype(q.dtype)
