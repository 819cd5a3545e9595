"""Pallas TPU flash-attention kernels (forward + backward).

TPU-native replacement for the reference's NKI device kernels
(``kernels/flash_attn.py``: ``flash_fwd`` / ``flash_attn_bwd`` :20, bound via
``nki_flash_attn_func`` :151). FlashAttention-2 structure:

- forward: grid (batch·q_heads, q_blocks, kv_blocks), kv innermost so the
  running max/denominator/accumulator live in VMEM scratch across kv
  iterations; causal blocks above the diagonal are predicated off entirely
  (the reference kernel does the same block-skip). Emits the logsumexp so
  the backward never re-materializes the softmax normalizer.
- backward: two kernels — dq (grid over q blocks, accumulating across kv)
  and dk/dv (grid over kv blocks, accumulating across q), recomputing P from
  (q, k, lse) flash-style.
- GQA: q head h reads kv head h // group through the BlockSpec index map —
  no KV replication in memory (the reference replicates KV heads
  ``kv_size_multiplier`` times instead, qkv_linear.py:454).

Unlike the NKI kernel's seq % 2048 == 0 constraint (flash_attn.py:178), any
seq length is accepted: the wrapper pads to the block size and masks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_llama3_2_tpu.kernels.mode import pallas_interpret

NEG_INF = float("-inf")
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 256


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(
    *refs,
    causal: bool, sm_scale: float, block_q: int, block_kv: int,
    kv_len: int, segmented: bool,
):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        sq_ref = skv_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    kv_start = ki * block_kv
    # causal: skip blocks fully above the diagonal
    run = True if not causal else kv_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        # keep matmul operands in the input dtype (bf16): the MXU runs bf16
        # at 4x its fp32 rate and accumulates in fp32 natively
        # (preferred_element_type) — casting operands to fp32 here would
        # quarter the kernel's flops ceiling. sm_scale is applied to the
        # fp32 product instead of pre-scaling q, which is exact.
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (bq, bk) fp32

        kv_pos = kv_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_pos < kv_len
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kv_pos <= q_pos)
        if segmented:
            # packed-document masking: q attends only within its own segment
            # (the jnp path's segment_ids semantics, flash_attention.py:47)
            mask = mask & (sq_ref[0, :, 0][:, None] == skv_ref[0, :, 0][None, :])
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]  # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # rows with no valid key yet keep m = -inf; exp(-inf - -inf) guarded
        alpha = jnp.where(
            m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new)
        )
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0]  # (bk, D)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha[:, None] + pv
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)
        m = m_scr[:, 0]
        lse = jnp.where(m == NEG_INF, NEG_INF, m + jnp.log(safe_l))
        lse_ref[0, 0, :, 0] = lse


def _pad_to(x, size, axis):
    pad = -x.shape[axis] % size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _seg_operands(segment_ids, sq, skv, block_q, block_kv):
    """(seg_q, seg_kv) padded to block multiples as (B, S_p, 1) int32; pad
    ids are -1 so padded keys can never match a real segment."""
    seg = segment_ids.astype(jnp.int32)
    seg_q = jnp.pad(seg, ((0, 0), (0, -sq % block_q)), constant_values=-1)
    seg_kv = jnp.pad(seg, ((0, 0), (0, -skv % block_kv)), constant_values=-1)
    return seg_q[..., None], seg_kv[..., None]


def _flash_fwd(q, k, v, segment_ids, causal, sm_scale, block_q, block_kv):
    """q (B, N, Sq, D), k/v (B, Nkv, Skv, D) → o (B, N, Sq, D), lse (B, N, Sq)."""
    b, n, sq, d = q.shape
    nkv, skv = k.shape[1], k.shape[2]
    group = n // nkv
    segmented = segment_ids is not None

    qp = _pad_to(q, block_q, 2)
    kp = _pad_to(k, block_kv, 2)
    vp = _pad_to(v, block_kv, 2)
    sq_p, skv_p = qp.shape[2], kp.shape[2]
    nq, nk = sq_p // block_q, skv_p // block_kv

    grid = (b * n, nq, nk)

    def q_idx(h, qi, ki):
        return (h // n, h % n, qi, 0)

    def kv_idx(h, qi, ki):
        return (h // n, (h % n) // group, ki, 0)

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_kv=block_kv,
        kv_len=skv,
        segmented=segmented,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_kv, d), kv_idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_kv, d), kv_idx, memory_space=pltpu.VMEM),
    ]
    operands = [qp, kp, vp]
    if segmented:
        seg_q, seg_kv = _seg_operands(segment_ids, sq, skv, block_q, block_kv)
        in_specs += [
            pl.BlockSpec(
                (1, block_q, 1), lambda h, qi, ki: (h // n, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_kv, 1), lambda h, qi, ki: (h // n, ki, 0),
                memory_space=pltpu.VMEM,
            ),
        ]
        operands += [seg_q, seg_kv]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_idx, memory_space=pltpu.VMEM),
            # trailing singleton keeps the block's last-two-dims tiling legal
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda h, qi, ki: (h // n, h % n, qi, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, n, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # (batch·head, q-block) iterations are independent; only the kv dim
        # carries the running-softmax scratch. Telling Mosaic unlocks
        # cross-iteration pipelining it must otherwise assume away.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
        name="flash_fwd",
    )(*operands)
    return o[:, :, :sq, :], lse[:, :, :sq, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(
    *refs, causal, sm_scale, block_q, block_kv, kv_len, segmented,
):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, skv_ref, dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        sq_ref = skv_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start, kv_start = qi * block_q, ki * block_kv
    run = True if not causal else kv_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        # bf16 operands / fp32 accumulation on every dot (see _fwd_kernel)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        kv_pos = kv_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_pos < kv_len
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kv_pos <= q_pos)
        if segmented:
            mask = mask & (sq_ref[0, :, 0][:, None] == skv_ref[0, :, 0][None, :])
        lse = lse_ref[0, 0, :, 0]  # (bq,)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        do = do_ref[0, 0]  # (bq, D)
        v = v_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        delta = delta_ref[0, 0, :, 0]  # (bq,)
        ds = (p * (dp - delta[:, None])).astype(k.dtype)  # (bq, bk)
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, causal, sm_scale, block_q, block_kv, kv_len, q_len, segmented,
):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, skv_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        sq_ref = skv_ref = None
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start, kv_start = qi * block_q, ki * block_kv
    run = True if not causal else q_start + block_q - 1 >= kv_start

    @pl.when(run)
    def _compute():
        # bf16 operands / fp32 accumulation on every dot (see _fwd_kernel)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (bq, bk)
        kv_pos = kv_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = (kv_pos < kv_len) & (q_pos < q_len)
        if causal:
            mask = mask & (kv_pos <= q_pos)
        if segmented:
            mask = mask & (sq_ref[0, :, 0][:, None] == skv_ref[0, :, 0][None, :])
        lse = lse_ref[0, 0, :, 0]
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # (bq, bk)
        do = do_ref[0, 0]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, D)
        v = v_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0, 0, :, 0]
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, D); q unscaled — the sm_scale prefactor covers it

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, segment_ids, causal, sm_scale, block_q, block_kv):
    b, n, sq, d = q.shape
    nkv, skv = k.shape[1], k.shape[2]
    group = n // nkv
    segmented = segment_ids is not None

    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )  # (B, N, Sq)

    qp = _pad_to(q, block_q, 2)
    dop = _pad_to(do, block_q, 2)
    lsep = _pad_to(lse, block_q, 2)[..., None]    # (B, N, Sq_p, 1)
    deltap = _pad_to(delta, block_q, 2)[..., None]
    kp = _pad_to(k, block_kv, 2)
    vp = _pad_to(v, block_kv, 2)
    sq_p, skv_p = qp.shape[2], kp.shape[2]
    nq_blk, nk_blk = sq_p // block_q, skv_p // block_kv

    def q_idx(h, i, j):
        return (h // n, h % n, i, 0)

    def q_vec_idx(h, i, j):
        return (h // n, h % n, i, 0)

    def kv_idx(h, i, j):
        return (h // n, (h % n) // group, j, 0)

    seg_operands = []
    if segmented:
        seg_q, seg_kv = _seg_operands(segment_ids, sq, skv, block_q, block_kv)
        seg_operands = [seg_q, seg_kv]

    def seg_specs(q_block_dim: int):
        # (seg_q, seg_kv) specs; q blocks iterate over grid dim q_block_dim
        qdim = (lambda h, i, j: (h // n, i, 0)) if q_block_dim == 1 else (
            lambda h, i, j: (h // n, j, 0)
        )
        kdim = (lambda h, i, j: (h // n, j, 0)) if q_block_dim == 1 else (
            lambda h, i, j: (h // n, i, 0)
        )
        return [
            pl.BlockSpec((1, block_q, 1), qdim, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_kv, 1), kdim, memory_space=pltpu.VMEM),
        ]

    # dq: grid (BN, nq, nk)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_kv=block_kv, kv_len=skv,
            segmented=segmented,
        ),
        grid=(b * n, nq_blk, nk_blk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_kv, d), kv_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_kv, d), kv_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, d), q_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1), q_vec_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1), q_vec_idx, memory_space=pltpu.VMEM),
        ] + (seg_specs(q_block_dim=1) if segmented else []),
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), q_idx, memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap, *seg_operands)

    # dk/dv: grid (BN, nk, nq) — per q-head, then group-summed for GQA
    def kv_idx2(h, j, i):
        return (h // n, (h % n) // group, j, 0)

    def q_idx2(h, j, i):
        return (h // n, h % n, i, 0)

    def q_vec_idx2(h, j, i):
        return (h // n, h % n, i, 0)

    def dkv_idx(h, j, i):
        return (h // n, h % n, j, 0)

    dk_ph, dv_ph = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_kv=block_kv, kv_len=skv, q_len=sq,
            segmented=segmented,
        ),
        grid=(b * n, nk_blk, nq_blk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_idx2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_kv, d), kv_idx2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_kv, d), kv_idx2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, d), q_idx2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1), q_vec_idx2, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1), q_vec_idx2, memory_space=pltpu.VMEM),
        ] + (seg_specs(q_block_dim=2) if segmented else []),
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d), dkv_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_kv, d), dkv_idx, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, skv_p, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, skv_p, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap, *seg_operands)

    # GQA: sum q-head contributions within each kv group
    dk = dk_ph[:, :, :skv, :].reshape(b, nkv, group, skv, d).sum(axis=2)
    dv = dv_ph[:, :, :skv, :].reshape(b, nkv, group, skv, d).sum(axis=2)
    return dq[:, :, :sq, :], dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_bnsd(q, k, v, segment_ids, causal, sm_scale, block_q, block_kv):
    o, _ = _flash_fwd(q, k, v, segment_ids, causal, sm_scale, block_q, block_kv)
    return o


def _fwd_rule(q, k, v, segment_ids, causal, sm_scale, block_q, block_kv):
    o, lse = _named(*_flash_fwd(q, k, v, segment_ids, causal, sm_scale, block_q, block_kv))
    return o, (q, k, v, segment_ids, o, lse)


def _bwd_rule(causal, sm_scale, block_q, block_kv, res, do):
    q, k, v, segment_ids, o, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, o, lse, do, segment_ids, causal, sm_scale, block_q, block_kv
    )
    return dq, dk, dv, None


_flash_attention_bnsd.defvjp(_fwd_rule, _bwd_rule)


def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: "jax.Array | None" = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
) -> jax.Array:
    """(B, S, N, D) layout entry point matching
    :func:`..kernels.flash_attention.flash_attention`. ``segment_ids``
    (B, S) int: packed-document masking in-kernel (the NKI reference kernel
    has no segment support, kernels/flash_attn.py — this beats it)."""
    sm_scale = q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash_attention_bnsd(
        qt, kt, vt, segment_ids, causal, sm_scale, block_q, block_kv
    )
    return o.transpose(0, 2, 1, 3)


def _named(o, lse):
    """The forward kernel's two outputs under the names a ``jax.checkpoint``
    policy can keep them by: they are no dot's output, and a policy that does
    not list them runs ``flash_fwd`` again in the backward pass to make them.
    (Defined last so that no line a forward-only program traces moves.)"""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(o, "flash_out"), checkpoint_name(lse, "flash_lse")
