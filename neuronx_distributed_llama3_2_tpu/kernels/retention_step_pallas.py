"""One pass over the paged pool's retention states a decode step.

Power retention's recurrent form (:func:`..models.brumby.retention_step`)
reads a lane's state for the output and reads and writes it for the update:
as plain XLA that is three passes over ``(kv heads, φ, head)`` float32 a lane
a layer, and a decode step is little else (``PERF.md`` section 5, longgen).
:func:`retention_step_paged` is the same arithmetic with **one** Pallas call a
layer over all lanes: a grid step holds one kv head's ``S`` of one lane in
VMEM, accumulates ``φ(q)ᵀ S`` from it and writes ``g·S + φ(k) vᵀ`` back to the
same place.

- The pool goes in as one run of ``L · num_blocks`` states and a lane's state
  is found by scalar prefetch of ``index + layer · num_blocks`` (the paged
  attention kernel's convention), with ``input_output_aliases`` pool → pool:
  a donated pool is updated in place, and a block no lane names is not
  touched. Idle lanes name the null block and rewrite it in turn.
- **φ is built inside the kernel**, never materialised: row ``(i, p, c)`` of
  the tiled symmetric square is ``a[16i+p] · w · a[c]`` (``w`` = 1 on the
  diagonal block, √2 right of it), so the 16 segments ``p`` of block row ``i``
  share one ``(d − 16i, dv)`` operand and differ by a scalar. The output's
  numerator is accumulated as ``A[c, :] += q[16i+p] · w · S[(i, p, c), :]``
  and contracted with ``q[c]`` once at the end; the update adds
  ``k[16i+p] · w · (k[c] v[:])``. Every operand is a sublane broadcast of a
  row of a ``(d, d)`` table: no lane shuffle in the loop, float32 throughout,
  13 vector operations a vreg of state against ≈ 9 cycles of HBM time for it.
  The products are float32 of the inputs' values (``retention_step`` rounds φ
  to the inputs' dtype first): with float32 inputs the two agree to round-off.
- The normaliser ``z`` is a 128th of the state's bytes and lies flat in φ's
  order, which the table trick cannot address: it is gathered, updated and
  scattered a block a lane by plain XLA beside the call, under the same
  scope.

A multi-device mesh cannot partition a bare Mosaic call; the caller
(:class:`..inference.model.RetentionDecode`) keeps ``retention_step`` there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_llama3_2_tpu.kernels.mode import pallas_interpret


def _state_pass_kernel(idx_ref, rows_ref, s_ref, num_ref, s_out_ref, tab_ref, acc_ref,
                       *, d, groups, phi_block):
    """One kv head of one lane. rows_ref (R8, d): q (scaled) of the head's
    ``groups`` query heads, k, v, and g in every lane; s_ref / s_out_ref
    (D, d) the state; num_ref (R8, d) ← φ(q)ᵀ S_old a query head."""
    del idx_ref  # used by the index maps alone
    f32 = jnp.float32
    rows = rows_ref[...]
    eye = (lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == lax.broadcasted_iota(jnp.int32, (d, d), 1)).astype(f32)

    def column(row):
        """(1, d) -> (d, d) with [c, :] = row[c]."""
        return jnp.broadcast_to(jnp.sum(eye * row, axis=1, keepdims=True), (d, d))

    # tab[n][r, :] = a_n[r] in every lane: row r is segment (i, p)'s scalar
    # (r = 16i + p), and the whole table the final contraction's q[c]
    for n in range(groups + 1):
        tab_ref[n] = column(rows[n:n + 1])
    kv = tab_ref[groups] * rows[groups + 1:groups + 2]          # [c, v] = k[c] v[v]
    decay = rows[groups + 2:groups + 3]
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    # a block row's 16 segments in one loop, a whole segment a trip: the pass
    # is bound by its DMA (a copy on the same specs runs 2.82 ms a layer of 24
    # lanes, the kernel 2.99; 32 and 64 rows a trip read the same to 0.3 %:
    # chip runs, PR 41), and fewer, longer loops are cheaper to trace and lower
    base = 0
    for i in range(d // phi_block):
        lo = i * phi_block
        seg = d - lo                                            # rows a segment
        diag = lax.broadcasted_iota(jnp.int32, (seg, d), 0) < phi_block
        w = jnp.where(diag, 1.0, math.sqrt(2.0)).astype(f32)
        kvw = kv[lo:] * w

        def segment(p, acc, base=base, seg=seg, lo=lo, kvw=kvw):
            at = pl.ds(pl.multiple_of(base + p * seg, 8), seg)
            s = s_ref[at, :].astype(f32)
            r = pl.ds(lo + p, 1)
            s_out_ref[at, :] = (decay * s + tab_ref[groups, r, :] * kvw).astype(s_out_ref.dtype)
            return tuple(a + tab_ref[n, r, :] * s for n, a in enumerate(acc))

        acc = lax.fori_loop(
            0, phi_block, segment, tuple(jnp.zeros((seg, d), f32) for _ in range(groups)))
        for n in range(groups):
            acc_ref[n, lo:, :] += acc[n] * w
        base += phi_block * seg

    num_ref[...] = jnp.zeros(num_ref.shape, f32)
    for n in range(groups):
        num_ref[n:n + 1, :] = jnp.sum(tab_ref[n] * acc_ref[n], axis=0, keepdims=True)


def retention_state_pass(flat_index, rows, s_flat, *, groups, phi_block):
    """flat_index (b,) int32 into s_flat's leading axis; rows (b, K, R8, d)
    float32; s_flat (blocks, K, D, d). Returns (num (b, K, R8, d) float32 —
    row n < groups is φ(q_n)ᵀ S_old — and s_flat with the named blocks
    updated in place)."""
    b, kh, r8, d = rows.shape
    feat = s_flat.shape[2]
    if s_flat.shape[1:] != (kh, feat, d):
        raise ValueError(f"states {s_flat.shape} do not fit rows {rows.shape}")

    def lane_rows(i, h, idx):
        return (i, h, 0, 0)

    def lane_state(i, h, idx):
        return (idx[i], h, 0, 0)

    state_bytes = feat * d * s_flat.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh),
        in_specs=[
            pl.BlockSpec((None, None, r8, d), lane_rows),
            pl.BlockSpec((None, None, feat, d), lane_state),
        ],
        out_specs=[
            pl.BlockSpec((None, None, r8, d), lane_rows),
            pl.BlockSpec((None, None, feat, d), lane_state),
        ],
        scratch_shapes=[
            pltpu.VMEM((groups + 1, d, d), jnp.float32),
            pltpu.VMEM((groups, d, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_state_pass_kernel, d=d, groups=groups, phi_block=phi_block),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(rows.shape, jnp.float32),
            jax.ShapeDtypeStruct(s_flat.shape, s_flat.dtype),
        ],
        # operand 0 is the prefetched index: the pool is operand 2, output 1
        input_output_aliases={2: 1},
        # a state in and one out, each double-buffered, and room for the rest;
        # sequential, so idle lanes rewriting the null block do not race
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * state_bytes + (16 << 20),
        ),
        interpret=pallas_interpret(),
        name="retention_state_pass",
    )(flat_index, rows, s_flat)


def retention_step_paged(s_pool, z_pool, index, layer, q, k, v, log_g, eps):
    """The recurrent form for every lane at once, over the pool in place.
    s_pool (L, blocks, K, D, dv), z_pool (L, blocks, K, D); index (b,) the
    block of each lane, ``layer`` a scalar; q (b, K, G, d) unscaled, k (b, K,
    d), v (b, K, dv), log_g (b, K) float32. Returns (y (b, K, G, dv) in q's
    dtype, s_pool, z_pool) — lane by lane what ``retention_step`` returns."""
    from neuronx_distributed_llama3_2_tpu.models.brumby import PHI_BLOCK, power_features

    f32 = jnp.float32
    b, kh, groups, d = q.shape
    if v.shape[-1] != d:
        raise ValueError("the state kernel packs q, k and v rows of one width")
    nl, nb = s_pool.shape[:2]
    qs = (q * jnp.asarray(d ** -0.5, q.dtype)).astype(f32)
    kf, vf = k.astype(f32), v.astype(f32)
    with jax.named_scope("expand"):
        phi_q, phi_k = power_features(qs), power_features(kf)     # for z alone
    # the pass and everything around it under the form's own scope, as
    # ``retention_step`` has it: one reader sees the same work on both paths
    with jax.named_scope("step"):
        flat = (index + layer * nb).astype(jnp.int32)
        g = jnp.exp(log_g)
        own = jnp.square(jnp.sum(qs * kf[:, :, None, :], axis=-1))       # (b, K, G)
        rows = jnp.concatenate(
            [qs, kf[:, :, None], vf[:, :, None],
             jnp.broadcast_to(g[..., None, None], (b, kh, 1, d))], axis=2)
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, -rows.shape[2] % 8), (0, 0)))
        num, s_flat = retention_state_pass(
            flat, rows, s_pool.reshape((nl * nb,) + s_pool.shape[2:]),
            groups=groups, phi_block=PHI_BLOCK)
        num = num[:, :, :groups]
        # the normaliser: whole blocks gathered and scattered, elementwise float32
        z_flat = z_pool.reshape((nl * nb,) + z_pool.shape[2:])
        z_old = z_flat[flat].astype(f32)                                 # (b, K, D)
        den = jnp.sum(phi_q * z_old[:, :, None, :], axis=-1)
        z_flat = z_flat.at[flat].set((g[..., None] * z_old + phi_k).astype(z_pool.dtype))
        y = (g[..., None, None] * num + own[..., None] * vf[:, :, None, :]) \
            / ((g[..., None] * den + own)[..., None] + eps)
    return y.astype(q.dtype), s_flat.reshape(s_pool.shape), z_flat.reshape(z_pool.shape)
