"""Pallas TPU paged-attention decode kernel (flash-decoding over block tables).

The serving decode path reads the KV pool through a per-request block table.
The jnp fallback (``LlamaDecode._attend_paged``) materializes the gather —
``kflat[rd_phys]`` builds a dense ``(b, kv_limit, NKV, D)`` K/V copy in HBM
every decode step before a masked-softmax einsum, doubling the cache read
traffic of the step that is already cache-bandwidth-bound. This kernel
removes the copy: the block table rides in as a *scalar-prefetch* operand,
and the K/V BlockSpec index maps dereference it, so Mosaic DMAs each pool
block straight from its pooled location into VMEM (vLLM PagedAttention's
gather-free read, done TPU-style through ``PrefetchScalarGridSpec``).

Structure (flash-decoding, Dao et al. 2023 — split-K for a single query row):

- the pool ``(num_blocks, bs, NKV, D)`` is viewed as ``(num_blocks, bs,
  NKV·D)`` — a free reshape — so one block is a lane-dense ``(bs, NKV·D)``
  tile whose last two dims equal the array's: the only K/V block shape
  Mosaic lowers for any (NKV, D) (a per-head ``(bs, D)`` block squeezes the
  second-to-last pool axis, which the TPU lowering refuses outright).
- grid ``(b, num_splits, blocks_per_split)``: one program instance per
  lane; the kv-length dimension is partitioned into ``num_splits``
  independent chunks so long contexts expose parallelism beyond the (tiny)
  decode batch.
- all kv heads of a block are scored by ONE dot: the query tile is laid
  out block-diagonally, row ``h·t·G + ti·G + gi`` holding query token
  ``ti`` / grouped head ``gi`` of kv head ``h`` in lanes ``[h·D, (h+1)·D)``
  and zeros elsewhere, so ``q_bd · k_blkᵀ`` contracts each row against its
  own head only. The p·v dot yields every head's lanes for every row; the
  caller keeps the diagonal. The (NKV−1)/NKV wasted MXU work is far below
  the per-grid-step cost at decode sizes, and the body stays whole-tile
  2-D matmuls — no per-head lane slicing, no 4-row tiles.
- within a split, the per-block online softmax carries the running max ``m``,
  denominator ``l`` and unnormalized accumulator in VMEM scratch — exactly
  the ``_fwd_kernel`` recurrence of :mod:`.pallas_flash_attention`.
- each split emits ``(acc, m, l)``; the final combine outside the kernel
  rescales by ``exp(m_s - m*)`` (log-sum-exp merge) and normalizes once.
- masking is per-lane by position (``row <= positions[lane]``), which also
  kills null-block garbage rows: the engine guarantees every row past a
  request's frontier is masked, whatever stale block the table points at.
- multi-token queries (speculative verify / short suffix-prefill blocks,
  static ``t <= LlamaConfig.paged_kernel_max_t``) fold the t fresh tokens
  into the query-tile rows and the mask becomes block-causal per query row
  (``row <= positions[lane] + ti``) — so each KV block is still DMA'd
  exactly once per (lane, split) and serves all t queries.
- packed draft trees (tree speculation) generalize that mask: an optional
  per-lane ``(t,)`` int32 ancestor-bitmask operand (``tree_bits``) makes
  each query node attend the committed prefix plus exactly its ancestor
  nodes within the block, so multiple candidate *branches* verify in one
  forward while still sharing one KV DMA per block. A linear chain's
  bitmasks reproduce the block-causal mask bit for bit.

:func:`paged_decode_walk` stands beside it as the body the cells run (the one
fresh row a lane of an unquantized pool; ``LlamaDecode._attend_paged`` and
``LagunaDecode._attend`` call it where ``LlamaDecode._walks`` says so): no
static grid over the rung — a grid step is a lane, and a loop whose trip count
is the lane's own copies its **live** blocks — under a ``window``, the blocks
of its ring that hold the window — a group at a time from the pool
in HBM (``memory_space=ANY``, ``make_async_copy``, two VMEM buffers) and folds
each group into the same online softmax. It takes the pool as ``(blocks · bs ·
NKV, D)``, which on the chip is the bytes as they lie where D is 128
(:func:`walk_fits`); the ``(blocks, bs, NKV·D)`` view above is a re-tiling
there. Which read a paged program of a ``(k, v)`` family holds, the first row
that fits deciding (``docs/serving.md`` "Gather-free decode" has it in full):

==========================================  =================  ====================
the fresh block; pool; devices              flag unset         ``use_paged_kernel``
==========================================  =================  ====================
one row a lane, no tree; unquantized rows   the walk           the walk
of 128; one device; not ``"reference"``
up to ``paged_kernel_max_t`` rows, trees    gather             ``paged_flash_decode``
of <= 32 nodes; any pool; one device
the same; a pure-tp mesh, heads divisible   gather             ``..._decode_tp``
anything else (long ``psfx`` blocks, other  gather             gather
meshes)
==========================================  =================  ====================

``_decode_kernel``'s variants (several rows, trees, int8 / fp8 pools, a tp
mesh) move onto the walk as they are needed (``ROADMAP.md`` D6).

:func:`latent_decode_walk` is the same walk over a latent (MLA) pool
(``SarvamDecode._latent_attention`` calls it, for sarvam and Xing4.0): a row
``[c ‖ k_r]`` is key and value at once, so a group lands in **one** buffer and
is scored whole by the absorbed query and summed over its first ``r`` columns.

The kernel mode (:mod:`.mode`) decides whether the body runs through Mosaic
or the Pallas interpreter; the real-chip numerics gate lives in
scripts/tpu_kernel_gate.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_llama3_2_tpu.kernels.mode import pallas_interpret
from neuronx_distributed_llama3_2_tpu.kernels.pallas_flash_attention import (
    NEG_INF,
)

# kv-length split count: enough to keep a megacore busy past small decode
# batches without shrinking per-split work below a few blocks
DEFAULT_NUM_SPLITS = 4


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _head_columns(x, nkv: int, width: int):
    """Widen per-head columns ``x (rows, NKV)`` to ``(rows, width)`` lanes,
    lane ``c`` taking head ``c // (width // NKV)``'s column — NKV selects
    over a lane-broadcast column, all VPU work on whole tiles."""
    per = width // nkv
    head = lax.broadcasted_iota(jnp.int32, (x.shape[0], width), 1) // per
    out = jnp.zeros((x.shape[0], width), x.dtype)
    for h in range(nkv):
        out = jnp.where(head == h, x[:, h:h + 1], out)
    return out


def _decode_kernel(
    tbl_ref,   # scalar prefetch: (b, W) int32 block table (SMEM)
    pos_ref,   # scalar prefetch: (b,) int32 first-fresh-query positions (SMEM)
    *refs,     # [live_ref (b,) int32 per-lane live-row counts (SMEM, only
    #            when has_live),]
    #            [tree_ref (b, t) int32 per-node ancestor bitmasks (SMEM,
    #            only when has_tree),] then
    #            q_ref (R, NKV·D) — this lane's block-diagonal query tile,
    #            R = NKV·t·G rows,
    #            k_ref / v_ref (bs, NKV·D) — one pool block via the table,
    #            [ks_ref, vs_ref (bs, NKV) f32 — quantized scale tiles,] then
    #            o_ref (R, NKV·D) f32 per-split UNNORMALIZED accumulator,
    #            m_ref / l_ref (R, 1) f32 per-split running max / denom,
    #            and the m/l/acc VMEM scratch
    bs: int, bps: int, nblk: int, t: int, g: int, nkv: int, sm_scale: float,
    quantized: bool = False, quant_mxu: bool = False, has_live: bool = False,
    has_tree: bool = False,
):
    if has_live:
        # mixed-width tile (fused_step): lane i's rows >= live_ref[i] are
        # packing padding — the per-lane KV walk stops at its live
        # frontier instead of the static pos + t - 1
        live_ref = refs[0]
        refs = refs[1:]
    else:
        live_ref = None
    if has_tree:
        # packed draft tree (tree speculation): bit m of tree_ref[i, q] is
        # set iff node m is an ancestor-or-self of node q in lane i's tree
        tree_ref = refs[0]
        refs = refs[1:]
    else:
        tree_ref = None
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    if quantized:
        # int8/fp8 pool: the block DMA moved low-bit payload + the block's
        # (bs, NKV) scale tile; dequant here in VMEM with the same
        # f32-widen formula as quantization.kv_cache.kv_dequantize
        ks_ref, vs_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(0)          # lane
    s = pl.program_id(1)          # kv split
    j = pl.program_id(2)          # block within split
    width = k_ref.shape[-1]       # NKV·D lanes

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lb = s * bps + j              # logical block index into the sequence
    pos = pos_ref[i]
    # skip padding blocks past kv_limit and blocks entirely beyond the
    # lane's LAST fresh query (the frontier: rows pos..pos+t-1 were just
    # written; earlier queries in the tile mask the deeper rows per-row).
    # With per-lane live counts the frontier tightens to the deepest LIVE
    # query — dead rows attend whatever the live walk visits and their
    # garbage output is discarded by the caller
    frontier = t - 1 if live_ref is None else live_ref[i] - 1
    run = (lb < nblk) & (lb * bs <= pos + frontier)

    @pl.when(run)
    def _compute():
        q = q_ref[...]                             # (R, NKV·D)
        nt = (((1,), (1,)), ((), ()))              # contract the lane dims
        if ks_ref is not None and quant_mxu:
            # low-precision MXU q·k: keep the stored payload as a dot
            # operand instead of widening it first. Both absmax scales
            # factor algebraically out of the contraction —
            # sc[r, c] = q_scale[r] * k_scale[c, h(r)] * Σ q̂[r,·]·k̂[c,·] —
            # so they apply to the fp32 outputs the LSE combine consumes,
            # never per-element before the dot. The per-(row, head) k
            # scale reaches the (R, bs) score tile through the same
            # block-diagonal contraction as the payload: a 0/1 row-to-head
            # selector against the lane-widened scale tile.
            lane = lax.broadcasted_iota(jnp.int32, q.shape, 1)
            row_head = lax.broadcasted_iota(jnp.int32, q.shape, 0) // (t * g)
            sel = (lane == row_head * (width // nkv)).astype(jnp.float32)
            ks_rows = lax.dot_general(
                sel, _head_columns(ks_ref[...], nkv, width), nt,
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                                  # (R, bs)
            if k_ref.dtype == jnp.int8:
                # int8 pool: quantize the query tile per row (symmetric
                # absmax / 127, the kv_quantize formula) so the MXU runs
                # int8 × int8 accumulating in int32
                qf = q.astype(jnp.float32)
                q_scl = jnp.maximum(
                    jnp.max(jnp.abs(qf), axis=1, keepdims=True), 1e-6
                ) / 127.0                                      # (R, 1)
                q_i8 = jnp.clip(
                    jnp.round(qf / q_scl), -127.0, 127.0
                ).astype(jnp.int8)
                acc = lax.dot_general(
                    q_i8, k_ref[...], nt,
                    preferred_element_type=jnp.int32,
                )                                              # (R, bs) i32
                sc = acc.astype(jnp.float32) * q_scl * ks_rows * sm_scale
            else:
                # fp8 pool: fp8 × fp8 operands with an fp32
                # preferred_element_type — no query requantization needed,
                # the cast is the same narrowing kv_quantize applied on
                # write (through f32, the one narrowing every Pallas TPU
                # lowering accepts; the value rounds once either way);
                # only k's stored scale remains to factor out
                acc = lax.dot_general(
                    q.astype(jnp.float32).astype(k_ref.dtype), k_ref[...], nt,
                    preferred_element_type=jnp.float32,
                )                                              # (R, bs) f32
                sc = acc * ks_rows * sm_scale
        else:
            if ks_ref is not None:
                k = (
                    k_ref[...].astype(jnp.float32)
                    * _head_columns(ks_ref[...], nkv, width)
                ).astype(q.dtype)                  # (bs, NKV·D)
            else:
                k = k_ref[...].astype(q.dtype)     # (bs, NKV·D)
            sc = lax.dot_general(
                q, k, nt, preferred_element_type=jnp.float32,
            ) * sm_scale                           # (R, bs) fp32
        rows = lb * bs + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        # block-causal across the fresh tokens: tile row r holds query
        # token ti = (r mod t·G) // G, which sits at sequence row pos + ti
        ti = (lax.broadcasted_iota(jnp.int32, sc.shape, 0) % (t * g)) // g
        if tree_ref is None:
            mask = rows <= pos + ti
        else:
            # packed-tree mask: the committed prefix stays fully visible,
            # and within the fresh block (node m's K/V sits at row
            # pos + m) query node ti sees exactly its ancestor set — the
            # per-node bitmask broadcast into the tile via a static loop
            # over the (small) node count. A chain tree
            # (bits[q] = (1 << (q+1)) - 1) reproduces rows <= pos + ti
            # bit for bit.
            bits = jnp.zeros(sc.shape, jnp.int32)
            for q_t in range(t):
                bits = jnp.where(ti == q_t, tree_ref[i, q_t], bits)
            u = rows - pos
            vis = (u >= 0) & (u < t) & (
                (lax.shift_right_logical(bits, jnp.clip(u, 0, 31)) & 1) > 0
            )
            mask = (rows < pos) | vis
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_scr[...]                        # (R, 1)
        # every real query row keeps >= 1 valid key row (its own, written
        # this step), so after the final block m_new is finite; a tile row
        # fully masked within a `run` block (deeper query still ahead of
        # this shallower row) is safe: p zeroes under the mask and the
        # row's (m, l, acc) carry unchanged through alpha == 1
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.exp(sc - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if vs_ref is not None:
            v = (
                v_ref[...].astype(jnp.float32)
                * _head_columns(vs_ref[...], nkv, width)
            ).astype(q.dtype)                      # (bs, NKV·D)
        else:
            v = v_ref[...].astype(q.dtype)         # (bs, NKV·D)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # (R, NKV·D)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == bps - 1)
    def _finalize():
        # emit the split's raw (acc, m, l); the LSE combine happens outside
        o_ref[...] = acc_scr[...]
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]


def paged_flash_decode(
    q: jax.Array,             # (b, N, D) single query — or (b, t, N, D)
    k_pool: jax.Array,        # (num_blocks, bs, NKV, D): every layer's blocks in one run
    v_pool: jax.Array,        # (num_blocks, bs, NKV, D)
    block_tables: jax.Array,  # (b, W) int32; entries must be < num_blocks
    positions: jax.Array,     # (b,) int32 — row of the FIRST fresh query
    *,
    kv_limit: int | None = None,
    num_splits: int | None = None,
    k_scale: jax.Array | None = None,  # (num_blocks, bs, NKV) — quantized pool
    v_scale: jax.Array | None = None,
    quant_mxu: bool = False,
    row_live: jax.Array | None = None,  # (b,) int32 live query rows per lane
    tree_bits: jax.Array | None = None,  # (b, t) int32 ancestor bitmasks
) -> jax.Array:
    """Gather-free paged decode attention; returns q's shape in q.dtype.

    Logical row ``p`` of lane ``i`` lives at pool row
    ``block_tables[i, p // bs] * bs + p % bs``. A 3-dim q is the T == 1
    token-gen step: rows ``<= positions[i]`` are attended. A 4-dim q is a
    fresh block of t tokens (speculative verify / short suffix prefill)
    written at rows ``positions[i] .. positions[i] + t - 1``; query ``ti``
    attends rows ``<= positions[i] + ti`` (block-causal, matching the dense
    path's ``j <= position + t`` mask). Everything else (padding,
    null-block garbage) is masked. ``kv_limit`` (static) bounds the logical
    rows visited, exactly like the dense path. The caller guarantees every
    *used* query row sits below ``kv_limit``; extra query rows (bucket
    padding, rejected draft tail) produce garbage the caller discards.

    ``k_scale``/``v_scale`` mark a quantized pool (int8/fp8 payload with
    per-(row, head) absmax scales, docs/serving.md "Quantized KV pool"):
    the ``(bs, NKV)`` scale tiles ride through the *same* table-
    dereferencing index map as the payload blocks — one extra tiny DMA per
    block — and the kernel dequantizes in VMEM, so HBM traffic stays
    low-bit.

    ``row_live`` marks a mixed-width tile (the serving engine's
    ``fused_step`` packing): lane ``i``'s query rows ``>= row_live[i]``
    are padding whose outputs the caller discards, and the lane's KV walk
    stops at ``positions[i] + row_live[i] - 1`` instead of the static
    ``positions[i] + t - 1``. It rides in as a third scalar-prefetch
    operand; ``None`` (the default) lowers exactly the two-operand kernel.

    ``tree_bits`` marks the fresh block as a packed draft *tree* (tree
    speculation, docs/serving.md "Tree speculation"): bit ``m`` of
    ``tree_bits[i, q]`` is set iff node ``m`` is an ancestor-or-self of
    node ``q`` in lane ``i``'s tree (node j's K/V sits at row
    ``positions[i] + j``, so the in-block mask becomes the ancestor set
    instead of ``row <= positions[i] + ti`` while the committed prefix
    ``row < positions[i]`` stays fully visible). Requires ``t <= 32``
    (one int32 bitmask per node; the serving path caps t at
    ``paged_kernel_max_t``). It rides in as one more tiny (b, t)
    scalar-prefetch operand — the per-block KV DMA is unchanged, so all
    candidate branches share one pool read per block. A chain tree
    (``tree_bits[i, q] = (1 << (q+1)) - 1``) is bitwise the block-causal
    mask; ``None`` (the default) leaves every other lowering unchanged.

    ``quant_mxu`` (quantized pool only) keeps the q·k dot itself in low
    precision: int8 pools contract int8 × int8 operands accumulating in
    int32 (the query tile is requantized per row in VMEM), fp8 pools run
    fp8 × fp8 with ``preferred_element_type=float32`` — the absmax scales
    factor out of the contraction and multiply the fp32 score outputs, so
    no per-element pre-dot dequant happens. The p·v dot keeps the
    dequant-widen path (p is a freshly-computed fp probability, not a
    stored payload). Off (default), both dots see fp32-widened operands —
    the graftcheck GC005 contract for ``quant_mxu=False`` engines.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, t, n, d = q.shape
    nb, bs, nkv, _ = k_pool.shape
    if n % nkv:
        raise ValueError(f"q heads ({n}) must be a multiple of kv heads ({nkv})")
    g = n // nkv
    w = block_tables.shape[1]
    limit = kv_limit if kv_limit is not None else w * bs
    nblk = _ceil_div(limit, bs)
    if nblk > w:
        raise ValueError(f"kv_limit {limit} exceeds table capacity {w * bs}")
    splits = num_splits if num_splits is not None else DEFAULT_NUM_SPLITS
    splits = max(1, min(splits, nblk))
    bps = _ceil_div(nblk, splits)
    sm_scale = d ** -0.5
    tg = t * g
    rows, width = nkv * tg, nkv * d

    # block-diagonal query tile: row h·tG + ti·G + gi carries query token
    # ti, grouped head gi of kv head h in lanes [h·D, (h+1)·D), zeros in
    # every other head's lanes — one (R, NKV·D) · (bs, NKV·D)ᵀ dot scores
    # each row against its own kv head only
    qg = q.reshape(b, t, nkv, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, nkv, tg, d)
    q_bd = jnp.einsum(
        "bhrd,hk->bhrkd", qg, jnp.eye(nkv, dtype=q.dtype)
    ).reshape(b, rows, width)
    grid = (b, splits, bps)

    # index maps see every scalar-prefetch operand after the grid indices;
    # *rest absorbs the optional row_live / tree_bits operands so one set
    # of maps serves every lowering
    def q_idx(i, s, j, tbl, pos, *rest):
        return (i, 0, 0)

    def kv_idx(i, s, j, tbl, pos, *rest):
        # the gather-free read: the table entry IS the pool block index the
        # pipeline DMAs next; clamp covers split padding (those iterations
        # are predicated off in the kernel body)
        lb = jnp.minimum(s * bps + j, nblk - 1)
        return (tbl[i, lb], 0, 0)

    def out_idx(i, s, j, tbl, pos, *rest):
        return (i, s, 0, 0)

    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    if quant_mxu and not quantized:
        raise ValueError(
            "quant_mxu needs a quantized pool (k_scale/v_scale) — the fp "
            "pool has no low-bit payload to keep on the MXU"
        )
    if tree_bits is not None:
        if t > 32:
            raise ValueError(
                f"tree_bits packs ancestor sets into int32 bitmasks — "
                f"t ({t}) must be <= 32"
            )
        if tree_bits.shape != (b, t):
            raise ValueError(
                f"tree_bits must be (b, t) = {(b, t)}, got {tree_bits.shape}"
            )
    kernel = functools.partial(
        _decode_kernel, bs=bs, bps=bps, nblk=nblk, t=t, g=g, nkv=nkv,
        sm_scale=sm_scale, quantized=quantized, quant_mxu=quant_mxu,
        has_live=row_live is not None, has_tree=tree_bits is not None,
    )
    in_specs = [
        pl.BlockSpec((None, rows, width), q_idx),
        pl.BlockSpec((None, bs, width), kv_idx),
        pl.BlockSpec((None, bs, width), kv_idx),
    ]
    operands = [
        q_bd, k_pool.reshape(nb, bs, width), v_pool.reshape(nb, bs, width),
    ]
    if quantized:
        if k_scale.shape != (nb, bs, nkv) or v_scale.shape != (nb, bs, nkv):
            raise ValueError(
                f"scale arrays must be (num_blocks, bs, NKV) = "
                f"{(nb, bs, nkv)}, got {k_scale.shape} / {v_scale.shape}"
            )
        # the (bs, NKV) scale tile of a block arrives with its payload
        # through the same table dereference. Widened to f32 on the way
        # in: the stored dtype is float16, which the v5e vector unit (and
        # Mosaic) does not carry
        in_specs += [
            pl.BlockSpec((None, bs, nkv), kv_idx),
            pl.BlockSpec((None, bs, nkv), kv_idx),
        ]
        operands += [
            k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
        ]
    prefetch = [block_tables.astype(jnp.int32), positions.astype(jnp.int32)]
    if row_live is not None:
        prefetch.append(row_live.astype(jnp.int32))
    if tree_bits is not None:
        prefetch.append(tree_bits.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, rows, width), out_idx),
            # trailing singleton keeps the last-two-dims tiling legal
            pl.BlockSpec((None, None, rows, 1), out_idx),
            pl.BlockSpec((None, None, rows, 1), out_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, width), jnp.float32),
        ],
    )
    o_parts, m_parts, l_parts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, rows, width), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, rows, 1), jnp.float32),
        ],
        # lane/split carry independent scratch epochs (re-inited at
        # j == 0); only the innermost block dim is a true reduction
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
        name="paged_flash_decode",
    )(
        *prefetch,
        *operands,
    )

    # keep each row's own-head lanes (the block diagonal), then the
    # flash-decoding combine: merge the per-split partial softmaxes by
    # rescaling each to the global max (log-sum-exp), normalize once
    o_parts = jnp.einsum(
        "bshrhd->bshrd", o_parts.reshape(b, splits, nkv, tg, nkv, d)
    ).reshape(b, splits, rows, d)
    m_star = jnp.max(m_parts, axis=1, keepdims=True)       # (b,1,R,1)
    weight = jnp.where(
        m_parts == NEG_INF, 0.0, jnp.exp(m_parts - m_star)
    )                                                      # (b,S,R,1)
    l_tot = jnp.sum(weight * l_parts, axis=1)              # (b,R,1)
    acc = jnp.sum(weight * o_parts, axis=1)                # (b,R,D)
    out = acc / jnp.where(l_tot == 0.0, 1.0, l_tot)
    out = out.reshape(b, nkv, t, g, d).transpose(0, 2, 1, 3, 4)
    out = out.reshape(b, t, n, d).astype(q.dtype)
    return out[:, 0] if squeeze else out


# (row, kv head) pairs a loop trip of the walk copies and scores: at 8 kv heads
# 32 blocks of 16 rows, 1 MB of K and 1 MB of V a buffer and an (N, 4096) score
# tile (chip sweeps, PERF.md section 6, PR 43 and PR 56)
WALK_SPAN = 4096


def walk_group(bs: int, nkv: int) -> int:
    """Blocks a loop trip of :func:`paged_decode_walk` takes where none is
    given: as many as hold :data:`WALK_SPAN` (row, kv head) pairs — 32 blocks
    of 16 rows at 8 kv heads, 16 at 16 — so a buffer and a score tile keep
    their size whatever the pool's kv heads and block are."""
    return max(1, WALK_SPAN // (bs * nkv))


def window_walk_group(blocks: int, bs: int, nkv: int) -> int:
    """Blocks a loop trip of a walk of at most ``blocks`` blocks takes where a
    window bounds it: the walk cut into the nearest whole number of trips of
    :func:`walk_group` blocks, evenly — 257 blocks of 16 rows at 4 kv heads go
    65 a trip in 4 trips, 33 at 8 go in one — where ``walk_group`` itself
    would spend a whole score tile on the odd block (64 × 4 + 1, 32 + 1)."""
    span = walk_group(bs, nkv)
    trips = max(1, (blocks + span // 2) // span)
    return _ceil_div(blocks, trips)


def walk_fits(head_dim: int) -> bool:
    """Whether :func:`paged_decode_walk` takes a pool of ``head_dim`` columns.
    Mosaic wants a pool row to be one register's 128 lanes: it refuses to slice
    a block out of a run of 64-column rows (the tiling pads them), and at 256
    the ``(rows, D)`` view is a copy of the whole pool, not a bitcast. The
    interpreter has no tiling and takes any width."""
    return pallas_interpret() or head_dim == 128


def _walk_kernel(
    tbl_ref,    # scalar prefetch: (b, nblk) int32 pool blocks, the layer's offset folded in
    live_ref,   # scalar prefetch: (b,) int32 blocks each lane walks, >= 1
    pos_ref,    # scalar prefetch: (b,) int32 the query's row, counted from the walk's first block
    q_ref,      # (N, D) this lane's query heads
    k_hbm,      # (pool blocks · bs · NKV, D) the pool where it lies (HBM)
    v_hbm,
    o_ref,      # (N, D)
    kbuf,       # (2, group · bs · NKV, D) VMEM: group n + 1 lands while n is scored
    vbuf,
    sem,        # DMA semaphores (k | v, buffer)
    slot_ref,   # SMEM (1,): the buffer the group being scored lies in
    m_scr, l_scr, acc_scr,
    *, bs: int, nkv: int, group: int, sm_scale: float, first_ref=None, lo_ref=None,
):
    """One lane a grid step, its live blocks a group a loop trip. A buffer row
    is one (row, kv head) pair, ``row · NKV + head`` — the pool's own order —
    so every query head is scored against every pair by one dot and keeps its
    own kv head's columns under the mask; ``p · v`` then sums a head's own
    pairs alone and the (N, D) accumulator is the output, no diagonal to take.
    Under a window (:func:`_bounded_walk_kernel`) two more (b,) int32: block
    ``j`` of lane ``i``'s walk is table column ``first_ref[i] + j``, less the
    table's width where that passes it — the table is a ring — and
    ``lo_ref[i]`` is the first row of the walk the query sees, inside the
    walk's first block."""
    i = pl.program_id(0)
    lanes = pl.num_programs(0)
    pairs = bs * nkv                  # buffer rows a block
    n = q_ref.shape[0]
    span = group * pairs

    def copies(lane, grp, slot, start: bool):
        """The copies of group ``grp`` of ``lane`` into buffer ``slot``: its
        live blocks and no others, each 2 · ``pairs`` · D contiguous bytes."""
        first = grp * group

        def one(j, carry):
            column = first + j
            if first_ref is not None:
                # one compare and one select a block on the scalar unit: the
                # table rotated ahead of the call was a gather a layer a step
                # that cost half as much again as the walk (PERF.md, PR 62)
                column += first_ref[lane]
                column = jnp.where(column >= tbl_ref.shape[1], column - tbl_ref.shape[1], column)
            at = pl.multiple_of(tbl_ref[lane, column] * pairs, pairs)
            to = pl.multiple_of(j * pairs, pairs)
            for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                copy = pltpu.make_async_copy(
                    hbm.at[pl.ds(at, pairs)], buf.at[slot, pl.ds(to, pairs)],
                    sem.at[which, slot])
                if start:
                    copy.start()
                else:
                    copy.wait()
            return carry

        lax.fori_loop(0, jnp.minimum(live_ref[lane] - first, group), one, 0)

    @pl.when(i == 0)
    def _first():
        # rows no copy has filled yet are multiplied by p == 0: they have to
        # be numbers. After this they hold an earlier group's rows
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        copies(0, 0, 0, True)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    groups = pl.cdiv(live_ref[i], group)
    # the rows this lane sees: up to its position, and no further than its
    # walk (a lane on the null block carries any position)
    seen = jnp.minimum(pos_ref[i] + 1, live_ref[i] * bs)
    q = q_ref[...]
    # column c holds row c // NKV of kv head c % NKV: a query head sees its
    # own kv head's columns, up to the last row it sees
    col = lax.broadcasted_iota(jnp.int32, (n, span), 1)
    own = col % nkv == lax.broadcasted_iota(jnp.int32, (n, span), 0) // (n // nkv)
    col = jnp.where(own, col, jnp.int32(2 ** 30))

    def trip(grp, carry):
        slot = slot_ref[0]
        # the next group — this lane's, or the next lane's first — is in
        # flight while this one is scored
        @pl.when(grp + 1 < groups)
        def _():
            copies(i, grp + 1, 1 - slot, True)

        @pl.when((grp + 1 == groups) & (i + 1 < lanes))
        def _():
            copies(i + 1, 0, 1 - slot, True)

        copies(i, grp, slot, False)
        k, v = kbuf[slot].astype(q.dtype), vbuf[slot].astype(q.dtype)
        sc = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale             # (N, span)
        sees = col < (seen - grp * group * bs) * nkv
        if lo_ref is not None:
            sees &= col >= (lo_ref[i] - grp * group * bs) * nkv
        sc = jnp.where(sees, sc, NEG_INF)
        m_prev = m_scr[...]
        # a row of the walk's first block (row 0, or lo) is visible to every
        # head, so m is finite from the first group on and a masked column's
        # p is exp(-1e30 - m) == 0
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        slot_ref[0] = 1 - slot
        return carry

    lax.fori_loop(0, groups, trip, 0)
    o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _bounded_walk_kernel(tbl_ref, live_ref, pos_ref, first_ref, lo_ref, *refs, **static):
    """:func:`_walk_kernel` under a window: two more scalar-prefetch operands,
    the walk's first column and the lower bound, ahead of the operands it has
    without one."""
    _walk_kernel(tbl_ref, live_ref, pos_ref, *refs, first_ref=first_ref, lo_ref=lo_ref, **static)


def paged_decode_walk(
    q: jax.Array,             # (b, N, D) one query row a lane
    k_pool: jax.Array,        # (L, num_blocks, bs, NKV, D): one kind's whole pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (b, W) int32 blocks of a layer; 0 is the null block
    positions: jax.Array,     # (b,) int32 the query's row
    layer,                    # scalar: the layer of the pool this read is of
    *,
    kv_limit: int | None = None,
    group: int | None = None,
    window: int | None = None,
    null_lanes: jax.Array | None = None,
) -> jax.Array:
    """The decode read of a layer: softmax(q · k / √D over the rows a lane's
    query sees) · v, lane by lane over the blocks that hold those rows and no
    others, read where they lie. Returns q's shape in q.dtype.

    A query at ``positions[i]`` sees the rows at or before it — with a
    ``window`` (static), the last ``window`` of them, itself included. The row
    of position ``p`` lies in block ``(p // bs) mod W`` of the lane's table,
    row ``p mod bs``: a table as wide as the context never wraps, a ring does.
    Lane ``i`` walks from the block of the first row it sees (block 0 without a
    window) to the block of its own row — ``positions[i] // bs + 1`` blocks
    without a window, at most ``(window − 1) // bs + 2`` with one, and never
    more than ``kv_limit`` rows' worth or the table's width (a ring holds at
    least ``window − 1 + bs`` rows) — block ``j`` of them at the table's entry
    ``+ layer · num_blocks`` of the pool taken as one run of ``L · num_blocks``
    blocks. Under a window the kernel takes the position counted from the
    walk's first block and two more scalar operands a lane — the table column
    of that block (the copy loop wraps from there) and the first row seen,
    inside it; **with no window the call and its operands are what they were
    before the walk knew of one**. A null lane — ``null_lanes`` (b,) where
    given (a lane with a ring of its own: a ring's table is never 0), else one
    whose first block is the null block: idle, or mid-prefill beside the
    decode batch — walks the null block alone whatever position it carries;
    its output is numbers nobody reads. The pool goes in as ``(blocks · bs · NKV, D)``: under the
    TPU's tiling of the last two dimensions that is the same bytes (the
    optimized HLO holds a bitcast, no copy), and a block is ``bs · NKV`` whole
    rows of it, contiguous. Blocks are copied ``group`` at a time (None:
    :func:`walk_group` of the pool's shape, :func:`window_walk_group` of the
    window's blocks) into one of two VMEM buffers, the next group in flight
    while this one is folded into a float32 online softmax; p is cast to q's
    dtype for ``p · v`` as ``models.laguna.masked_attention`` and
    ``LlamaDecode._cache_attention`` do, which are this kernel's plain twins
    over gathered rows.
    """
    b, n, d = q.shape
    nl, nb, bs, nkv, _ = k_pool.shape
    if n % nkv:
        raise ValueError(f"q heads ({n}) must be a multiple of kv heads ({nkv})")
    width = block_tables.shape[1]
    nblk = width if kv_limit is None else min(width, _ceil_div(kv_limit, bs))
    null = block_tables[:, 0] == 0 if null_lanes is None else null_lanes
    if window is None:
        live = jnp.where(null, 1, jnp.clip(positions // bs + 1, 1, nblk))
        tables = block_tables[:, :nblk] + layer * nb
        scalars = (tables, live, positions)
        kernel = _walk_kernel
        if group is None:
            group = walk_group(bs, nkv)
    else:
        nblk = min(nblk, (window - 1) // bs + 2)
        lo = jnp.where(null, 0, jnp.maximum(positions - (window - 1), 0))
        first = lo // bs
        live = jnp.where(null, 1, jnp.clip(positions // bs - first + 1, 1, nblk))
        tables = jnp.where(null[:, None], 0, block_tables) + layer * nb
        scalars = (tables, live, positions - first * bs, first % width, lo - first * bs)
        kernel = _bounded_walk_kernel
        if group is None:
            group = window_walk_group(nblk, bs, nkv)
    span = group * bs * nkv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, n, d), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, n, d), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, span, d), k_pool.dtype),
            pltpu.VMEM((2, span, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, d), jnp.float32),
        ],
    )
    buffers = 2 * 2 * span * d * k_pool.dtype.itemsize
    return pl.pallas_call(
        functools.partial(kernel, bs=bs, nkv=nkv, group=group, sm_scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # the buffers, and the (N, span) float32 scores and what is made of
        # them; lanes in turn, because a lane starts the next lane's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + 10 * n * span * 4 + (8 << 20),
        ),
        interpret=pallas_interpret(),
        name="paged_decode_walk",
    )(
        *(a.astype(jnp.int32) for a in scalars),
        q, k_pool.reshape(nl * nb * bs * nkv, d), v_pool.reshape(nl * nb * bs * nkv, d),
    )


# blocks a loop trip of the latent walk copies and scores: 128 blocks of 16
# rows of 640 are 2.6 MB a buffer (chip sweep, PERF.md section 6, PR 45)
LATENT_WALK_GROUP = 128
# blocks whose copies one trip of the issuing loop starts, and awaits as one
LATENT_WALK_RUN = 16


def _latent_walk_kernel(
    tbl_ref,    # scalar prefetch: (b, nblk) int32 pool blocks, the layer's offset folded in
    live_ref,   # scalar prefetch: (b,) int32 blocks each lane walks, >= 1
    pos_ref,    # scalar prefetch: (b,) int32 the query's row
    q_ref,      # (N, W) this lane's absorbed query heads, zeros past the row's values
    pool_hbm,   # (pool blocks · bs, W) the pool where it lies (HBM)
    o_ref,      # (N, VW) the probabilities over the rows' first VW columns
    buf,        # (2, group · bs, W) VMEM: group n + 1 lands while n is scored
    sem,        # DMA semaphores (buffer)
    slot_ref,   # SMEM (1,): the buffer the group being scored lies in
    m_scr, l_scr, acc_scr,
    *, bs: int, group: int, sm_scale: float,
):
    """One lane a grid step, its live blocks a group a loop trip. A row is
    key and value at once — every head scores it whole and sums its first VW
    columns — so a trip is one buffer, one run of copies and two dots."""
    i = pl.program_id(0)
    lanes = pl.num_programs(0)
    n = q_ref.shape[0]
    vw = o_ref.shape[1]
    span = group * bs

    def copies(lane, grp, slot, start: bool):
        """Start, or await, the copies of group ``grp`` of ``lane`` into
        buffer ``slot``: its live blocks and no others, each ``bs`` whole
        rows, contiguous. ``LATENT_WALK_RUN`` blocks a loop trip, then the
        rest one by one: a trip's starts are straight-line code (issued one a
        trip of a loop over the count, the copies cost more scalar time than
        they took to land) and a trip's blocks are awaited as one — the
        semaphore counts bytes."""
        first = grp * group
        count = jnp.clip(live_ref[lane] - first, 0, group)

        def runs_of(width):
            def started(j, base):
                at = pl.multiple_of(tbl_ref[lane, first + base + j] * bs, bs)
                to = pl.multiple_of((base + j) * bs, bs)
                pltpu.make_async_copy(
                    pool_hbm.at[pl.ds(at, bs)], buf.at[slot, pl.ds(to, bs)], sem.at[slot]).start()
                return base

            def run(k, base):
                if start:
                    lax.fori_loop(0, width, started, base + k * width, unroll=True)
                else:
                    pltpu.make_async_copy(
                        pool_hbm.at[pl.ds(0, width * bs)],
                        buf.at[slot, pl.ds(0, width * bs)], sem.at[slot]).wait()
                return base

            return run

        wide = min(group, LATENT_WALK_RUN)
        whole = count // wide
        lax.fori_loop(0, whole, runs_of(wide), 0)
        lax.fori_loop(0, count - whole * wide, runs_of(1), whole * wide)

    @pl.when(i == 0)
    def _first():
        # rows no copy has filled yet are multiplied by p == 0: they have to
        # be numbers. After this they hold an earlier group's rows
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    groups = pl.cdiv(live_ref[i], group)
    # the rows this lane sees: up to its position, and no further than its
    # walk (a lane on the null block carries any position)
    seen = jnp.minimum(pos_ref[i] + 1, live_ref[i] * bs)
    q = q_ref[...]
    col = lax.broadcasted_iota(jnp.int32, (n, span), 1)

    def trip(grp, carry):
        # the group to score lies in buffer ``slot`` or is on its way there;
        # the next one — this lane's, or the next lane's first — is started
        # into the other before this one is awaited, and lands while it is
        # scored. ``grp`` -1 is the call's first step: nothing to score yet
        slot = slot_ref[0]
        last = grp + 1 == groups
        lane_next = jnp.where(last, jnp.minimum(i + 1, lanes - 1), i)
        grp_next = jnp.where(last, 0, grp + 1)

        @pl.when(jnp.logical_not(last & (i + 1 == lanes)))
        def _():
            copies(lane_next, grp_next, 1 - slot, True)

        @pl.when(grp >= 0)
        def _():
            copies(i, grp, slot, False)
            sc = lax.dot_general(
                q, buf[slot].astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale             # (N, span)
            sc = jnp.where(col < seen - grp * span, sc, NEG_INF)
            m_prev = m_scr[...]
            # row 0 of the walk is visible, so m is finite from the first
            # group on and a masked column's p is exp(-1e30 - m) == 0
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
                p.astype(q.dtype), buf[slot, :, :vw].astype(q.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        slot_ref[0] = 1 - slot
        return carry

    lax.fori_loop(jnp.where(i == 0, -1, 0), groups, trip, 0)
    o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def latent_decode_walk(
    q_abs: jax.Array,         # (b, N, r + d_r) one absorbed query row a lane
    pool: jax.Array,          # (L, num_blocks, bs, W): the latent pool, rows [c ‖ k_r ‖ 0…]
    block_tables: jax.Array,  # (b, T) int32 blocks of a layer; 0 is the null block
    positions: jax.Array,     # (b,) int32 the query's row
    layer,                    # scalar: the layer of the pool this read is of
    *,
    rank: int,                # r: the row's first columns are the values
    sm_scale: float,
    kv_limit: int | None = None,
    group: int = LATENT_WALK_GROUP,
) -> jax.Array:
    """The absorbed decode read of a latent layer: softmax(``sm_scale`` ·
    q_abs · rowᵀ over rows ``<= positions``) · row[:r], lane by lane over the
    lane's **live** blocks only, read where they lie. Returns (b, N, r) in
    q_abs's dtype — ``o_lat``, which ``W_UV`` takes from there.

    :func:`paged_decode_walk`'s walk over a pool whose row is key and value
    at once: lane ``i`` walks ``positions[i] // bs + 1`` blocks (at most
    ``kv_limit`` rows' worth), block ``j`` of them at ``block_tables[i, j] +
    layer · num_blocks`` of the pool taken as one run of ``L · num_blocks``
    blocks; a lane whose first block is the null block walks that one block
    whatever position it carries. The pool goes in as ``(blocks · bs, W)`` —
    the bytes as they lie, a block ``bs`` whole contiguous rows — and a loop
    trip copies ``group`` blocks into **one** of two VMEM buffers, the next
    group in flight while this one is scored. The query is padded with zeros
    to the pool's ``W`` columns, so nothing depends on what a row holds past
    its values; scores and the online softmax are float32, p is cast to the
    query's dtype for ``p · row[:r]`` and accumulated in float32 —
    ``models.sarvam.latent_attention(..., absorbed=True)``'s arithmetic over
    gathered rows, which is this kernel's plain twin."""
    d, w = q_abs.shape[-1], pool.shape[-1]
    if not rank <= d <= w:
        raise ValueError(f"need rank ({rank}) <= query width ({d}) <= pool row ({w})")
    return _latent_walk(
        q_abs, pool, block_tables, positions, jnp.asarray(layer, jnp.int32), rank=rank,
        sm_scale=sm_scale, kv_limit=kv_limit, group=group, interpret=pallas_interpret())


# a jit of its own: a program whose layer stacks each hold the call (the dense
# layers' scan and the expert layers') traces and lowers the kernel once
@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "kv_limit", "group", "interpret"))
def _latent_walk(q_abs, pool, block_tables, positions, layer, *, rank, sm_scale, kv_limit, group, interpret):
    b, n, d = q_abs.shape
    nl, nb, bs, w = pool.shape
    width = block_tables.shape[1]
    nblk = width if kv_limit is None else min(width, _ceil_div(kv_limit, bs))
    live = jnp.where(
        block_tables[:, 0] == 0, 1, jnp.clip(positions // bs + 1, 1, nblk))
    tables = block_tables[:, :nblk] + layer * nb
    # the values in whole lanes: the columns past r of the output are dropped
    vw = min(w, _ceil_div(rank, 128) * 128)
    span = group * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, n, w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, n, vw), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, span, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, vw), jnp.float32),
        ],
    )
    buffers = 2 * span * w * pool.dtype.itemsize
    o_lat = pl.pallas_call(
        functools.partial(_latent_walk_kernel, bs=bs, group=group, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, vw), q_abs.dtype),
        # the buffers, a group's rows in the query's dtype, and the (N, span)
        # float32 scores and what is made of them; lanes in turn, because a
        # lane starts the next lane's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * buffers + 10 * n * span * 4 + (8 << 20),
        ),
        interpret=interpret,
        name="latent_decode_walk",
    )(
        tables.astype(jnp.int32), live.astype(jnp.int32), positions.astype(jnp.int32),
        jnp.pad(q_abs, ((0, 0), (0, 0), (0, w - d))), pool.reshape(nl * nb * bs, w),
    )
    return o_lat[..., :rank]


def paged_flash_decode_tp(
    q: jax.Array,             # (b, N, D) single query — or (b, t, N, D)
    k_pool: jax.Array,        # (num_blocks, bs, NKV, D): every layer's blocks in one run
    v_pool: jax.Array,        # (num_blocks, bs, NKV, D)
    block_tables: jax.Array,  # (b, W) int32 — REPLICATED per rank
    positions: jax.Array,     # (b,) int32 — REPLICATED per rank
    *,
    mesh,
    kv_limit: int | None = None,
    num_splits: int | None = None,
    k_scale: jax.Array | None = None,  # (num_blocks, bs, NKV) — quantized pool
    v_scale: jax.Array | None = None,
    quant_mxu: bool = False,
    row_live: jax.Array | None = None,  # (b,) int32 — REPLICATED per rank
    tree_bits: jax.Array | None = None,  # (b, t) int32 — REPLICATED per rank
) -> jax.Array:
    """:func:`paged_flash_decode` sharded over the tensor-parallel mesh.

    ``pallas_call`` is opaque to the SPMD partitioner, so the kernel cannot
    live inside an auto-sharded jit region on a multi-chip mesh. This
    wrapper puts it in a manual (``shard_map``) region instead, split on the
    **NKV head axis** — the kernel grid is already ``(b, NKV, splits,
    blocks)``, so each rank runs the *identical* kernel body on its
    ``NKV/tp`` head slice:

    - q heads shard contiguously over tp (the QKV column-parallel layout):
      rank r's q heads ``[r·N/tp, (r+1)·N/tp)`` are exactly the G-groups of
      its kv heads ``[r·NKV/tp, (r+1)·NKV/tp)``, so per-rank GQA grouping
      (``g = N/NKV``) is unchanged and no head ever crosses a rank.
    - the K/V pool shards the same way (``LlamaDecode.paged_cache_specs``):
      the pool *block* dim stays whole per rank, so block tables index
      identically on every chip — per-chip pool bytes drop by tp, which is
      the multi-chip capacity win (tp× aggregate lanes/kv_limit at fixed
      per-chip HBM).
    - block tables, positions and the optional per-lane scalars
      (``row_live``, ``tree_bits``) ride in replicated, matching the
      serving engine's device-resident state: the ``lane_set``/
      ``table_delta`` scatters and the zero-upload steady state are
      layout-independent, and a tree's ancestor bitmasks are lane data,
      not head data — every rank masks identically.
    - the region contains NO collective: each rank's output is its head
      slice (out spec = q spec), and the model's row-parallel o-projection
      immediately after attention performs the tp reduction it already
      owned — the tp decode step adds zero extra communication.

    Axes the specs don't mention (dp/pp/cp/ep) replicate; eligibility
    (``_paged_kernel_eligible``) only routes here on a pure-tp mesh where
    those axes are size 1.

    The operand list is assembled dynamically (one closure serves the
    fp/quantized × row_live × tree_bits lattice) — each optional operand
    appends itself and its spec, so adding a kernel operand never forks
    another hand-written shard_map variant.
    """
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_llama3_2_tpu.parallel.state import TP_AXIS

    n = q.shape[-2]
    nkv = k_pool.shape[2]
    tp = mesh.shape[TP_AXIS]
    if n % tp or nkv % tp:
        raise ValueError(
            f"q heads ({n}) and kv heads ({nkv}) must both divide tp ({tp}); "
            "the caller (_paged_kernel_eligible) should have fallen back"
        )
    if k_scale is None and quant_mxu:
        raise ValueError(
            "quant_mxu needs a quantized pool (k_scale/v_scale)"
        )
    q_spec = (
        P(None, TP_AXIS, None) if q.ndim == 3 else P(None, None, TP_AXIS, None)
    )
    pool_spec = P(None, None, TP_AXIS, None)
    # quantized pool: the (num_blocks, bs, NKV) scale arrays split the SAME
    # kv-head axis as the payload pools, so each rank dequantizes its own
    # head slice locally — zero in-region collectives
    scale_spec = P(None, None, TP_AXIS)

    operands = [q, k_pool, v_pool]
    specs = [q_spec, pool_spec, pool_spec]
    has_scale = k_scale is not None
    if has_scale:
        operands += [k_scale, v_scale]
        specs += [scale_spec, scale_spec]
    operands += [block_tables, positions]
    specs += [P(None, None), P(None)]
    has_live = row_live is not None
    if has_live:
        operands.append(row_live)
        specs.append(P(None))
    has_tree = tree_bits is not None
    if has_tree:
        operands.append(tree_bits)
        specs.append(P(None, None))

    def local(*args):
        it = iter(args)
        qs, ks, vs = next(it), next(it), next(it)
        kss = next(it) if has_scale else None
        vss = next(it) if has_scale else None
        tbl, pos = next(it), next(it)
        live = next(it) if has_live else None
        bits = next(it) if has_tree else None
        return paged_flash_decode(
            qs, ks, vs, tbl, pos,
            kv_limit=kv_limit, num_splits=num_splits,
            k_scale=kss, v_scale=vss, quant_mxu=quant_mxu,
            row_live=live, tree_bits=bits,
        )

    # check_vma off: pallas_call carries no replication rule; the per-rank
    # outputs are genuinely tp-varying anyway
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=q_spec,
        check_vma=False,
    )(*operands)
