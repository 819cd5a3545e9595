"""A block of rows through a block-sparse attention layer with its scores on the chip.

The chunk read of :class:`..inference.model.SalaDecode`'s sparse layers
(``pctx`` / ``psfx``: a block of ``t`` query rows over the first ``limit`` rows
of a lane's context, under the selection's per-(row, kv head, block) mask) was
:func:`..models.minicpm_sala.attend_tiles` as plain XLA: a ``lax.scan`` over
2,048-row tiles whose float32 scores ``(b, NKV, g, t, 2048)`` — 134 MB at the
published widths — were written to HBM and read back some four times a tile
(mask, max, exp, sum, the cast for ``p · v``): 0.64–0.75 ms a tile a layer for
17 GFLOP of matmul (``PERF.md`` section 5, PR 52). :func:`sparse_chunk_attend`
is the same arithmetic as **one** Pallas call a layer in which a tile's scores
never leave VMEM.

- **The grid** is (sequence, kv head, query tile, kv tile), kv innermost so
  that the running max, sum and accumulator sit in VMEM scratch across a
  query tile's walk. The ``g`` query heads of a kv group share its rows and —
  by construction of the selection — its mask, so the matmuls' rows are the
  whole group's: ``g ·`` :func:`query_tile` rows against :func:`kv_tile` keys.
- **The rows arrive in order.** The caller gathers the rung's blocks of the
  layer once into ``(b, NKV, limit, d)`` (block-wise, through the table: 34 MB
  read and written at the top rung, ≈ 0.1 ms, where the tile walk's per-tile
  gathers moved the same bytes) and the pipeline brings a kv tile a step.
- **A block's flag becomes its rows' lanes on the MXU.** The mask comes in as
  bfloat16 flags ``(b, NKV, t, blocks)``, the block axis padded to whole
  vregs of 128 lanes; a step holds the 128 flags that contain its kv tile's
  and multiplies them by a one-hot ``(128, kv tile)`` matrix built from two
  iotas — flag ``c`` on the lanes of block ``c``'s rows — which is exact and
  costs a sixteenth of the score matmul. ``position ≤ the row's own`` is two
  more iotas; both masks are made once a step for the query tile's rows and
  broadcast over the group's heads.
- **What it skips**: a kv tile wholly past the query tile's last position —
  the rung is the ladder's next step above the context, and a chunk's later
  rows see further than its first — is not computed, and not fetched either
  (the index maps stay on the last tile that is needed, so the pipeline has
  nothing new to bring). A tile no row of the query tile names is *not*
  skipped: under seeded weights 128 rows' choices cover the context.
- Rows are at consecutive positions: row ``i`` of sequence ``b`` is at
  ``q_start[b] + i`` (a scalar a sequence, prefetched), as every block of rows
  a paged program holds is.

Arithmetic as :func:`..models.minicpm_sala.attend_tiles`, row for row: the two
dots in the operands' dtype with float32 accumulation, the scale on the
float32 product, float32 max / sum / accumulator, the weights cast to v's
dtype before ``p · v``, masked scores at −1e30 and their weights zero — zero
by underflow, the running max starting above the masked score, where the twin
starts at it and zeroes them with a second select: one pass less over the
scores, the same weights. Only the tile differs otherwise (512 rows where the
twin walks 2,048), so the two agree to rounding, not bit for bit.

On the chip (``scripts/sparse_chunk_bench.py``, ``PERF.md`` section 6, PR 53)
the call runs a 2,048-row tile-equivalent of a layer in 0.17 ms — half the
FLOP peak — where the twin took 0.66. The elementwise passes run over the
whole ``(g · 128, 512)`` tile as straight-line code: a loop over strips of 16
or 32 rows that kept a strip's scores in registers was two to three times
slower (0.3 µs a trip: the reductions' latency with nothing to overlap).

A multi-device mesh cannot partition a bare Mosaic call and the ``reference``
kernel mode asks for the plain twin: the caller keeps ``attend_tiles`` for
both, and for a shape :func:`chunk_attend_fits` refuses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_llama3_2_tpu.kernels.mode import pallas_interpret

# query rows a grid step (times the group's heads: the matmuls' rows)
QUERY_TILE = 128
# keys a grid step: 512 divides every rung of the serving ladders
KV_TILE = 512
# flags a step holds: one vreg's lanes
FLAG_LANES = 128
_MASKED = -1e30
# where the running max starts: above a masked score by enough that its weight
# underflows to zero by itself (``attend_tiles`` starts at the masked score and
# zeroes masked weights with a select: the same weights)
_START = -0.5e30


def query_tile(t: int) -> int:
    """Query rows a grid step of a block of ``t`` rows: :data:`QUERY_TILE`
    where it divides ``t``, the block whole where it is shorter and whole
    sublanes; 0 where neither."""
    if t % QUERY_TILE == 0:
        return QUERY_TILE
    return t if t < QUERY_TILE and t % 8 == 0 else 0


def kv_tile(limit: int) -> int:
    """Keys a grid step over ``limit`` rows: the widest of 512, 256, 128 that
    divides them, a shorter rung whole; 0 where none."""
    return next((w for w in (KV_TILE, 256, 128) if limit % w == 0), limit if limit < KV_TILE else 0)


def chunk_attend_fits(t: int, limit: int, block: int) -> bool:
    """Whether :func:`sparse_chunk_attend` takes a block of ``t`` rows over
    ``limit`` rows in selection blocks of ``block``: whole query tiles, whole
    kv tiles of whole blocks, and a kv tile's flags inside one vreg's lanes."""
    tq, tk = query_tile(t), kv_tile(limit)
    return tq > 0 and tk > 0 and tk % block == 0 and FLAG_LANES % (tk // block) == 0


def _chunk_attend_kernel(start_ref, q_ref, k_ref, v_ref, flag_ref, o_ref, high, total, acc,
                         *, group, tq, tk, block, scale):
    """One (sequence, kv head, query tile, kv tile). q_ref, o_ref (group · tq,
    d): the group's heads' rows, head-major; k_ref, v_ref (tk, d); flag_ref
    (tq, 128): the flags of the 128 blocks that hold this kv tile's; high,
    total (group, tq, 1) and acc (group · tq, d) float32: the walk's state."""
    f32 = jnp.float32
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first = start_ref[b] + i * tq                   # the query tile's first position

    @pl.when(j == 0)
    def _():
        high[...] = jnp.full(high.shape, _START, f32)
        total[...] = jnp.zeros(total.shape, f32)
        acc[...] = jnp.zeros(acc.shape, f32)

    @pl.when(j * tk <= first + tq - 1)
    def _():
        k, v = k_ref[...], v_ref[...]
        s = lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())), preferred_element_type=f32) * scale
        # flag c of the vreg names the lanes of block c's rows
        c = lax.broadcasted_iota(jnp.int32, (FLAG_LANES, tk), 0) - (j * (tk // block)) % FLAG_LANES
        lane = lax.broadcasted_iota(jnp.int32, (FLAG_LANES, tk), 1)
        spread = ((lane >= c * block) & (lane < (c + 1) * block)).astype(flag_ref.dtype)
        named = jnp.dot(flag_ref[...], spread, preferred_element_type=f32) > 0.5
        at = j * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        own = first + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        seen = (named & (at <= own))[None]                                   # (1, tq, tk)
        s = jnp.where(seen, s.reshape(group, tq, tk), _MASKED)
        old = high[...]
        new = jnp.maximum(old, jnp.max(s, axis=-1, keepdims=True))
        w = jnp.exp(s - new)                # a masked score's weight underflows to zero: see _START
        keep = jnp.exp(old - new)
        high[...] = new
        total[...] = total[...] * keep + jnp.sum(w, axis=-1, keepdims=True)
        acc[...] = acc[...] * keep.reshape(group * tq, 1) + jnp.dot(
            w.reshape(group * tq, tk).astype(v.dtype), v, preferred_element_type=f32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc[...] / jnp.maximum(total[...], 1e-30).reshape(group * tq, 1)).astype(o_ref.dtype)


def sparse_chunk_attend(q, k, v, mask, q_start, block: int):
    """Softmax attention of q (b, t, N, d), row ``i`` of sequence ``b`` at
    position ``q_start[b] + i``, over k, v (b, NKV, limit, d) — the context's
    rows in order, a kv head's together — where a row sees the positions at or
    before its own inside the blocks of ``block`` rows that ``mask`` (b, t,
    NKV, limit / block) bool names. Returns (b, t, N, d) in q's dtype."""
    t, limit = q.shape[1], k.shape[2]
    if not chunk_attend_fits(t, limit, block) or mask.shape[-1] * block != limit:
        raise ValueError(
            f"a block of {t} rows over {limit} rows in blocks of {block} (mask {mask.shape}) "
            "is not whole query tiles over whole kv tiles of whole blocks")
    return _chunk_attend(q, k, v, mask, q_start, block=block, interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _chunk_attend(q, k, v, mask, q_start, *, block, interpret):
    # a jit of its own: a program's two runs of sparse layers trace and lower
    # the call once (the kernel mode is read at trace time, so it is an argument)
    b, t, n, d = q.shape
    nkv, limit = k.shape[1:3]
    group, tq, tk = n // nkv, query_tile(t), kv_tile(limit)
    tiles, per = limit // tk, tk // block
    # the group's heads' rows of a query tile together, head-major
    rows = jnp.transpose(q.reshape(b, t // tq, tq, nkv, group, d), (0, 3, 1, 4, 2, 5))
    rows = rows.reshape(b, nkv, t // tq, group * tq, d)
    flags = jnp.swapaxes(mask, 1, 2).astype(jnp.bfloat16)
    flags = jnp.pad(flags, ((0, 0), (0, 0), (0, 0), (0, -flags.shape[-1] % FLAG_LANES)))

    def tile_of(b, i, j, start):
        # past the last kv tile a query tile sees the index stays: nothing new to bring
        return jnp.minimum(j, jnp.minimum((start[b] + (i + 1) * tq - 1) // tk, tiles - 1))

    q_spec = pl.BlockSpec((None, None, None, group * tq, d), lambda b, h, i, j, start: (b, h, i, 0, 0))
    kv_spec = pl.BlockSpec((None, None, tk, d), lambda b, h, i, j, start: (b, h, tile_of(b, i, j, start), 0))
    flag_spec = pl.BlockSpec(
        (None, None, tq, FLAG_LANES),
        lambda b, h, i, j, start: (b, h, i, tile_of(b, i, j, start) * per // FLAG_LANES))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nkv, t // tq, tiles),
        in_specs=[q_spec, kv_spec, kv_spec, flag_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, tq, 1), jnp.float32), pltpu.VMEM((group, tq, 1), jnp.float32),
            pltpu.VMEM((group * tq, d), jnp.float32),
        ],
    )
    size = q.dtype.itemsize
    # q and o, k and v, each double-buffered; the state (max and sum a lane
    # each of a vreg's 128); some five (rows, tk) float32 arrays of scores
    vmem = 4 * group * tq * d * size + 4 * tk * d * size + group * tq * (d + 2 * 128) * 4 \
        + 5 * group * tq * tk * 4 + (8 << 20)
    out = pl.pallas_call(
        functools.partial(
            _chunk_attend_kernel, group=group, tq=tq, tk=tk, block=block, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(rows.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="sparse_chunk_attend",
    )(q_start.astype(jnp.int32), rows, k, v, flags)
    out = jnp.transpose(out.reshape(b, nkv, t // tq, group, tq, d), (0, 2, 4, 1, 3, 5))
    return out.reshape(b, t, n, d)
