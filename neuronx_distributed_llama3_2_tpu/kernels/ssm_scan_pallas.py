"""A block of rows through a Mamba-1 layer's recurrence in one call.

The chunk form of :func:`..models.jamba.selective_scan` is a loop over time
with the state as its carry: ``h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ c_t) ⊗ B_t``,
``y_t = h_t C_t``. As plain XLA every trip is two or three device operations —
0.85 µs a row a layer, and some two million events in a three-second profile
(``PERF.md`` section 6, PR 49). :func:`ssm_chunk_scan` is the same arithmetic
as **one** Pallas call a layer: a grid step holds ``CHANNEL_BLOCK`` channels of
one sequence — its ``h`` (N, channels) in registers, the block's Δ and Δ ⊙ c
rows in VMEM — and walks the rows eight at a trip. On the chip it is bit for
bit the loop's ``y`` and ``h`` at 2.4 times its speed; the vector unit bounds
it (one ``exp`` and some nine operations a vreg of state a row).

- The state is held ``(N, D)`` as the pool holds it: a row's Δ is a sublane
  broadcast, ``B_t`` and ``C_t`` are lane broadcasts of a column. So that a
  column is a static slice, B and C come in as ``(t / 8, N, 8)``: trip ``g``
  indexes the leading axis and takes columns 0 … 7.
- Rows at or past ``live`` have Δ = 0 as the caller masks them; whole trips
  past the last live row are not run and their ``y`` is zero (the plain form
  gives ``D ⊙ c`` there: a padded row's output means nothing).
- The state is computed in float32 and handed from row to row in the dtype it
  came in, as the plain form hands it: a pool in bfloat16 loses what it loses
  there.

A multi-device mesh cannot partition a bare Mosaic call and the ``reference``
kernel mode asks for the plain twin: the caller
(:class:`..inference.model.JambaDecode`) keeps ``lax.scan`` for both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_llama3_2_tpu.kernels.mode import pallas_interpret

# rows a trip of the loop: a float32 tile's sublanes
ROW_GROUP = 8
# channels a grid step: h (16, 1024) float32 is 16 vregs of carry. A 512-row
# bucket at Jamba2-3B's widths, ms a layer (chip runs, PR 49): 256 channels
# 0.243, 512 0.170, 1,024 0.151, 2,560 0.156, 5,120 0.179; the loop a row 0.366
CHANNEL_BLOCK = 1024


def channel_block(d: int) -> int:
    """The widest block of whole lanes up to :data:`CHANNEL_BLOCK` that
    divides ``d`` channels; 0 where none does."""
    return next((w for w in (CHANNEL_BLOCK, 512, 256, 128) if d % w == 0), 0)


def chunk_scan_fits(t: int, d: int) -> bool:
    """Whether :func:`ssm_chunk_scan` takes a block of ``t`` rows over ``d``
    channels: whole trips, whole lanes."""
    return t % ROW_GROUP == 0 and channel_block(d) > 0


def _chunk_scan_kernel(live_ref, h_ref, delta_ref, dx_ref, bt_ref, ct_ref, a_ref,
                       y_ref, h_out_ref):
    """One sequence's block of channels. h_ref / h_out_ref (N, W); delta_ref,
    dx_ref, y_ref (t, W); bt_ref, ct_ref (t / 8, N, 8); a_ref (N, W)."""
    f32 = jnp.float32
    a = a_ref[...]
    trips = (live_ref[pl.program_id(0)] + ROW_GROUP - 1) // ROW_GROUP
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def trip(g, h):
        at = pl.ds(pl.multiple_of(g * ROW_GROUP, ROW_GROUP), ROW_GROUP)
        delta, dx = delta_ref[at, :], dx_ref[at, :]
        b_cols, c_cols = bt_ref[g], ct_ref[g]
        ys = []
        for j in range(ROW_GROUP):
            h = jnp.exp(delta[j:j + 1, :] * a) * h + dx[j:j + 1, :] * b_cols[:, j:j + 1]
            h = h.astype(h_out_ref.dtype).astype(f32)
            ys.append(jnp.sum(h * c_cols[:, j:j + 1], axis=0, keepdims=True))
        y_ref[at, :] = jnp.concatenate(ys, axis=0)
        return h

    h = lax.fori_loop(0, trips, trip, h_ref[...].astype(f32))
    h_out_ref[...] = h.astype(h_out_ref.dtype)


def ssm_chunk_scan(h, delta, dx, b_t, c_t, a, live):
    """h (b, N, D) in the pool's dtype; delta, dx = Δ ⊙ c (b, t, D) float32,
    Δ already 0 at or past ``live`` (b,) int32; b_t, c_t (b, t, N) float32; a
    (N, D) = −exp(A_log). Returns (``h_t C_t`` (b, t, D) float32, h)."""
    b, n, d = h.shape
    t = delta.shape[1]
    if not chunk_scan_fits(t, d):
        raise ValueError(f"a block of {t} rows over {d} channels is not whole trips of whole lanes")
    w = channel_block(d)

    def columns(x):     # (b, t, N) -> (b, t / 8, N, 8): a trip's columns side by side
        return jnp.swapaxes(x.reshape(b, t // ROW_GROUP, ROW_GROUP, n), 2, 3)

    rows = pl.BlockSpec((None, t, w), lambda i, j, live: (i, 0, j))
    state = pl.BlockSpec((None, n, w), lambda i, j, live: (i, 0, j))
    cols = pl.BlockSpec((None, t // ROW_GROUP, n, ROW_GROUP), lambda i, j, live: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, d // w),
        in_specs=[state, rows, rows, cols, cols, pl.BlockSpec((n, w), lambda i, j, live: (0, j))],
        out_specs=[rows, state],
    )
    # Δ, Δ ⊙ c and y, each double-buffered; the columns pad out to whole lanes
    vmem = 6 * t * w * 4 + 4 * (t // ROW_GROUP) * max(n, 8) * 128 * 4 + (8 << 20)
    return pl.pallas_call(
        _chunk_scan_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), jnp.float32),
            jax.ShapeDtypeStruct(h.shape, h.dtype),
        ],
        # operand 0 is the prefetched ``live``: h is operand 1, output 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=vmem),
        interpret=pallas_interpret(),
        name="ssm_chunk_scan",
    )(live.astype(jnp.int32), h, delta, dx, columns(b_t), columns(c_t), a)
