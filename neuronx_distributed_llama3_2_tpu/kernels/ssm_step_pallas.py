"""One visit a live lane's state a decode step of a Mamba-1 layer.

The step form of :func:`..models.jamba.selective_step` reads a lane's ``h``,
updates it — ``h' = exp(Δ ⊙ A) ⊙ h + (Δ ⊙ c) ⊗ B`` — and takes ``y = Σ_n h'·C``
from it. As plain XLA over the pool that was a pass over **every** slot of a
layer, live or not, and a second pass over the new ``h`` for ``y`` (``PERF.md``
section 5, smallchat). :func:`ssm_state_step` is the same arithmetic as **one**
Pallas call a layer that walks the live lanes: a visit brings one lane's whole
``h`` (N, D) into VMEM, updates it, takes ``y`` from the ``h'`` it still holds
and writes ``h'`` back to the same slot.

- The pool goes in where it lies (``memory_space=ANY``) as one run of
  ``layers · state_slots`` states and a lane's state is found by scalar
  prefetch of ``layer · state_slots + index`` (the retention and paged
  attention kernels' convention), with ``input_output_aliases`` pool → pool: a
  donated pool is updated in place and a slot no live lane names is never
  touched.
- **A lane that is not live moves nothing.** The walk is a loop over the
  count of live lanes (:func:`visits` lists them, in lane order), its own
  copies in and out a visit, the next slot on its way in and the last one's on
  its way out while this one is updated; where no lane is live nothing runs.
  (As a grid over the lanes with the idle ones' steps skipped, every step and
  every operand of it still cost scalar time — 0.034 ms a layer with one lane
  live, and skipped steps between live ones lost the pipeline's prefetch:
  chip runs, PR 50, ``PERF.md`` section 6.)
- A whole slot a visit: 320 KiB at the published widths is 0.8 µs of HBM time
  in and out; the body walks the slot :func:`..ssm_scan_pallas.channel_block`
  channels at a time so that a piece's operands stay in registers.
- The rows — Δ, Δ ⊙ c, B, C in and ``y`` out — go in and out whole as the
  mixer has them, lanes by values, and a visit picks its lane's row (a block
  of one lane would be another layout in HBM, and copies a layer to make it).
  ``y`` of a lane that is not live is not written by the call;
  :func:`ssm_step_paged` puts zero there. The state is computed in float32
  and goes on in the pool's dtype, ``y`` comes from the rounded ``h'`` — the
  plain form's order of roundings, bit for bit on the chip.

A multi-device mesh cannot partition a bare Mosaic call and the ``reference``
kernel mode asks for the plain twin: the caller
(:class:`..inference.model.JambaDecode`) keeps the pass over every slot around
``selective_step`` for both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_llama3_2_tpu.kernels.mode import pallas_interpret
from neuronx_distributed_llama3_2_tpu.kernels.ssm_scan_pallas import channel_block


def state_step_fits(d: int) -> bool:
    """Whether :func:`ssm_state_step` takes states of ``d`` channels: whole lanes."""
    return channel_block(d) > 0


def _state_walk_kernel(slot_ref, lane_ref, count_ref, delta_ref, dx_ref, bt_ref, ct_ref, a_ref, pool_hbm,
                       y_ref, pool_out_hbm, h_in, h_out, sem_in, sem_out, *, width):
    """The whole walk. slot_ref, lane_ref (b,), count_ref (1,): SMEM;
    delta_ref, dx_ref, y_ref (b, D), bt_ref, ct_ref (b, N) and a_ref (N, D):
    VMEM, whole; pool_hbm / pool_out_hbm (slots, N, D): the pool where it
    lies, one buffer under two names; h_in, h_out (2, N, D): the slot being
    visited and the next one's on its way in, this one's and the last one's
    on their way out; a DMA semaphore a buffer."""
    f32 = jnp.float32
    n, d = a_ref.shape
    count = count_ref[0]
    # a row of N values as a column: B and C multiply a state's rows
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(f32)

    def fetch(k, buf):
        return pltpu.make_async_copy(pool_hbm.at[slot_ref[k]], h_in.at[buf], sem_in.at[buf])

    def store(k, buf):
        return pltpu.make_async_copy(h_out.at[buf], pool_out_hbm.at[slot_ref[k]], sem_out.at[buf])

    @pl.when(count > 0)
    def _():
        fetch(0, 0).start()

    def visit(k, carry):
        buf = k % 2

        @pl.when(k + 1 < count)
        def _():
            fetch(k + 1, 1 - buf).start()

        fetch(k, buf).wait()

        @pl.when(k >= 2)
        def _():                    # the visit before last has left h_out[buf]
            store(k - 2, buf).wait()

        row = pl.ds(lane_ref[k], 1)
        b_col = jnp.sum(eye * bt_ref[row, :], axis=1, keepdims=True)
        c_col = jnp.sum(eye * ct_ref[row, :], axis=1, keepdims=True)
        for lo in range(0, d, width):
            at = slice(lo, lo + width)
            new = jnp.exp(delta_ref[row, at] * a_ref[:, at]) * h_in[buf, :, at].astype(f32) \
                + dx_ref[row, at] * b_col
            new = new.astype(h_out.dtype)
            h_out[buf, :, at] = new
            y_ref[row, at] = jnp.sum(new.astype(f32) * c_col, axis=0, keepdims=True)
        store(k, buf).start()
        return carry

    lax.fori_loop(0, count, visit, 0)
    for back in (2, 1):             # the last two visits' states are still on their way out

        @pl.when(count >= back)
        def _(back=back):
            store(count - back, (count - back) % 2).wait()


def ssm_state_step(slot, lane, count, h_flat, delta, dx, b_t, c_t, a):
    """slot, lane (b,) int32: the state and the lane of visit ``k``; count
    (1,) int32: the visits made (:func:`visits`); h_flat (slots, N, D); delta,
    dx = Δ ⊙ c (b, D) and b_t, c_t (b, N) float32, as the mixer has them; a
    (N, D). Returns (``h' C`` (b, D) float32, written at the lanes visited
    alone, and h_flat with their states updated in place)."""
    b = delta.shape[0]
    n, d = h_flat.shape[1:]
    if not state_step_fits(d) or not delta.shape == dx.shape == (b, d) or not b_t.shape == c_t.shape == (b, n):
        raise ValueError(
            f"states {h_flat.shape} of whole lanes do not fit rows {delta.shape} and columns {b_t.shape}")

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, slot, lane, count: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[whole(b, d), whole(b, d), whole(b, n), whole(b, n), whole(n, d),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[whole(b, d), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, n, d), h_flat.dtype), pltpu.VMEM((2, n, d), h_flat.dtype),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_state_walk_kernel, width=channel_block(d)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct(h_flat.shape, h_flat.dtype),
        ],
        # operands 0-2 are the prefetched walk: the pool is operand 8, output 1
        input_output_aliases={8: 1},
        # Δ, Δ ⊙ c and y whole (each may get two buffers), A, and four slots
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * 4 * (3 * b * d + n * d) + 4 * n * d * 4 + (8 << 20),
        ),
        interpret=pallas_interpret(),
        name="ssm_state_step",
    )(slot.astype(jnp.int32), lane.astype(jnp.int32), count.astype(jnp.int32),
      delta, dx, b_t, c_t, a, h_flat)


def visits(live):
    """live (b,) bool -> (lane (b,) int32, count (1,) int32): the lanes the
    walk visits — the live ones, in lane order, in the first ``count`` places
    (the rest are not read) — and how many."""
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    return order, jnp.sum(live, dtype=jnp.int32)[None]


def ssm_step_paged(h_flat, slot, lane, count, live, delta, c, b_t, c_t, a, d_skip):
    """:func:`..models.jamba.selective_step` for every lane at once over the
    pool in place. h_flat (slots, N, D): the pool as one run of slots; live
    (b,) bool: a lane that is not live — idle, or mid-prefill beside the
    batch — leaves every slot as it was and gets ``y`` = 0; (lane, count) =
    :func:`visits` of ``live`` and slot (b,) the slots of those lanes (the
    same for every layer but for the layer's offset: the caller's to make
    once); delta (b, D), b_t, c_t (b, N) float32; c (b, D); a (N, D); d_skip
    (D,). Returns (y (b, D) float32, h_flat)."""
    c = c.astype(jnp.float32)
    y, h_flat = ssm_state_step(slot, lane, count, h_flat, delta, delta * c, b_t, c_t, a)
    return jnp.where(live[:, None], y + d_skip * c, 0.0), h_flat
