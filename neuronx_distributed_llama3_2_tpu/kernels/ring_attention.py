"""Ring attention: context parallelism for long sequences.

The reference has NO context-parallel strategy — its long-context story is
Megatron-SP + selective checkpointing + the NKI flash kernel, tested to 32K
(SURVEY §2.10 long-context row; test_long_seqlen.py:13). On TPU we make
sequence/context parallelism first-class: the sequence dim is sharded over a
``cp`` mesh axis and attention runs as a **ring** — each device holds one
q/k/v sequence chunk, k/v chunks rotate around the ring via
``lax.ppermute`` (one ICI hop per step), and each device folds every
visiting k/v chunk into its local queries' online-softmax state. Peak memory
is O(S/cp) per device; comm is the k/v chunk per step, overlappable with
the chunk's attention math.

Causality over chunks: with contiguous partitioning, ring step r on device i
sees the k/v chunk of device ``(i - r) mod cp``; chunks entirely in the
future are masked (their compute is wasted — the classic contiguous-ring
imbalance), the diagonal chunk is causal-masked, past chunks attend fully.

This module holds the pure-jnp executor — the numerics oracle, taken in
the ``"reference"`` kernel mode (:mod:`.mode`). Otherwise
:func:`ring_attention_sharded` dispatches to the Pallas-fused executors
(``ring_attention_pallas.py``): the FA2 kernel per visiting chunk, a
custom-VJP ring backward, and zigzag chunk
assignment that fixes the causal imbalance (each device holds half-chunks
``(i, 2cp-1-i)``, so every ring step does equal work everywhere).

Autodiff: the ring is a ``lax.scan`` whose carry is the (acc, m, l) softmax
state plus the rotating k/v; each step is ``jax.checkpoint``-ed, so the
backward replays single steps (XLA differentiates the ppermute into the
reverse rotation) — activation memory stays O(S/cp), matching the forward.

Usage: inside a shard_map manual over the cp axis (the model wraps this;
:func:`ring_attention` is also usable standalone), with q/k/v already
RoPE'd — rope is elementwise in sequence so it stays outside, auto-sharded.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from neuronx_distributed_llama3_2_tpu.kernels.flash_attention import (
    DEFAULT_BLOCK_KV,
    ambient_manual_axes,
    blockwise_attention_stats,
    manual_attention_region,
)
from neuronx_distributed_llama3_2_tpu.kernels.mode import prefer_pallas


def _chunk_attn_stats(
    q, k, v, q_off, kv_off, causal, kv_len, block_kv=DEFAULT_BLOCK_KV
):
    """One ring step's stats: local q against one visiting k/v chunk at
    global offsets (q_off, kv_off). Delegates to the shared blockwise
    online-softmax primitive (kernels/flash_attention.py) so the delicate
    numerics live in exactly one place; the inner block loop keeps memory
    at O(Sq · block_kv) per ring step in forward AND backward (each block
    step is checkpointed there)."""
    return blockwise_attention_stats(
        q, k, v,
        causal=causal,
        q_off=q_off,
        kv_off=kv_off,
        kv_len=kv_len,
        block_kv=block_kv,
    )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    kv_len: Optional[int] = None,
    block_kv: int = DEFAULT_BLOCK_KV,
) -> jax.Array:
    """Exact attention over the cp-sharded sequence (call under shard_map
    manual over ``axis_name``). q/k/v are the local chunks (B, S/cp, N, D) /
    (B, S/cp, Nkv, D) of a contiguous sequence split; returns the local
    output chunk (B, S/cp, N, D)."""
    cp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, n, d = q.shape

    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def merge(carry, stats):
        acc, m, l = carry
        a2, m2, l2 = stats
        m_new = jnp.maximum(m, m2)
        # fully-masked chunks keep m2 == -1e30: their alpha2 underflows to 0
        alpha = jnp.exp(m - m_new)
        alpha2 = jnp.exp(m2 - m_new)
        return (
            acc * alpha[..., None] + a2 * alpha2[..., None],
            m_new,
            l * alpha + l2 * alpha2,
        )

    def stats_for(kc, vc, r):
        src = (idx - r) % cp  # which device's chunk is visiting
        return _chunk_attn_stats(
            q, kc, vc,
            q_off=idx * s_loc,
            kv_off=src * s_loc,
            causal=causal,
            kv_len=kv_len,
            block_kv=block_kv,
        )

    def step(carry, r):
        acc, m, l, kc, vc = carry
        # rotate first (r starts at 1): the local chunk was consumed before
        # the scan, and no dead hop is paid after the last visiting chunk
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        acc, m, l = merge((acc, m, l), stats_for(kc, vc, r))
        return (acc, m, l, kc, vc), None

    local = jax.checkpoint(stats_for)(k, v, 0)
    if cp > 1:
        (acc, m, l, _, _), _ = lax.scan(
            jax.checkpoint(step), (*local, k, v), jnp.arange(1, cp)
        )
    else:
        acc, m, l = local
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, s_loc, n, d).astype(q.dtype)


def resolve_cp_layout(seq: int, cp: int, causal: bool = True,
                      force: str = "auto") -> str:
    """Decide the cp sequence layout: ``"zigzag"`` or ``"contiguous"``.

    The model permutes its hidden states ONCE (after embedding, inverse
    before the loss) when this returns zigzag, so every attention layer
    runs the balanced ring with no per-call layout shuffles. ``force``
    ("auto"/"contiguous"/"zigzag") comes from the model config (tests
    force zigzag on CPU).

    PROVISIONAL: the zigzag-over-contiguous choice rests on the analytic
    critical path (~(cp+1)/2 vs cp full-chunk attentions) and
    interpret-mode parity — no on-chip rotation timing has been taken
    (ROADMAP S5 decides it on the four-chip host)."""
    if force != "auto":
        return force
    if causal and seq % (2 * cp) == 0 and prefer_pallas():
        return "zigzag"
    return "contiguous"


# Trace-time layout context: the site that PERMUTES the hidden states
# (backbone / pipeline executor) declares the layout around the layer
# stack, and attention layers read it — one source of truth, so a
# layout/executor mismatch is impossible by construction. Executors that
# never permute (the 1F1B manual-VJP path) simply don't set it and their
# attention stays contiguous. Purely static (python-level): captured at
# jit trace time like any other structural decision.
_CP_LAYOUT_STACK: list = []


@contextlib.contextmanager
def cp_layout(layout: str):
    """Declare the cp sequence layout for attention calls traced inside."""
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"layout must be contiguous|zigzag, got {layout!r}")
    _CP_LAYOUT_STACK.append(layout)
    try:
        yield
    finally:
        _CP_LAYOUT_STACK.pop()


def active_cp_layout() -> str:
    return _CP_LAYOUT_STACK[-1] if _CP_LAYOUT_STACK else "contiguous"


def cp_layout_from_inv(zz_inv):
    """The executor-side declare ceremony in one place: pass the inverse
    permutation returned by ``_zigzag_enter`` (None ⇒ contiguous)."""
    return cp_layout("zigzag" if zz_inv is not None else "contiguous")


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    axis_name: str,
    causal: bool = True,
    block_kv: int = DEFAULT_BLOCK_KV,
    impl: str = "auto",
    pre_permuted: bool = False,
) -> jax.Array:
    """Global-view entry point: q/k/v (B, S, N, D) with S sharded over
    ``axis_name``; wraps a ring executor in a partial-manual shard_map.
    Only the cp axis goes manual — specs may not mention other axes, so
    batch (dp/ep) and head (tp) shardings stay GSPMD-auto.

    ``impl``: ``"jnp"`` (blockwise online-softmax ring, any backend),
    ``"pallas"`` (Pallas FA2 kernel per visiting chunk,
    ring_attention_pallas.py), ``"zigzag"`` (pallas + zigzag-balanced
    chunk assignment — the causal-imbalance fix), or ``"auto"`` (zigzag
    when the shapes allow, else pallas — the jnp ring throughout in the
    ``"reference"`` kernel mode, :mod:`.mode`).

    ``pre_permuted``: the inputs are ALREADY in zigzag layout (the model
    permutes once outside the layer stack — the cheap path); without it
    the zigzag impl applies the layout permutation around the shard_map
    itself, paying an all-to-all-shaped shuffle per call (standalone
    use / oracle tests only)."""
    from jax.sharding import PartitionSpec as P

    cp = mesh.shape[axis_name]
    seq = q.shape[1]
    if impl == "auto":
        # same eligibility rule as the model's permute site — one owner
        if resolve_cp_layout(seq, cp, causal) == "zigzag":
            impl = "zigzag"
        else:
            impl = "pallas" if prefer_pallas() else "jnp"
    if impl == "zigzag" and seq % (2 * cp):
        # validate here too: with pre_permuted=True the zigzag_permutation
        # check below never runs, and a bad shape would otherwise die as a
        # cryptic _halves/concat mismatch inside the kernel
        raise ValueError(
            f"zigzag ring needs seq % (2*cp) == 0, got seq={seq} cp={cp}"
        )

    if impl == "jnp":
        # kv_len=None: the sequence is exactly S with no padding; pass a
        # real length here only when wiring padded-batch support
        fn = functools.partial(
            ring_attention, axis_name=axis_name, causal=causal, kv_len=None,
            block_kv=block_kv,
        )
    elif impl in ("pallas", "zigzag"):
        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention_pallas import (
            ring_attention_pallas,
        )

        fn = functools.partial(
            ring_attention_pallas, axis_name=axis_name, causal=causal,
            zigzag=(impl == "zigzag"), block_kv=block_kv,
        )
    else:
        raise ValueError(f"impl must be auto|jnp|pallas|zigzag, got {impl!r}")

    perm = inv = None
    if impl == "zigzag" and not pre_permuted:
        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention_pallas import (
            zigzag_permutation,
        )

        # layout shuffle (an all-to-all-shaped gather): each device swaps
        # the late half of its contiguous chunk for the mirror device's.
        # Model code should instead permute hidden states once outside
        # the layer stack and call with pre_permuted=True
        perm, inv = zigzag_permutation(seq, cp)
        q, k, v = (x.take(perm, axis=1) for x in (q, k, v))

    spec = P(None, axis_name, None, None)
    shard_mesh, manual_axes = mesh, {axis_name}
    if impl != "jnp":
        # the Pallas executors hold Mosaic calls, which the partitioner
        # refuses anywhere but a region that leaves it no axis: batch and
        # heads split over the data and tp axes instead of staying auto
        shard_mesh, manual_axes, spec = manual_attention_region(
            mesh, q.shape, k.shape[2], seq_axis=axis_name
        )
    else:
        # nested-manual support (attention inside the pp-manual pipeline
        # executors): the inner shard_map is built on the CURRENT abstract
        # mesh and lists the union of the already-manual axes and ours
        abs_mesh, already_manual = ambient_manual_axes()
        if already_manual:
            shard_mesh = abs_mesh
            manual_axes = already_manual | {axis_name}

    out = jax.shard_map(
        lambda q, k, v: fn(q, k, v),
        mesh=shard_mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=manual_axes,
        check_vma=False,
    )(q, k, v)
    if inv is not None:
        out = out.take(inv, axis=1)
    return out
