"""How serving weights rest on the device.

A fused ``gate_up`` leaf is ``(..., in, 2, out)``: its second-minor axis has
extent 2, narrower than the TPU's (8, 128) tile, so in the default layout the
compiler tiles the parameter ``T(2,128)`` while the matmul that reads it
(``"ech,ehti->ecti"`` / ``"bsh,hti->bsti"``) wants ``(in, out)`` as the two
minor axes in ``T(8,128)``. Every program then re-tiles one layer's weight —
read, write, and a third read by the dot — before each layer's matmul: at
Mixtral's widths 1.88 GB a layer, the largest device op of both serving cells
(PERF.md §5, PR 24). :func:`rest_fused_weights` places such leaves once, with
the size-2 axis physically ahead of the contraction axis (the order the dot
converts *to* today), so the slice of the layer scan fuses into the dot and
the copy is gone. Only the physical layout changes: logical shape, key, dtype
and sharding stay, so checkpoints, ``to_hf``, LoRA, quantization and the
training model see the array they saw. jit adopts a committed argument's
layout when its own ``in_shardings`` pins none, so no program is told; a
program lowered from abstract arguments has to be given the leaf's
``format`` (``InferenceEngine._abstract``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.quantization.quantize import (
    fused_reduce_axes,
    walk_tree,
)
from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP

# second-minor extent of the TPU's (8, 128) tile: an axis narrower than this
# in that position is what makes the default layout one no matmul reads.
# Other backends take the same placement (a CPU accepts the Format and jit
# adopts it), so tier-1 runs the path the chip runs.
SUBLANES = 8


def fused_rest_layout(path: str, leaf: Any) -> Optional[Layout]:
    """The layout a float fused leaf ``(..., in, t, out)`` with ``t`` narrower
    than a tile should rest in — ``t`` ahead of ``in`` — or None for every
    other leaf. Reads the leaf's path, shape and dtype only, so an abstract
    leaf answers as the array would."""
    ndim = len(getattr(leaf, "shape", ()))
    if fused_reduce_axes(path, ndim) is None:
        return None
    if not jnp.issubdtype(leaf.dtype, jnp.floating) or leaf.shape[-2] >= SUBLANES:
        return None
    return Layout(major_to_minor=(*range(ndim - 3), ndim - 2, ndim - 3, ndim - 1))


def rest_fused(a):
    return a


def _place(leaf: jax.Array, layout: Layout) -> jax.Array:
    """``leaf`` copied into ``layout``, committed to its sharding. The copy is
    a jitted identity with the layout on its output, compiled afresh and kept
    out of the persistent compile cache: an executable loaded back from that
    cache (jax 0.9.0, TPU and CPU alike) has lost its output layout, and the
    array it returns is labelled default while its bytes are not — permuted
    weights, silently (chip run, PR 24; ``jax.device_put(x, Format)`` goes
    through the cache). Programs that *read* a placed argument come back from
    the cache intact. Nothing below the threshold is written, and an entry
    under this module's name (``jit_rest_fused``) is written nowhere else."""
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        placed = jax.jit(rest_fused, out_shardings=Format(layout, leaf.sharding))(leaf)
        return jax.block_until_ready(placed)
    finally:
        jax.config.update(name, before)


def rest_fused_weights(params: Any) -> Tuple[Any, Dict[str, int]]:
    """``params`` with every fused leaf re-placed (see the module docstring),
    and what that took: ``{"leaves": n, "bytes": b}``. One leaf at a time,
    each finished before the next starts, so the transient is one leaf; the
    caller's tree still holds the old buffers until the caller drops it (the
    engine never deletes an array it was handed). Placing a leaf commits it
    to its sharding — jit adopts no layout from an uncommitted argument — so
    an uncommitted leaf under a mesh of several devices, which jit would
    spread itself, is left as it is. Quantized payloads are not
    arrays here (``walk_tree`` stops at a ``QuantizedTensor``) and stay where
    quantization put them: their scales follow the logical axes and they
    dequantize in-program. A leaf already in its rest layout is passed
    through, so a second engine over ``engine.params`` copies nothing. The
    relayout compiles on every start (``_place``): the ``setup.placement``
    span of the process's set-up record says what that costs."""
    placed = {"leaves": 0, "bytes": 0}
    on_a_mesh = (
        parallel_state.model_parallel_is_initialized()
        and parallel_state.get_parallel_state().mesh.size > 1
    )

    def visit(path, leaf):
        if not isinstance(leaf, jax.Array) or (on_a_mesh and not leaf.committed):
            return leaf
        layout = fused_rest_layout(path, leaf)
        if layout is None:
            return leaf
        now = getattr(leaf.format.layout, "major_to_minor", None)
        if now == layout.major_to_minor:
            return leaf
        placed["leaves"] += 1
        placed["bytes"] += int(leaf.nbytes)
        return _place(leaf, layout)

    with SETUP.span("setup.placement"):
        return walk_tree(params, visit), placed


def committed_home(params: Any) -> Optional[jax.sharding.Sharding]:
    """The single device the weights are committed to, as a sharding; None
    where nothing is committed or the weights span devices (a mesh has its
    own rule, ``shard_pytree``). State that a program replaces with its own
    output — a cache, a resident array — is born committed here too: a
    program's outputs are committed as soon as one input is, and jit lowers
    and compiles again for the committed successor of an uncommitted
    argument (graftcheck GC008)."""
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array) and leaf.committed:
            return leaf.sharding if len(leaf.sharding.device_set) == 1 else None
    return None
