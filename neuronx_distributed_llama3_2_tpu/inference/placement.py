"""How serving weights rest on the device.

Two kinds of leaf rest in a layout of their own, because in the default one
every program re-lays one layer's weight before each layer's matmul:

=====================================  ==================  ====================
leaf (by its path, rank and dtype)     logical shape       rests, major → minor
=====================================  ==================  ====================
``…/gate_up`` (dense MLP, shared)      ``(…, in, 2, out)``  ``(…, 2, in, out)``
``…/experts/gate_up``                  ``(…, in, 2, out)``  ``(…, 2, in, out)``
``attn/qkv/{q,k,v}_kernel``            ``(L, in, out)``     ``(L, out, in)``
``attn/{q,q_b,kv_a}/kernel`` (MLA)     ``(L, in, out)``     ``(L, out, in)``
``attn/kv_b/kernel`` (MLA)             ``(L, r, n, d)``     ``(L, n, r, d)``
=====================================  ==================  ====================

**Fused ``gate_up``** (:func:`fused_rest_layout`, PR 24). The leaf is
``(..., in, 2, out)``: its second-minor axis has extent 2, narrower than the
TPU's (8, 128) tile, so in the default layout the compiler tiles the
parameter ``T(2,128)`` while the matmul that reads it (``"ech,ehti->ecti"`` /
``"bsh,hti->bsti"``) wants ``(in, out)`` as the two minor axes in
``T(8,128)``. Every program then re-tiles one layer's weight — read, write,
and a third read by the dot — before each layer's matmul: at Mixtral's widths
1.88 GB a layer, the largest device op of both serving cells (PERF.md §5,
PR 24). Resting with the size-2 axis physically ahead of the contraction axis
(the order the dot converts *to*), the slice of the layer scan fuses into the
dot and the copy is gone.

**A stacked attention projection whose output is split into heads**
(:func:`head_split_rest_layout`, PR 51). The compiler folds the head split of
``q = (x @ kernel).reshape(b, t, n, d)`` into the dot and then reads the
kernel with its *contraction* axis minor — ``bf16[1,4096,4096]{1,2,0}`` —
while the stacked leaf ``(L, in, out)`` rests ``{2,1,0}``; so in every layer
of every call it slices the layer out, transposes it (``copy … {1,2,0}``) and
only then multiplies (sarvam: a 100-MB ``q`` kernel a layer, 10 % of the
cell's busy time; PERF.md §5, PR 51). Resting ``(L, out, in)`` the transpose
is gone. MLA's ``kv_b`` ``(L, r, n, d)`` is read two ways — ``pdecode``
absorbs it a head at a time, a prefill chunk expands latents through it — and
rests as the decode step, which runs every step, reads it: ``(L, n, r, d)``.
The compiler alone would pick ``(L, n, d, r)`` for a prefill chunk, but reads
the decode step's layout there without a copy.

Both rules are the compiler's own choice: lowered with ``Layout.AUTO`` on the
weights, ``pdecode`` and a 512-row ``psfx`` of every served family report
these layouts in ``input_formats`` (``tests/test_weight_placement.py`` holds
the rules to that). Leaves of kilobytes a layer that the compiler would also
transpose (``router/kernel``, ``out_gate``, ``gate``, ``phi``) stay default:
a program prefetches them whole and copies none. MiniCPM-SALA's two
full-width output gates (``attn/gate/kernel``, 33 MB a layer) stay default
for another reason: their output is multiplied in whole, not split into
heads, and the compiler reads them ``(L, in, out)`` as stored (the same
test, ``sala`` among its stacks); that family's q / k / v kernels are
``attn/qkv/*_kernel`` and take the rule above.

:func:`rest_weights` places the table's leaves once, at construction. Only
the physical layout changes: logical shape, key, dtype and sharding stay, so
checkpoints, ``to_hf``, LoRA, quantization and the training model see the
array they saw. jit adopts a committed argument's layout when its own
``in_shardings`` pins none, so no program is told; a program lowered from
abstract arguments has to be given the leaf's ``format``
(``InferenceEngine._abstract``).
"""

from __future__ import annotations

import re
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

# a layer stack's attention projections whose output the program splits into
# heads, as (rank, rest order, path): ``(L, in, out)`` under ``qkv/`` (every
# multi-head family) or one of MLA's (``q`` where the query has no latent,
# ``q_b`` where it has; ``kv_a``, whose output is split into the latent and
# the rotary key), and MLA's ``kv_b`` ``(L, r, n, d)``, stored with its heads
# already apart
_HEAD_SPLIT = (
    (3, (0, 2, 1), re.compile(r"(?:^|/)(?:attn|attention)/(?:qkv/[qkv]_kernel|(?:q|q_b|kv_a)/kernel)$")),
    (4, (0, 2, 1, 3), re.compile(r"(?:^|/)(?:attn|attention)/kv_b/kernel$")),
)


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


def head_split_rest_layout(path: str, leaf: Any) -> Optional[Layout]:
    """The layout a float stacked attention projection whose output is split
    into heads should rest in — the contraction axis minor, ``(L, out, in)``;
    ``kv_b`` ``(L, r, n, d)`` as the decode step reads it, ``(L, n, r, d)`` —
    or None for every other leaf. Reads path, rank and dtype only."""
    ndim = len(getattr(leaf, "shape", ()))
    for rank, order, names in _HEAD_SPLIT:
        if ndim == rank and names.search(path) and jnp.issubdtype(leaf.dtype, jnp.floating):
            return Layout(major_to_minor=order)
    return None


def rest_layout(path: str, leaf: Any) -> Optional[Layout]:
    """The layout ``leaf`` should rest in where that is not the default one
    (the module docstring's table), else None."""
    return fused_rest_layout(path, leaf) or head_split_rest_layout(path, leaf)


def rest_leaf(a):
    return a


def _relayout(leaf: jax.Array, layout: Layout):
    """The compiled copy of an array like ``leaf`` into ``layout``, committed
    to its sharding: a jitted identity with the layout on its output, compiled
    afresh and kept out of the persistent compile cache. An executable loaded
    back from that cache (jax 0.9.0, TPU and CPU alike) has lost its output
    layout, and the array it returns is labelled default while its bytes are
    not — permuted weights, silently (chip run, PR 24; ``jax.device_put(x,
    Format)`` goes through the cache). Programs that *read* a placed argument
    come back from the cache intact. Nothing below the threshold is written,
    and an entry under this module's name (``jit_rest_leaf``) is written
    nowhere else."""
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        out = Format(layout, leaf.sharding)
        return jax.jit(rest_leaf, out_shardings=out).lower(leaf).compile()
    finally:
        jax.config.update(name, before)


def rest_weights(params: Any) -> Tuple[Any, Dict[str, int]]:
    """``params`` with every leaf :func:`rest_layout` names re-placed (see the
    module docstring), and what that took: ``{"leaves": n, "bytes": b}``. One
    leaf at a time, each finished before the next starts, so the transient is
    one leaf; the caller's tree still holds the old buffers until the caller
    drops it (the engine never deletes an array it was handed). Placing a leaf
    commits it to its sharding — jit adopts no layout from an uncommitted
    argument — so an uncommitted leaf under a mesh of several devices, which
    jit would spread itself, is left as it is. Quantized payloads are not
    arrays here (``walk_tree`` stops at a ``QuantizedTensor``) and stay where
    quantization put them: their scales follow the logical axes and they
    dequantize in-program. A leaf already in its rest layout is passed
    through, so a second engine over ``engine.params`` copies nothing. The
    relayout compiles on every start (``_relayout``), once for all leaves of
    one shape, dtype, sharding and pair of layouts (a stack's ``k`` and ``v``
    kernels, the same projection in two layer groups): the
    ``setup.placement`` span of the process's set-up record says what that
    costs."""
    placed = {"leaves": 0, "bytes": 0}
    on_a_mesh = (
        parallel_state.model_parallel_is_initialized()
        and parallel_state.get_parallel_state().mesh.size > 1
    )
    compiled: Dict[Tuple, Any] = {}

    def visit(path, leaf):
        if not isinstance(leaf, jax.Array) or (on_a_mesh and not leaf.committed):
            return leaf
        layout = rest_layout(path, leaf)
        if layout is None:
            return leaf
        now = getattr(leaf.format.layout, "major_to_minor", None)
        if now == layout.major_to_minor:
            return leaf
        placed["leaves"] += 1
        placed["bytes"] += int(leaf.nbytes)
        key = (leaf.shape, leaf.dtype, leaf.sharding, now, layout.major_to_minor)
        if key not in compiled:
            compiled[key] = _relayout(leaf, layout)
        return jax.block_until_ready(compiled[key](leaf))

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
