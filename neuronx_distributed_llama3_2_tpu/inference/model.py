"""KV-cache decoder model.

TPU-native replacement for the reference's inference decoder
(``examples/inference/modules/model_base.py``): ``NeuronBaseModel`` keeps the
KV cache as per-layer ``nn.ParameterList`` state inside the traced NEFF
(:52,:114-125), distinguishes context-encoding vs token-gen vs speculation by
input length (:334,:348-352), scatters new K/V by position_ids or — under
continuous batching — by seq_ids (:389-419), and gathers the last token before
the LM head (:444-452).

The TPU-first redesign collapses those three forward modes into ONE function::

    forward(params, cache, tokens (b, T), positions (b,), slots (b,))

- context-encode  = T == bucket,  positions == 0
- token-gen       = T == 1
- speculation     = T == gamma+1 (draft-verify block)

because with scatter-writes into the cache and the mask ``j <= position + t``,
block-causal decode *is* prefill when position == 0. Each static T compiles to
its own XLA program sharing the same weight arrays — the reference needs a
multi-model ModelBuilder (trace/model_builder.py:82) + shape router
(trace/spmd.py:152) to get the same effect; here it is just multiple jit
specializations of one function.

The cache is a donated pytree of global arrays sharded over the mesh
(kv-head dim over tp) — the reference's ``StateInitializer`` per-rank state
alloc (trace/spmd.py:63) dissolves into PartitionSpecs.

``slots`` is the reference's continuous-batching ``seq_ids`` scatter
(model_base.py:394-401): requests live in cache rows ("slots") and a batch of
b <= B active requests addresses its rows explicitly.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    _head_axis,
    apply_rope,
    make_norm,
)
from neuronx_distributed_llama3_2_tpu.moe import tap as routing_tap
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    BATCH_AXES,
    constrain,
)

Params = Dict[str, Any]


class KVCache(NamedTuple):
    """Stacked-layer KV cache: k/v (L, B, S_max, n_kv, head_dim). The whole
    stack is the carry of ``forward``'s layer loop, written and read at
    ``[layer, slot, row]``."""

    k: jax.Array
    v: jax.Array

    @property
    def max_batch(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


class PagedKVCache(NamedTuple):
    """Block-pooled KV cache: k/v (L, num_blocks, block_size, n_kv, head_dim).

    The dense cache reserves a full ``max_seq_len`` row per slot; here
    sequence rows live in fixed-size blocks drawn from one global pool
    (vLLM PagedAttention, Kwon et al. SOSP 2023) and a per-request *block
    table* maps logical block index -> pool block id. Block 0 is reserved
    as the null block: block-table entries past a request's allocated
    frontier point at it, so bucket-padding writes land in garbage rows
    that no masked read ever sees.

    ``forward``'s layer loop carries the whole pool and folds the layer into
    the row index (layer ``l``'s rows start at ``l · num_blocks ·
    block_size`` of the pool seen as one run of rows), so a donated pool is
    updated in place: a call moves the rows it writes and the rows it
    attends over, never a layer of the pool.

    Quantized mode (``PagedConfig.kv_cache_dtype`` int8/fp8): ``k``/``v``
    hold the low-bit payloads and ``k_scale``/``v_scale`` carry the
    per-(token row, kv head) absmax scales in block-granular arrays
    ``(L, num_blocks, block_size, n_kv)`` — a block copy (COW) copies its
    scale tile, a frontier overwrite replaces payload and scale together
    (:mod:`..quantization.kv_cache`). ``None`` scales (the default) are the
    fp pool: the pytree then flattens to exactly the pre-quantization
    ``(k, v)`` pair, so every fp trace and donation pattern is unchanged.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


class MixedKVCache(NamedTuple):
    """The paged cache of a stack whose layers differ in what they keep
    (:class:`LagunaDecode`): two :class:`PagedKVCache`s side by side, each
    over the layers of its kind and each with a table of its own. ``full``
    (L_f, num_blocks, block_size, n_kv, d) is the block pool every other
    family has — a request's blocks come from the allocator and cover its
    whole context. ``window`` (L_w, window_blocks, block_size, n_kv, d) holds
    the window layers' rows: a lane's *ring* of blocks, in which the row of
    position ``p`` is block ``(p // block_size) mod ring blocks`` of the
    lane's table, row ``p mod block_size`` — a position's row is overwritten
    by the position one ring later, which no live query can still see. Block
    0 of each is its null block. Both honour ``cache_dtype`` and
    ``kv_cache_dtype`` (scale tiles included)."""

    full: PagedKVCache
    window: PagedKVCache

    @property
    def num_blocks(self) -> int:
        return self.full.num_blocks

    @property
    def block_size(self) -> int:
        return self.full.block_size

    @property
    def quantized(self) -> bool:
        return self.full.quantized


class CacheKind(NamedTuple):
    """One kind of cache a decode model's layers keep: ``name`` (the field of
    the paged cache that holds it, where the cache has fields by kind),
    ``layers`` of the stack that keep it, and ``rows`` a query of such a layer
    can see — ``None``: every row of the context, held in the allocator's
    blocks; a count: the last so many, held in a ring of blocks a lane; 0 with
    ``state`` set: none — what such a layer keeps of the past is one
    fixed-size state, held in one slot a lane. A kind with a row count is laid
    out a lane by the serving engine beside the allocator's pool, sized
    ``<name>_blocks`` at :meth:`LlamaDecode.init_paged_cache` and addressed
    through ``<name>_tables`` at ``forward``."""

    name: str
    layers: int
    rows: Optional[int]
    state: bool = False


class LatentCache(NamedTuple):
    """Latent-attention cache (MLA, models/sarvam.py): one array of rows
    ``[c ‖ k_r]`` — the normed latent and the rotated shared rotary key — and
    nothing by head. Dense: ``kv`` (L, B, S_max, W); paged: (L, num_blocks,
    block_size, W), block 0 the null block, with the layer folded into the
    row index exactly as :class:`PagedKVCache`'s. ``W`` is the row's width
    rounded up to whole 128-lanes (``SarvamDecode.pool_row_width``): the
    values sit first, zeros after. There is no quantized form."""

    kv: jax.Array

    @property
    def max_batch(self) -> int:
        return self.kv.shape[1]

    @property
    def max_len(self) -> int:
        return self.kv.shape[2]

    num_blocks = max_batch
    block_size = max_len
    quantized = False


def cache_row_bytes(cache: Any) -> int:
    """Bytes a token leaves in ``cache`` a layer *as the device lays the
    arrays out*: the argument bytes of a compiled program that takes the
    cache — tile padding included: on a TPU a bf16 array's two minor axes
    are tiled (16, 128), so a 576-wide row occupies 640 — over its
    (layer, block or slot, row) positions. Compiles an identity; for a
    traced engine's ``setup`` record."""
    positions = getattr(cache, "positions", None) or math.prod(jax.tree.leaves(cache)[0].shape[:3])
    return _laid_out_bytes(cache) // positions


def _laid_out_bytes(cache: Any) -> int:
    """Argument bytes of a compiled identity over ``cache``."""
    compiled = jax.jit(lambda c: c).lower(cache).compile()
    return compiled.memory_analysis().argument_size_in_bytes


class StateCache(NamedTuple):
    """Retention cache (:mod:`..models.brumby`): what a sequence leaves
    behind is one fixed-size state a layer — ``s`` (L, N, kv heads, φ, head)
    and the normaliser ``z`` (L, N, kv heads, φ), float32 — and no row per
    token. Dense: ``N`` slots; paged: ``N`` blocks of the pool, **one block one
    whole state**, block 0 the null state that idle lanes and lanes
    mid-prefill read and write. A block's size in tokens is not a dimension:
    a lane's state is the block its table's first entry names. There is no
    quantized form."""

    s: jax.Array
    z: jax.Array

    @property
    def max_batch(self) -> int:
        return self.s.shape[1]

    num_blocks = max_batch
    quantized = False


class SsmState(NamedTuple):
    """What the state-space layers of a stack keep (:mod:`..models.jamba`):
    ``h`` (L, slots, N, D) — float32 unless asked otherwise, the wide axis
    minor so that it tiles without padding — and the convolution's tail
    ``tail`` (L, slots, 16, width): a slot's (K − 1) · D values folded into
    16 rows of whole 128-lanes (:func:`tail_width`; the places past the
    values unused), so that a slot is whole tiles. With the slot axis among
    the two minor ones — as a plain (L, slots, (K − 1) · D) has it, and as
    the compiler lays out any array whose own two minor axes would pad —
    seeing every layer's slots as one run is a copy of the pool, twice a
    layer a call (PERF.md §6, PR 49). Slot 0 is
    the null slot that idle lanes, lanes mid-prefill beside the decode batch
    and warm-up calls read and write."""

    h: jax.Array
    tail: jax.Array

    quantized = False


TAIL_ROWS = 16      # rows of a bfloat16 tile: a slot's tail is this many rows of whole lanes


def tail_width(values: int) -> int:
    """Width of the :data:`TAIL_ROWS` rows a slot's ``values`` tail values
    are folded into, in whole 128-lanes: 15,360 values lie in (16, 1024),
    the last 1,024 places unused — the room a tile's padding would take."""
    return 128 * -(-values // (TAIL_ROWS * 128))


class HybridCache(NamedTuple):
    """The cache of a stack that keeps **rows a token** in some layers and **a
    state a lane** in the others (:class:`JambaDecode`): ``rows`` is the
    attention layers' block pool — k / v (L_a, num_blocks, block_size,
    NKV · D), a row's heads side by side (one kv head of 128 would otherwise
    tile (16, 128) for 1 row in 16), block 0 the null block, or dense
    (L_a, B, S_max, NKV · D) — and ``state`` the state-space layers'
    :class:`SsmState`. A request's blocks come from the allocator; its slot is
    its lane's. There is no quantized form."""

    rows: PagedKVCache
    state: SsmState

    @property
    def num_blocks(self) -> int:
        return self.rows.num_blocks

    @property
    def block_size(self) -> int:
        return self.rows.block_size

    quantized = False


class SparseRows(NamedTuple):
    """The ``rows`` kind of a stack whose attention layers choose the blocks
    they read (:class:`SalaDecode`): k / v (L_s, num_blocks, NKV, block_size,
    D), block 0 the null block — **a block's rows of one kv head lie
    together**, because a kv group chooses its own blocks and reads its own
    head of them (side by side in a row, as :class:`HybridCache`'s other rows
    are, a group's gather moves every head's bytes) — and a third leaf beside
    them, the **pooled index keys** ``pooled`` (L_s, R, NKV · D): one row a
    kernel, kernel ``j`` of the sequence — the mean of its rows ``stride · j
    ..`` — in row ``block · (block_size / stride) + j mod (block_size /
    stride)`` of the pool block that holds its first row, so the block table
    that names a row's block names its kernels' too. ``R`` is the blocks' rows
    rounded up to whole (16, 128) tiles, so every layer's kernels are one run
    of rows without a copy."""

    k: jax.Array
    v: jax.Array
    pooled: jax.Array

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def positions(self) -> int:
        """(layer, block, row) positions: what :func:`cache_row_bytes` divides by."""
        return self.k.shape[0] * self.k.shape[1] * self.k.shape[3]

    quantized = False


class MatrixState(NamedTuple):
    """What the Lightning layers of a stack keep (:mod:`..models.minicpm_sala`):
    ``s`` (L_l, slots, heads, D, D), a decayed sum of ``kᵀ v`` a head, float32
    unless asked otherwise. Slot 0 is the null slot (see :class:`SsmState`)."""

    s: jax.Array

    quantized = False


def cache_block_bytes(cache: Any) -> int:
    """Bytes one block (or slot) of ``cache`` holds over all layers and all of
    its arrays, as the device lays them out (see :func:`cache_row_bytes`)."""
    return _laid_out_bytes(cache) // jax.tree.leaves(cache)[0].shape[1]


@dataclasses.dataclass(frozen=True)
class LlamaDecode:
    """Decode-mode Llama sharing the training model's parameter pytree.

    Construction mirrors the reference's DecoderModelInstance (the same
    checkpoint drives both the training and the inference model,
    model_wrapper.py:303); here they are literally the same arrays.
    """

    config: LlamaConfig

    # trace layout depends on global parallel state (shardlint SL002); valid
    # across re-init only because initialize/destroy_model_parallel clear
    # the jit cache (parallel/state.py)
    __layout_deps__ = (
        "model_parallel_is_initialized", "get_parallel_state",
        "get_tensor_model_parallel_size", "mesh_is_tp_only",
    )

    # What the cache holds is rows by position: a padded or stale row is
    # masked out by position, a prefix of a sequence's rows is a prefix of its
    # cache, a row written past the frontier is overwritten before it is read.
    # The serving engine's prefix sharing, speculation, fused step and spill
    # rest on this; a model whose cache is a state (:class:`RetentionDecode`)
    # says False and the engine turns them off (docs/serving.md "Models whose
    # cache is a state").
    cache_is_positional = True

    @property
    def keeps_state(self) -> bool:
        """Whether what some layer keeps of the past is a state: the whole
        cache (``cache_is_positional`` False) or one kind of it
        (:class:`CacheKind` ``state``). Everything that rests on rows being
        masked, overwritten or shared by position is then off."""
        return not self.cache_is_positional or any(kind.state for kind in self.cache_kinds)

    def uses_state_kernel(self) -> bool:
        """Whether a decode program of this model holds the one-pass state
        kernel (:class:`RetentionDecode`); the engine counts such dispatches."""
        return False

    def selected_rows(self, context: int) -> Optional[Tuple[int, int]]:
        """Where a layer reads only the blocks it chooses (:class:`SalaDecode`):
        (rows a decode step's query at the last of ``context`` rows reads a
        layer, blocks among them it did not choose by score), else None."""
        return None

    def chunk_read(self) -> Optional[str]:
        """Where a layer's read for a block of rows (``pctx`` / ``psfx``) walks
        the context in tiles under a block mask (:class:`SalaDecode`):
        ``"kernel"`` or ``"tiles"``; else None. The traced engine's ``setup``
        record says it beside ``decode_read``."""
        return None

    def chunk_tiles(self, t: int, start: int, limit: Optional[int]) -> Optional[Tuple[bool, int, int]]:
        """Where :meth:`chunk_read` is not None, what a block of ``t`` rows
        from position ``start`` over the first ``limit`` rows (None: its own
        rows, ``pctx``) costs a layer: (whether the program holds the kernel,
        the kv tiles at or before the block's last position, the rung's)."""
        return None

    def residual_row_bytes(self) -> Optional[int]:
        """Bytes a token's state takes between layers where the layer loop
        carries more than one (b, t, H) array (:class:`XingDecode`), else
        None; the traced engine's ``setup`` record says it."""
        return None

    def decode_read(self, kind: CacheKind, quantized: bool = False) -> str:
        """How a decode step (one fresh row a lane) reads ``kind``'s rows:
        ``"kernel"`` — a Pallas call reads the pool where it lies — or
        ``"gather"`` — rows gathered through the table, then attended. The
        traced engine's ``setup`` record says it a kind. What the program can
        see decides, no option: where :meth:`_walks`, the block walk; else
        what ``use_paged_kernel`` asked for (:meth:`paged_dispatch_path`)."""
        return "kernel" if self._walks(quantized) else self.paged_dispatch_path(1)

    def _walks(self, quantized: bool) -> bool:
        """Whether one fresh row a lane of a ``(k, v)`` pool is attended by
        :func:`..kernels.paged_attention_pallas.paged_decode_walk` over the
        blocks that hold the rows the lane sees — all its live blocks, or a
        window's of its ring: an unquantized pool whose rows the walk takes
        (``walk_fits``), where :func:`_kernels_on_one_device`. A block of
        several rows (``psfx``, a verify block, a tree), an int8 / fp8 pool, a
        mesh and the ``"reference"`` mode keep the gather, the walk's plain
        twin."""
        if quantized or not _kernels_on_one_device():
            return False
        from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
            walk_fits,
        )

        return walk_fits(self.config.head_dim)

    def _model(self) -> LlamaForCausalLM:
        return LlamaForCausalLM(self.config)

    def _rope_tables(self, max_len: int):
        """Rotary tables sized for the cache — delegated to the training
        model's ``_rope`` hook so per-family rope semantics (partial rotary,
        scaling) have exactly one source (llama.py:631, gptneox.py _rope)."""
        return self._model()._rope(max_len)

    # -- cache ------------------------------------------------------------

    def init_cache(
        self, max_batch: int, max_len: int, dtype: Any = None
    ) -> KVCache:
        c = self.config
        dtype = dtype or c.dtype
        shape = (c.num_layers, max_batch, max_len, c.num_kv_heads, c.head_dim)
        return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype: Any = None,
        kv_cache_dtype: Optional[str] = None,
    ) -> PagedKVCache:
        """Block-pool cache for the paged serving path (``serving/``):
        capacity is ``num_blocks * block_size`` token rows shared by every
        request, instead of ``max_batch * max_seq_len`` dense rows.

        ``kv_cache_dtype`` int8/fp8 allocates the low-bit payload pools plus
        the per-(row, head) scale arrays (docs/serving.md "Quantized KV
        pool"); ``None``/"bf16" is the fp pool at ``dtype or config.dtype``
        with no scales — byte-identical to the pre-quantization cache.
        """
        c = self.config
        shape = (c.num_layers, num_blocks, block_size, c.num_kv_heads, c.head_dim)
        if kv_cache_dtype in (None, "bf16"):
            dtype = dtype or c.dtype
            return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
        from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (
            KV_SCALE_DTYPE,
            kv_cache_jax_dtype,
        )

        if dtype is not None:
            raise ValueError(
                "cache dtype override and quantized kv_cache_dtype are "
                "mutually exclusive — the storage dtype IS the quantization"
            )
        qdt = kv_cache_jax_dtype(kv_cache_dtype)
        sshape = shape[:-1]
        return PagedKVCache(
            k=jnp.zeros(shape, qdt), v=jnp.zeros(shape, qdt),
            k_scale=jnp.zeros(sshape, KV_SCALE_DTYPE),
            v_scale=jnp.zeros(sshape, KV_SCALE_DTYPE),
        )

    def cache_row_dims(self) -> Tuple[int, int, int]:
        """(arrays, heads, width) of what a token leaves in the cache, a
        layer — here k and v by kv head. The serving layer's byte arithmetic
        (pool size, a block's bytes, a program's cost) reads this and nothing
        else of the cache's shape."""
        return 2, self.config.num_kv_heads, self.config.head_dim

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        """What the serving layer cannot derive from :meth:`cache_row_dims`:
        the kinds of cache the layers keep, each with its layer count and the
        rows a query sees (``None`` = the whole context). One kind wherever
        every layer keeps the same thing. A kind with a row count keeps only
        a ring of rows a lane, so a prefix of the pool's blocks is not a
        prefix's cache (docs/serving.md "Stacks whose layers cache different
        things")."""
        return (CacheKind("rows", self.config.num_layers, None),)

    def paged_cache_specs(self, quantized: bool = False) -> PagedKVCache:
        """Paged-pool sharding: kv heads over tp (same GQA rule as the dense
        cache); the pool dim is not sharded — any block must be writable by
        any request regardless of which dp rank admitted it. Scale arrays
        (``quantized=True``) shard their kv-head axis with the *same* rule,
        so a rank's scale slice always matches its payload slice and dequant
        needs no collective."""
        ha = _head_axis(self.config.num_kv_heads)
        # no trailing None: GSPMD normalizes specs by dropping trailing
        # unsharded axes, so program *outputs* come back as
        # P(None, None, None, ha). Declaring the canonical form here keeps
        # the constructed pool and every program output on ONE sharding —
        # otherwise each program re-lowers on its second dispatch under a
        # tp mesh (caught by graftcheck GC008's trace-cache probe)
        spec = P(None, None, None, ha)
        if not quantized:
            return PagedKVCache(k=spec, v=spec)
        sspec = P(None, None, None, ha)
        return PagedKVCache(k=spec, v=spec, k_scale=sspec, v_scale=sspec)

    def cache_specs(self, max_batch: Optional[int] = None) -> KVCache:
        """Cache sharding: batch over dp axes, kv heads over tp when
        divisible (the decode analogue of the training GQA sharding rule,
        parallel/layers.py GQAQKVColumnParallelLinear). Pass ``max_batch`` to
        drop batch sharding when it doesn't divide the dp size (serving
        batches are small; replication is the correct fallback)."""
        from neuronx_distributed_llama3_2_tpu.parallel import (
            state as parallel_state,
        )

        ha = _head_axis(self.config.num_kv_heads)
        batch_axes: Any = BATCH_AXES
        if max_batch is not None and parallel_state.model_parallel_is_initialized():
            dp_total = parallel_state.get_parallel_state().data_parallel_size
            if max_batch % dp_total != 0:
                batch_axes = None
        spec = P(None, batch_axes, None, ha, None)
        return KVCache(k=spec, v=spec)

    # -- forward ----------------------------------------------------------

    def forward(
        self,
        params: Params,
        cache: KVCache,
        tokens: jax.Array,      # (b, T) int32
        positions: jax.Array,   # (b,)  int32 — absolute start position
        slots: Optional[jax.Array] = None,  # (b,) int32 cache rows; None = arange
        *,
        context_encode: bool = False,
        return_hidden: bool = False,
        tree: Optional[Tuple[jax.Array, jax.Array]] = None,
        kv_limit: Optional[int] = None,
        block_tables: Optional[jax.Array] = None,  # (b, W) int32 pool block ids
        row_live: Optional[jax.Array] = None,      # (b,) int32 live fresh rows
    ) -> Tuple[jax.Array, KVCache]:
        """Block-causal forward over the cache.

        Returns (logits (b, T, V), updated cache). ``context_encode=True``
        asserts positions == 0 and computes attention only over the fresh
        block (bucket-causal, no cache read) — the fast prefill path; the
        general path attends over the whole cache with the mask
        ``j <= position + t``.

        ``kv_limit`` (static) bounds the cache rows read by attention to the
        first ``kv_limit`` — the token-gen bucket of the reference's
        autobucketing (:31-56: pick bucket from position), cutting cache
        read traffic from S_max to the bucket while writes still land in the
        full cache. Caller guarantees ``position + T <= kv_limit``.

        ``tree``: Medusa-style tree verification — a pair
        ``(depths (T,) int32, ancestor_mask (T, T) bool)``, or the batched
        per-lane form ``(depths (b, T), ancestor_mask (b, T, T))`` (packed
        draft trees from the serving drafter differ lane to lane). The
        fresh block is a candidate *tree*, not a sequence: token i sits at
        sequence depth ``position + depths[i]`` (rope + causal base) but is
        written at cache row ``position + i``; within the block, query i
        attends key j iff ``ancestor_mask[i, j]`` (its ancestors on the
        tree path), plus the whole committed prefix.

        ``block_tables``: the paged-KV path. ``cache`` must be a
        :class:`PagedKVCache` and row ``i``'s logical position ``p`` lives at
        pool row ``block_tables[i, p // bs] * bs + p % bs``. ``slots`` is
        ignored (the table IS the indirection). ``kv_limit`` bounds the
        *logical* rows gathered for attention, exactly as in the dense path.

        ``row_live`` (paged kernel path only): per-lane count of *live*
        fresh query rows in a mixed-width block — lane ``i``'s rows
        ``>= row_live[i]`` are packing padding whose outputs the caller
        discards, so the kernel stops its per-lane KV walk at
        ``positions[i] + row_live[i] - 1`` instead of the static
        ``positions[i] + t - 1`` frontier. Semantically inert (the
        block-causal mask already governs every live row); ``None`` (the
        default, static) leaves all existing lowerings bitwise unchanged.
        """
        c = self.config
        model = self._model()
        b, t = tokens.shape
        if context_encode and tree is not None:
            raise ValueError(
                "tree verification runs through the cache-attention path; "
                "context_encode=True would silently ignore the ancestor mask"
            )
        if slots is None:
            slots = jnp.arange(b, dtype=jnp.int32)

        if tree is None:
            pos_block = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        else:
            depths = tree[0]
            pos_block = positions[:, None] + (
                depths if depths.ndim == 2 else depths[None, :]
            )
        # a quantized paged pool rides the loop as (payload, scale) pairs, so
        # _decode_layer and the per-family overrides stay signature-stable
        # (they only hand the pairs through to _attend_with_cache, which
        # unpacks)
        quantized = getattr(cache, "k_scale", None) is not None
        if quantized and block_tables is None:
            raise ValueError(
                "quantized KV storage is paged-only — the dense slot cache "
                "has no scale arrays (use block_tables / PagedServingEngine)"
            )

        if block_tables is None:
            rope_len = cache.max_len
        else:
            # paged: logical capacity is the table width (write positions can
            # reach the bucket-padding overflow region past max_seq_len)
            rope_len = block_tables.shape[1] * cache.block_size
        sin, cos = self._rope_tables(rope_len)

        x = model._embed()(params["embed"], tokens)
        x = constrain(x, P(BATCH_AXES, None, None))
        norm = make_norm(c)

        x, new_cache = self._run_layers(
            params, cache, x, sin, cos, pos_block, positions, slots,
            context_encode=context_encode, tree=tree, kv_limit=kv_limit,
            block_tables=block_tables, row_live=row_live,
        )
        x = norm(params["final_norm"], x)
        if return_hidden:
            return x, new_cache
        logits = model._logits(params, x)
        return logits, new_cache

    def _run_layers(
        self, params, cache, x, sin, cos, pos_block, positions, slots,
        *, context_encode: bool, tree=None, kv_limit=None, block_tables=None,
        row_live=None,
    ):
        """The layer loop of :meth:`forward`: x (b, T, H) through every
        decoder layer over ``cache``; returns (x, the cache updated)."""
        c = self.config
        # a traced serving engine's routing tap (moe/tap.py), None otherwise:
        # each layer's expert counts leave the body that traced them as the
        # loop's one per-layer output (nothing for a dense model)
        tap = routing_tap.current()

        # the cache is the layer loop's carry, whole, and each layer writes
        # and reads its rows at [layer, ...]: a loop updates its carry in
        # place, so with the cache donated only the written rows move. As
        # the loop's xs and ys — which cannot alias — every call copied the
        # whole cache three times (PERF.md §6, PR 31)
        def layer_body(carry, layer_in):
            x, kc, vc = carry
            lp, layer = layer_in
            x, kc, vc = self._decode_layer(
                lp, x, kc, vc, layer, sin, cos, pos_block, positions, slots,
                context_encode=context_encode, tree=tree, kv_limit=kv_limit,
                block_tables=block_tables, row_live=row_live,
            )
            return (x, kc, vc), (None if tap is None else tap.take_layer())

        quantized = getattr(cache, "k_scale", None) is not None
        if quantized:
            carry: Any = (x, (cache.k, cache.k_scale), (cache.v, cache.v_scale))
        else:
            carry = (x, cache.k, cache.v)
        if c.scan_layers:
            carry, counts = jax.lax.scan(
                layer_body, carry,
                (params["layers"], jnp.arange(c.num_layers, dtype=jnp.int32)),
            )
        else:
            per_layer = []
            for i in range(c.num_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                carry, out = layer_body(carry, (lp, i))
                per_layer.append(out)
            counts = None if per_layer[0] is None else jnp.stack(per_layer)
        x, k_new, v_new = carry
        if tap is not None:
            tap.commit(counts, c.num_layers)

        if quantized:
            return x, type(cache)(
                k=k_new[0], v=v_new[0], k_scale=k_new[1], v_scale=v_new[1]
            )
        return x, type(cache)(k=k_new, v=v_new)

    def _decode_layer(
        self, lp, x, kc, vc, layer, sin, cos, pos_block, positions, slots,
        *, context_encode: bool, tree=None, kv_limit=None, block_tables=None,
        row_live=None,
    ):
        """One decoder layer with cache read/write.

        kc/vc: the whole cache of every layer, (L, B, S_max, NKV, D) — or,
        under ``block_tables``, the (L, num_blocks, block_size, NKV, D) pool —
        and ``layer`` (a traced or a Python int) this layer's index into it;
        x: (b, T, H). Writes fresh K/V at (layer, slots, pos_block) then
        attends, and returns the same arrays updated.
        """
        c = self.config
        from neuronx_distributed_llama3_2_tpu.models.llama import (
            LlamaAttention,
        )

        attn = LlamaAttention(c)
        norm = make_norm(c)
        b, t, _ = x.shape

        h = norm(lp["attn_norm"], x)
        # the training block's scopes (models/llama.py LlamaAttention) plus
        # attn/kv_write and attn/kv_read inside _attend_with_cache
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                q, k, v = attn._qkv()(lp["attn"]["qkv"], h)
                if c.clip_qkv is not None:
                    q = jnp.clip(q, -c.clip_qkv, c.clip_qkv)
                    k = jnp.clip(k, -c.clip_qkv, c.clip_qkv)
                    v = jnp.clip(v, -c.clip_qkv, c.clip_qkv)
                q = q.reshape(b, t, c.num_heads, c.head_dim)
                k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
                v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
            if c.qk_norm:
                q, k = attn._qk_norm(lp["attn"], q, k)
            with jax.named_scope("rope"):
                q = apply_rope(q, sin, cos, pos_block)
                k = apply_rope(k, sin, cos, pos_block)

            att, kc, vc = self._attend_with_cache(
                q, k, v, kc, vc, layer, slots, pos_block, positions,
                context_encode=context_encode, tree=tree, kv_limit=kv_limit,
                block_tables=block_tables, row_live=row_live,
            )
            att = att.reshape(b, t, c.num_heads * c.head_dim)
            with jax.named_scope("o_proj"):
                attn_out = attn._o()(lp["attn"]["o"], att)
        x = x + attn_out
        h = norm(lp["mlp_norm"], x)
        x = x + self._mlp_block(lp, h)
        return x, kc, vc

    def _attend_with_cache(
        self, q, k, v, kc, vc, layer, slots, pos_block, positions,
        *, context_encode: bool, tree=None, kv_limit=None, block_tables=None,
        row_live=None,
    ):
        """Cache write + attention, shared by every decode family (Llama,
        MoE, GPT-NeoX): scatter the fresh roped K/V into layer ``layer`` of
        the cache, then bucket-causal (prefill) or cache attention
        (token-gen). kc/vc are the whole (L, ...) cache; the layer is part
        of the scatter's and the gather's index, never sliced out first — a
        ``kc[layer]`` would copy that layer. Returns (att (b,T,N,D), kc, vc)."""
        c = self.config

        # scatter-write the fresh block into the cache at (slot, position) —
        # the reference's position_ids/seq_ids KV scatter (model_base.py:389-419);
        # writes cast to the cache dtype so cache_dtype survives and donation
        # can reuse the buffers. Tree blocks write at consecutive rows
        # (position + i), decoupled from their rope depth in pos_block.
        t = q.shape[1]
        write_rows = (
            pos_block
            if tree is None
            else positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        )
        if block_tables is not None:
            return self._attend_paged(
                q, k, v, kc, vc, layer, block_tables, write_rows, pos_block,
                positions, context_encode=context_encode, tree=tree,
                kv_limit=kv_limit, row_live=row_live,
            )
        if isinstance(kc, tuple):
            raise ValueError(
                "a quantized (payload, scale) cache reaches the dense "
                "path only on a caller bug — forward() guards block_tables"
            )
        with jax.named_scope("kv_write"):
            kc = kc.at[layer, slots[:, None], write_rows].set(k.astype(kc.dtype))
            vc = vc.at[layer, slots[:, None], write_rows].set(v.astype(vc.dtype))

        ha = _head_axis(c.num_heads)
        if context_encode:
            # bucket-causal over the fresh block only (reference
            # context-encoding path, model_base.py:348-352) — exactly the
            # training model's core attention, shared so the decode model can
            # never diverge numerically from the trained one
            from neuronx_distributed_llama3_2_tpu.models.llama import (
                core_attention,
            )

            with jax.named_scope("sdpa"):
                att = core_attention(q, k, v, causal=True)
        else:
            # attend over the cache rows of the active slots, bounded to the
            # token-gen bucket when given (static bound — reads only
            # kv_limit rows from HBM instead of the whole S_max cache)
            with jax.named_scope("kv_read"):
                k_all = kc[layer, slots, :kv_limit].astype(q.dtype)  # (b,S≤max,NKV,D)
                v_all = vc[layer, slots, :kv_limit].astype(q.dtype)
            with jax.named_scope("sdpa"):
                att = self._cache_attention(
                    q, k_all, v_all, pos_block, ha, positions=positions,
                    tree=tree,
                )
        return att, kc, vc

    def _attend_paged(
        self, q, k, v, kc, vc, layer, block_tables, write_rows, pos_block,
        positions, *, context_encode: bool, tree=None, kv_limit=None,
        row_live=None,
    ):
        """Paged cache write + attention: the block table translates logical
        sequence rows to pool rows for both the fresh-block scatter and the
        attention gather. kc/vc: the whole (L, num_blocks, block_size, NKV, D)
        pool — or, quantized, the ((L, num_blocks, block_size, NKV, D)
        payload, (L, num_blocks, block_size, NKV) scale) pair — seen as one
        run of ``L · num_blocks · block_size`` rows (a reshape of leading,
        unsharded axes), in which layer ``layer``'s rows start at
        ``layer · num_blocks · block_size``. Numerically
        identical to the dense path — the gathered K/V rows carry the same
        values in the same logical order, and garbage rows (stale blocks,
        null-block padding) are removed by the same ``j <= position + t``
        mask. Under quantization every attention consumer — the fresh-block
        prefill softmax included — sees the *round-tripped* (dequantized)
        K/V, so whole-prompt prefill, chunked re-reads from the pool, the
        kernel and both gather fallbacks all agree token-for-token."""
        c = self.config
        quantized = isinstance(kc, tuple)
        ksc = vsc = None
        if quantized:
            kc, ksc = kc
            vc, vsc = vc
        with jax.named_scope("kv_write"):
            nl, nb, bs = kc.shape[:3]

            def rows(a):  # every layer's rows in one run
                return a.reshape((nl * nb * bs,) + a.shape[3:])

            kflat, vflat = rows(kc), rows(vc)
            # logical row p of batch row i -> this layer's pool row
            # table[i, p//bs]*bs + p%bs; rows past the allocated frontier map
            # to the layer's null block (id 0)
            base = layer * (nb * bs)
            wr_phys = (
                base
                + jnp.take_along_axis(block_tables, write_rows // bs, axis=1) * bs
                + write_rows % bs
            )
            if quantized:
                from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (
                    kv_dequantize,
                    kv_quantize,
                )

                # quantize-on-write: payload + per-(row, head) scale land in the
                # same scatter, so frontier overwrites (speculative rollback)
                # replace both and stale rows can never poison a later read
                kq, ks = kv_quantize(k, kflat.dtype)   # (b,t,NKV,D) / (b,t,NKV)
                vq, vs = kv_quantize(v, vflat.dtype)
                ksflat, vsflat = rows(ksc), rows(vsc)
                kflat = kflat.at[wr_phys].set(kq)
                vflat = vflat.at[wr_phys].set(vq)
                ksflat = ksflat.at[wr_phys].set(ks)
                vsflat = vsflat.at[wr_phys].set(vs)
                ksc, vsc = ksflat.reshape(ksc.shape), vsflat.reshape(vsc.shape)
                # the fresh block the prefill softmax consumes is the same
                # round-trip a later chunk will read back from the pool
                k = kv_dequantize(kq, ks, q.dtype)
                v = kv_dequantize(vq, vs, q.dtype)
            else:
                kflat = kflat.at[wr_phys].set(k.astype(kflat.dtype))
                vflat = vflat.at[wr_phys].set(v.astype(vflat.dtype))
            kc, vc = kflat.reshape(kc.shape), vflat.reshape(vc.shape)

        ha = _head_axis(c.num_heads)
        if context_encode:
            from neuronx_distributed_llama3_2_tpu.models.llama import (
                core_attention,
            )

            with jax.named_scope("sdpa"):
                att = core_attention(q, k, v, causal=True)
        else:
            limit = (
                kv_limit if kv_limit is not None
                else block_tables.shape[1] * bs
            )
            if q.shape[1] == 1 and tree is None and self._walks(quantized):
                # one row a lane: the lane's live blocks are read where they
                # lie, nothing gathered — whatever use_paged_kernel says (the
                # static-grid kernel below reads the rung, the walk what is
                # live)
                from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
                    paged_decode_walk,
                )

                with jax.named_scope("sdpa"):
                    att = paged_decode_walk(
                        q[:, 0], kc, vc, block_tables, positions, layer,
                        kv_limit=limit)
                return att[:, None], kc, vc
            if self._paged_kernel_eligible(q.shape[1], tree):
                # gather-free read: the kernel dereferences the block table
                # inside its BlockSpec index maps, so the (b, limit, NKV, D)
                # K/V copy below never materializes (flash-decoding split-K,
                # kernels/paged_attention_pallas). Linear fresh blocks ride
                # the kernel's block-causal mask row <= position + ti (the
                # dense path's j <= position + t, per fresh token); tree
                # blocks hand their ancestor matrix in as per-node int32
                # bitmasks, so every candidate branch shares one KV DMA
                # per block.
                from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
                    paged_flash_decode,
                    paged_flash_decode_tp,
                )
                from neuronx_distributed_llama3_2_tpu.parallel import (
                    state as parallel_state,
                )

                with jax.named_scope("sdpa"):
                    # the kernel sees every layer's blocks as one pool of
                    # L · num_blocks, and this layer's table points into it
                    def blocks(a):
                        return a.reshape((nl * nb,) + a.shape[2:])

                    kpool, vpool = blocks(kc), blocks(vc)
                    kspool = vspool = None
                    if quantized:
                        kspool, vspool = blocks(ksc), blocks(vsc)
                    layer_tables = block_tables + layer * nb
                    tree_bits = None
                    if tree is not None:
                        anc = tree[1]
                        if anc.ndim == 2:
                            anc = jnp.broadcast_to(
                                anc[None], (q.shape[0],) + anc.shape
                            )
                        t_nodes = anc.shape[-1]
                        bits = jnp.zeros(anc.shape[:2], jnp.int32)
                        for m_ in range(t_nodes):
                            bits = bits | (
                                anc[:, :, m_].astype(jnp.int32) << m_
                            )
                        tree_bits = bits
                    if (
                        parallel_state.model_parallel_is_initialized()
                        and parallel_state.get_parallel_state().mesh.size > 1
                    ):
                        # multi-chip: the kernel runs per rank in a shard_map
                        # region on its NKV head slice (eligibility guarantees
                        # a pure-tp mesh with divisible heads); out spec = the
                        # q head split, so the constrain below is a no-op
                        # restatement, and the row-parallel o-projection right
                        # after attention performs the tp reduction. Scale
                        # arrays ride in on the same head split — no new
                        # collective.
                        att = paged_flash_decode_tp(
                            q, kpool, vpool, layer_tables, positions,
                            mesh=parallel_state.get_parallel_state().mesh,
                            kv_limit=limit, k_scale=kspool, v_scale=vspool,
                            quant_mxu=c.quant_mxu and ksc is not None,
                            row_live=row_live, tree_bits=tree_bits,
                        )
                    else:
                        att = paged_flash_decode(
                            q, kpool, vpool, layer_tables, positions,
                            kv_limit=limit, k_scale=kspool, v_scale=vspool,
                            quant_mxu=c.quant_mxu and ksc is not None,
                            row_live=row_live, tree_bits=tree_bits,
                        )
                    att = constrain(att, P(BATCH_AXES, None, ha, None))
            else:
                with jax.named_scope("kv_read"):
                    jlog = jnp.arange(limit, dtype=jnp.int32)
                    rd_phys = (
                        base
                        + block_tables[:, jlog // bs] * bs
                        + (jlog % bs)[None, :]
                    )
                    if quantized:
                        # dequant outside the kernel, same f32-widen formula the
                        # kernel fuses after its block DMA — bit-identical
                        # operands on every eligibility path
                        from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (  # noqa: E501
                            kv_dequantize,
                        )

                        k_all = kv_dequantize(
                            kflat[rd_phys], ksflat[rd_phys], q.dtype
                        )  # (b, limit, NKV, D)
                        v_all = kv_dequantize(vflat[rd_phys], vsflat[rd_phys], q.dtype)
                    else:
                        k_all = kflat[rd_phys].astype(q.dtype)  # (b, limit, NKV, D)
                        v_all = vflat[rd_phys].astype(q.dtype)
                with jax.named_scope("sdpa"):
                    att = self._cache_attention(
                        q, k_all, v_all, pos_block, ha, positions=positions,
                        tree=tree,
                    )
        if quantized:
            return att, (kc, ksc), (vc, vsc)
        return att, kc, vc

    def decode_step(
        self,
        params: Params,
        cache: PagedKVCache,
        tokens: jax.Array,       # (b,) int32 — last sampled token per lane
        positions: jax.Array,    # (b,) int32 — write row per lane
        block_tables: jax.Array,  # (b, W) int32
        *,
        kv_limit: Optional[int] = None,
        pos_cap: Optional[int] = None,
        sampling: Optional[tuple] = None,
        logit_poison: Optional[jax.Array] = None,
        **kind_tables: jax.Array,
    ) -> Tuple[jax.Array, ...]:
        """One resident-state decode step: T=1 paged forward plus the
        on-device state advance. Returns ``(logits (b, V), new_positions,
        cache)`` where ``new_positions = positions + 1`` — the sampled token
        and incremented position ARE the next step's inputs, so a serving
        loop can dispatch step N+1 without any host round trip (the
        double-buffered async loop in ``serving/engine.py``).

        ``pos_cap`` clamps the advanced positions (static). Idle lanes in a
        resident batch keep stepping with all-null tables — their writes
        land in the null block and their outputs are discarded — so without
        a cap a long-idle lane's position would eventually walk past the
        rope table. The cap only ever binds on such garbage lanes: real
        lanes finish at ``max_seq_len - 1``, below any sane cap.

        ``sampling`` opts into fused on-device sampling
        (``PagedConfig.on_device_sampling``): a ``(rng_data (b, 2) uint32,
        temperature (b,), top_k (b,), top_p (b,))`` tuple of device-resident
        per-lane arrays — the first return becomes the sampled int32 tokens
        instead of logits, drawn by :func:`..sampling.sample_lanes` with the
        per-lane key folded by the landing index ``positions + 1`` (pre-cap:
        the clamp only ever binds on garbage lanes). ``logit_poison``
        composes the checked variant in-fuse: the finite check runs on the
        raw logits *before* sampling and a ``finite (b,)`` bool slots in
        after the first return — ``(tokens, finite, new_positions, cache)``.
        Both default to None (static), leaving the host-sampling traces
        bitwise unchanged.

        ``kind_tables``: ``<name>_tables`` (b, blocks a lane) of each kind of
        cache that is laid out a lane (:class:`CacheKind`: :class:`LagunaDecode`'s
        ``window_tables``, :class:`JambaDecode`'s ``state_tables``), handed on
        to the model's ``forward``; a model of one kind takes none.
        """
        logits, cache = self.forward(
            params, cache, tokens[:, None], positions, None,
            block_tables=block_tables, kv_limit=kv_limit, **kind_tables,
        )
        logits = logits[:, 0, :]
        finite = None
        if logit_poison is not None:
            logits, finite = self.finite_logit_check(logits, logit_poison)
        new_positions = positions + 1
        if pos_cap is not None:
            new_positions = jnp.minimum(new_positions, pos_cap)
        if sampling is not None:
            from neuronx_distributed_llama3_2_tpu.inference.sampling import (
                sample_lanes,
            )

            rng_data, temperature, top_k, top_p = sampling
            out = sample_lanes(
                logits, rng_data, positions + 1, temperature, top_k, top_p
            )
        else:
            out = logits
        if finite is not None:
            return out, finite, new_positions, cache
        return out, new_positions, cache

    @staticmethod
    def finite_logit_check(
        logits: jax.Array, poison_mask: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, jax.Array]:
        """Per-lane logit health check for the serving engine's "checked"
        program variants (docs/serving.md "Failure handling & degradation"):
        returns ``(logits, finite (b,) bool)`` where ``finite[i]`` is the
        on-device ``isfinite`` reduction over lane i's logits — a single
        boolean per lane rides the existing readback instead of shipping the
        vocab axis to host. ``poison_mask`` (b,) int32 is the chaos-injection
        hook: lanes with a nonzero mask get their logits overwritten with NaN
        *before* the check (and before sampling / the accept rule), so fault
        tests exercise the same detection path a genuine numerical blow-up
        would take. ``poison_mask=None`` is static — the unchecked trace is
        bitwise unchanged."""
        if poison_mask is not None:
            bad = (poison_mask > 0).reshape(
                poison_mask.shape + (1,) * (logits.ndim - 1)
            )
            logits = jnp.where(bad, jnp.asarray(jnp.nan, logits.dtype), logits)
        finite = jnp.all(jnp.isfinite(logits), axis=tuple(range(1, logits.ndim)))
        return logits, finite

    def verify_step(
        self,
        params: Params,
        cache: PagedKVCache,
        tokens: jax.Array,        # (b, k+1) int32 — [cur, d_0 .. d_{k-1}]
        positions: jax.Array,     # (b,) int32 — cur's write row per lane
        block_tables: jax.Array,  # (b, W) int32
        draft_len: jax.Array,     # (b,) int32 — valid drafts per lane, <= k
        *,
        kv_limit: Optional[int] = None,
        pos_cap: Optional[int] = None,
        logit_poison: Optional[jax.Array] = None,
        sampling: Optional[tuple] = None,
    ) -> Tuple[jax.Array, ...]:
        """One speculative verify step: the greedy multi-token sibling of
        :meth:`decode_step`. The candidate block ``[cur, d_0 .. d_{k-1}]``
        is scored in ONE block-causal forward (writing its K/V at rows
        ``positions .. positions + k``), the longest draft prefix agreeing
        with the target's argmax is accepted on device — capped per lane by
        ``draft_len``, so a lane with no drafts degrades to a plain decode
        step — and the resident state advances without any host round trip.

        Returns ``(emitted (b, k+1), accept (b,), new_tokens (b,),
        new_positions (b,), cache)``: ``emitted[i, :accept[i] + 1]`` are the
        tokens the lane commits this step (accepted drafts plus the
        correction/bonus token), ``new_tokens[i] = emitted[i, accept[i]]``
        is the new resident token (newest emitted, K/V not yet written —
        the same invariant :meth:`decode_step` keeps), and
        ``new_positions = positions + accept + 1`` is its write row.
        Rejected rows ``> positions + accept`` need no rollback: the
        block-causal mask never looks past the frontier, so the next step
        simply overwrites them (the overwrite-frontier trick of
        :mod:`.speculative`). By default acceptance compares against
        ``argmax``, which is exactly ``sample()`` under
        ``SamplingConfig(greedy=True)``.

        ``sampling`` — the same ``(rng_data, temperature, top_k, top_p)``
        per-lane tuple :meth:`decode_step` takes — lifts the greedy-only
        restriction: the per-row targets become position-keyed draws
        (``fold_in(lane_key, positions + 1 + j)`` for row j), so the
        accepted stream is deterministically equivalent to the sequential
        fused-sampling decode of the same lane — a lane at the greedy
        sentinel (``temperature <= 0``) reduces exactly to the argmax rule.

        ``logit_poison`` (b,) int32 opts into the checked variant: logits
        run through :meth:`finite_logit_check` *before* the accept rule and
        the return grows a trailing-``finite`` element —
        ``(emitted, accept, new_tokens, new_positions, finite, cache)``.
        None (the default, static) keeps the unchecked trace bitwise
        unchanged.
        """
        from neuronx_distributed_llama3_2_tpu.inference.speculative import (
            accept_rule,
        )

        logits, cache = self.forward(
            params, cache, tokens, positions, None,
            block_tables=block_tables, kv_limit=kv_limit,
        )
        finite = None
        if logit_poison is not None:
            logits, finite = self.finite_logit_check(logits, logit_poison)
        if sampling is not None:
            from neuronx_distributed_llama3_2_tpu.inference.sampling import (
                sample_lanes,
            )

            rng_data, temperature, top_k, top_p = sampling
            # targets[i, j] = the token this lane WOULD emit at row
            # positions[i] + j + 1 — keyed by that landing index, so the
            # accept comparison replays the sequential sampled stream
            kp1 = tokens.shape[1]
            index = positions[:, None] + 1 + jnp.arange(kp1, dtype=jnp.int32)
            targets = sample_lanes(
                logits, rng_data, index, temperature, top_k, top_p
            )
        else:
            # targets[i, j] = target's argmax for row positions[i] + j + 1
            targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        accept, emitted = accept_rule(tokens[:, 1:], targets, draft_len=draft_len)
        new_tokens = jnp.take_along_axis(emitted, accept[:, None], axis=1)[:, 0]
        new_positions = positions + accept + 1
        if pos_cap is not None:
            new_positions = jnp.minimum(new_positions, pos_cap)
        if finite is not None:
            return emitted, accept, new_tokens, new_positions, finite, cache
        return emitted, accept, new_tokens, new_positions, cache

    def _tree_frontier_commit(
        self, cache, block_tables, positions, depths, amask, best
    ):
        """Relocate the accepted root→leaf path to the true frontier. A
        packed tree block writes node ``j``'s K/V at row ``positions + j``
        (branch-interleaved), but the lane's committed history must occupy
        consecutive rows ``positions + 1 .. positions + accept``. Gather the
        accepted path's rows and scatter them depth-ordered at the frontier
        through the same flat-pool indexing the fresh-block write uses — no
        pool copy, COW/preempt/spill invariants untouched (only rows inside
        the lane's own already-allocated blocks move). Depth slots with no
        path node (beyond the accepted depth, or a lane that accepted
        nothing — ``best == 0``, plain decode step included) default to an
        identity ``src == dst`` move, so the commit is uniformly safe on
        every lane, forced mixed lanes included. Gathers complete before the
        single scatter, so overlapping src/dst rows read pre-commit values.
        Quantized pools move (payload, scale) together, so relocated rows
        dequantize exactly as they did at their packed positions."""
        t = depths.shape[1]
        if t <= 1:
            return cache
        iota = jnp.arange(t, dtype=jnp.int32)[None, :]
        # path[i, m] — node m is on lane i's accepted root→best path
        path = jnp.take_along_axis(amask, best[:, None, None], axis=1)[:, 0]
        src_cols = []
        for dd in range(1, t):
            dsel = path & (depths == dd)  # at most one node per lane
            node = jnp.sum(jnp.where(dsel, iota, 0), axis=1)
            src_cols.append(jnp.where(jnp.any(dsel, axis=1), node, dd))
        src_rows = positions[:, None] + jnp.stack(src_cols, axis=1)
        dst_rows = (
            positions[:, None] + 1 + jnp.arange(t - 1, dtype=jnp.int32)[None, :]
        )
        bs = cache.k.shape[2]

        def phys(rows):
            return (
                jnp.take_along_axis(block_tables, rows // bs, axis=1) * bs
                + rows % bs
            )

        src_phys, dst_phys = phys(src_rows), phys(dst_rows)

        def move(arr):
            l, nb = arr.shape[0], arr.shape[1]
            flat = arr.reshape((l, nb * bs) + arr.shape[3:])
            vals = flat[:, src_phys]  # (L, b, t-1, ...)
            return flat.at[:, dst_phys].set(vals).reshape(arr.shape)

        kwargs = dict(k=move(cache.k), v=move(cache.v))
        if getattr(cache, "k_scale", None) is not None:
            kwargs.update(
                k_scale=move(cache.k_scale), v_scale=move(cache.v_scale)
            )
        return type(cache)(**kwargs)

    def tree_verify_step(
        self,
        params: Params,
        cache: PagedKVCache,
        tokens: jax.Array,        # (b, t) int32 — [cur, node_1 .. node_{t-1}]
        positions: jax.Array,     # (b,) int32 — cur's write row per lane
        block_tables: jax.Array,  # (b, W) int32
        parents: jax.Array,       # (b, t) int32 — parents[j] < j, node space
        node_len: jax.Array,      # (b,) int32 — live nodes incl. root, <= t
        *,
        kv_limit: Optional[int] = None,
        pos_cap: Optional[int] = None,
        logit_poison: Optional[jax.Array] = None,
        sampling: Optional[tuple] = None,
    ) -> Tuple[jax.Array, ...]:
        """One speculative **tree** verify step: the branching sibling of
        :meth:`verify_step`. The packed candidate tree ``tokens`` (node 0 is
        the resident token, parents precede children) is scored in ONE
        ancestor-masked forward — node ``j`` writes K/V at row
        ``positions + j``, attends at RoPE position ``positions + depth(j)``
        and sees exactly the committed prefix plus its own root→self chain —
        then the deepest root-anchored accepted path is selected on device
        (:func:`..speculative.tree_accept_rule`) and its K/V rows are
        relocated to the true frontier (:meth:`_tree_frontier_commit`).

        Per-row targets are keyed by each node's *child landing index*
        (``positions + 1 + depth``), so on a single-chain tree
        (``parents[j] == j - 1``) the whole step — mask, targets, accept,
        identity commit — reduces bit-for-bit to :meth:`verify_step`.
        ``node_len`` caps acceptance per lane (the root is always live, so
        ``node_len <= 1`` degrades to a plain decode step); padding nodes
        past it are parent-clipped and self-visible only, never ancestors
        of live nodes.

        Returns the :meth:`verify_step` tuple ``(emitted (b, t),
        accept (b,), new_tokens (b,), new_positions (b,), [finite (b,)],
        cache)`` — ``emitted[i, :accept[i] + 1]`` is the accepted path's
        token stream (bonus/correction last), ``new_positions = positions
        + accept + 1`` clamped to ``pos_cap``. ``sampling`` /
        ``logit_poison`` compose exactly as in :meth:`verify_step`."""
        from neuronx_distributed_llama3_2_tpu.inference.speculative import (
            tree_accept_rule,
            tree_topology,
        )

        depths, amask = tree_topology(parents)
        logits, cache = self.forward(
            params, cache, tokens, positions, None,
            block_tables=block_tables, kv_limit=kv_limit,
            tree=(depths, amask),
        )
        finite = None
        if logit_poison is not None:
            logits, finite = self.finite_logit_check(logits, logit_poison)
        if sampling is not None:
            from neuronx_distributed_llama3_2_tpu.inference.sampling import (
                sample_lanes,
            )

            rng_data, temperature, top_k, top_p = sampling
            # targets[i, j] = the token this lane WOULD emit at node j's
            # child landing index positions[i] + 1 + depth(j) — the same
            # position-keyed draw the sequential fused-sampling decode of
            # the accepted path makes, so sampled acceptance replays it
            index = positions[:, None] + 1 + depths
            targets = sample_lanes(
                logits, rng_data, index, temperature, top_k, top_p
            )
        else:
            targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        accept, emitted, best = tree_accept_rule(
            tokens, targets, parents, node_len=node_len,
            topology=(depths, amask),
        )
        cache = self._tree_frontier_commit(
            cache, block_tables, positions, depths, amask, best
        )
        new_tokens = jnp.take_along_axis(emitted, accept[:, None], axis=1)[:, 0]
        new_positions = positions + accept + 1
        if pos_cap is not None:
            new_positions = jnp.minimum(new_positions, pos_cap)
        if finite is not None:
            return emitted, accept, new_tokens, new_positions, finite, cache
        return emitted, accept, new_tokens, new_positions, cache

    def mixed_step(
        self,
        params: Params,
        cache: PagedKVCache,
        tokens: jax.Array,        # (b,) int32 — resident decode token per lane
        positions: jax.Array,     # (b,) int32 — resident write row per lane
        block_tables: jax.Array,  # (b, W) int32
        rows: jax.Array,          # (b, t) int32 — per-lane packed row payload
        row_start: jax.Array,     # (b,) int32 — forced rows' first write row
        row_len: jax.Array,       # (b,) int32 — live payload rows, <= t
        forced: jax.Array,        # (b,) int32 — 1 = prefill-chunk lane
        *,
        kv_limit: Optional[int] = None,
        pos_cap: Optional[int] = None,
        logit_poison: Optional[jax.Array] = None,
        sampling: Optional[tuple] = None,
        parents: Optional[jax.Array] = None,  # (b, t) int32 — tree topology
    ) -> Tuple[jax.Array, ...]:
        """One fused mixed-mode step: decode lanes, speculative-verify rows
        and active prefill-chunk suffixes share a single t-row block-causal
        forward over the paged pool (``PagedConfig.fused_step`` — ROADMAP
        item 5's one-dispatch steady state). Per lane, ``forced`` selects
        the row role:

        - ``forced == 0`` (decode/verify): the scored block is
          ``[tokens[i], rows[i, :t-1]]`` at rows ``positions[i] ..`` —
          ``rows`` carries the lane's drafts and ``row_len`` its draft
          count, so ``row_len == 0`` is exactly a plain decode step and
          ``row_len == k`` exactly :meth:`verify_step` at width ``k + 1``.
        - ``forced == 1`` (prefill chunk): the block is the next
          ``row_len`` prompt tokens written at rows ``row_start[i] ..``
          over the lane's own table (the psfx chunk semantics), the accept
          length is *forced* to ``row_len - 1``, and the emitted token at
          that index is the sample keyed ``row_start + row_len`` — on the
          final chunk, byte-identical to the suffix-prefill program's
          first generated token, and the resident (token, position)
          advance to exactly what the unfused ``lane_set`` install would
          have uploaded.

        Rows past a lane's live width (``row_len`` forced,
        ``row_len + 1`` otherwise) are packing padding: their outputs are
        garbage the accept clamp never selects, and their frontier writes
        are rewritten by the next dispatch over the same rows before any
        block-causal mask admits them (the same overwrite-frontier
        argument as rejected verify rows). ``row_live`` rides into
        :meth:`forward` so the paged kernel stops each lane's KV walk at
        its live frontier instead of the packed width.

        Returns the :meth:`verify_step` tuple — ``(emitted (b, t),
        accept (b,), new_tokens (b,), new_positions (b,), [finite (b,)],
        cache)`` — with ``new_positions = eff_pos + accept + 1`` (clamped
        to ``pos_cap``), where ``eff_pos`` is ``row_start`` on forced
        lanes and ``positions`` otherwise. ``sampling`` / ``logit_poison``
        compose exactly as in :meth:`verify_step`.

        ``parents`` opts the verify rows into **tree** speculation
        (:meth:`tree_verify_step` semantics): ``rows[:, :t-1]`` become the
        packed draft nodes 1..t-1 of a per-lane candidate tree rooted at
        the resident token, accepted along the deepest root-anchored path
        and committed to the frontier. Forced lanes are steered onto the
        single-chain topology (depth j == row j), which makes their
        ancestor mask exactly the linear block-causal mask and their
        frontier commit the identity — chunk semantics are unchanged.
        ``parents=None`` (static) keeps the linear trace bitwise unchanged.
        """
        from neuronx_distributed_llama3_2_tpu.inference.speculative import (
            accept_rule,
            tree_accept_rule,
            tree_topology,
        )

        t = rows.shape[1]
        is_forced = forced > 0
        eff_pos = jnp.where(is_forced, row_start, positions)
        # decode/verify lanes score [resident token, drafts]; forced lanes
        # score the chunk payload verbatim
        block = jnp.where(
            is_forced[:, None],
            rows,
            jnp.concatenate([tokens[:, None], rows[:, : t - 1]], axis=1),
        )
        live = jnp.where(is_forced, row_len, row_len + 1)
        topo = None
        eff_parents = None
        if parents is not None:
            # forced lanes ride the chain topology: depths == arange(t) and
            # a lower-triangular ancestor mask, i.e. exactly the linear
            # block-causal mask + write rows the unfused psfx chunk uses
            chain = jnp.maximum(jnp.arange(t, dtype=jnp.int32) - 1, 0)
            eff_parents = jnp.where(is_forced[:, None], chain[None, :], parents)
            topo = tree_topology(eff_parents)
        logits, cache = self.forward(
            params, cache, block, eff_pos, None,
            block_tables=block_tables, kv_limit=kv_limit, row_live=live,
            tree=topo,
        )
        finite = None
        if logit_poison is not None:
            logits, finite = self.finite_logit_check(logits, logit_poison)
        if sampling is not None:
            from neuronx_distributed_llama3_2_tpu.inference.sampling import (
                sample_lanes,
            )

            rng_data, temperature, top_k, top_p = sampling
            index = eff_pos[:, None] + 1 + (
                jnp.arange(t, dtype=jnp.int32)[None, :]
                if topo is None
                else topo[0]
            )
            targets = sample_lanes(
                logits, rng_data, index, temperature, top_k, top_p
            )
        else:
            targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # forced lanes carry draft_len 0 (linear) / node_len 1 (tree), so
        # the accept rule hands back targets / the root bonus untouched;
        # their accept is then overridden to land on the chunk's last row
        # (targets[row_len - 1] is the token keyed row_start + row_len —
        # the psfx sample index) and, on the tree path, their emitted row
        # is restored to raw targets so the override indexes the same
        # values the linear trace would
        if topo is None:
            dl = jnp.where(is_forced, 0, row_len)
            raw_accept, emitted = accept_rule(
                block[:, 1:], targets, draft_len=dl
            )
        else:
            node_len = jnp.where(is_forced, 1, row_len + 1)
            raw_accept, emitted, best = tree_accept_rule(
                block, targets, eff_parents, node_len=node_len, topology=topo
            )
            emitted = jnp.where(is_forced[:, None], targets, emitted)
            cache = self._tree_frontier_commit(
                cache, block_tables, eff_pos, topo[0], topo[1], best
            )
        accept = jnp.where(
            is_forced, jnp.maximum(row_len - 1, 0), raw_accept
        )
        new_tokens = jnp.take_along_axis(emitted, accept[:, None], axis=1)[:, 0]
        new_positions = eff_pos + accept + 1
        if pos_cap is not None:
            new_positions = jnp.minimum(new_positions, pos_cap)
        if finite is not None:
            return emitted, accept, new_tokens, new_positions, finite, cache
        return emitted, accept, new_tokens, new_positions, cache

    def forbidden_gather_shapes(self, batch: int, kv_limit: int):
        """The aval shapes a kernel-path decode/verify trace must never
        contain: the materialized ``(b, kv_limit, NKV, D)`` gathered-KV
        copy, plus its per-rank ``NKV/tp`` slice when a tp mesh is live.
        This is the single source of truth behind graftcheck GC001 and
        the no-gather jaxpr assertions (the gather fallback in
        :meth:`_attend_paged` is exactly what materializes these)."""
        from neuronx_distributed_llama3_2_tpu.parallel import (
            state as parallel_state,
        )

        nkv, d = self.config.num_kv_heads, self.config.head_dim
        shapes = {(batch, kv_limit, nkv, d)}
        tp = parallel_state.tensor_parallel_size_or(1)
        if tp > 1 and nkv % tp == 0:
            shapes.add((batch, kv_limit, nkv // tp, d))
        return shapes

    def _paged_kernel_eligible(self, t: int, tree) -> bool:
        """Gate for the Pallas paged-decode kernel: the ``use_paged_kernel``
        config opt-in and a fresh block of at most ``paged_kernel_max_t``
        tokens — T == 1 token-gen, speculative verify blocks (linear OR
        packed trees: the ancestor matrix rides into the kernel as per-node
        int32 bitmasks), and suffix-prefill chunks that fit the bound all
        qualify; longer prefill buckets keep the dense gather.

        Multi-device meshes are eligible when the mesh is **pure tensor
        parallel** and tp divides both head counts: the kernel then runs
        per rank inside a manual region on its NKV head slice
        (``paged_flash_decode_tp`` — identical grid, NKV/tp heads per
        chip, tables/positions replicated, tp-reduce supplied by the
        row-parallel o-projection). A non-divisible head count (the pool
        replicates, ``paged_cache_specs``) or a dp/pp/cp/ep-extended mesh
        (replicated tables no longer cover the whole mesh head-split-only)
        keeps the sharded dense-gather einsums."""
        from neuronx_distributed_llama3_2_tpu.parallel import (
            state as parallel_state,
        )

        if not self.config.use_paged_kernel:
            return False
        if not 1 <= t <= self.config.paged_kernel_max_t:
            return False
        if tree is not None and t > 32:
            return False  # ancestor sets pack into int32 bitmasks
        if (
            parallel_state.model_parallel_is_initialized()
            and parallel_state.get_parallel_state().mesh.size > 1
        ):
            if not parallel_state.mesh_is_tp_only():
                return False
            tp = parallel_state.get_tensor_model_parallel_size()
            if self.config.num_kv_heads % tp or self.config.num_heads % tp:
                return False
        return True

    def paged_dispatch_path(self, t: int, tree=None) -> str:
        """Public name for the kernel/gather dispatch decision at fresh-block
        width ``t``: ``"kernel"`` when :meth:`_paged_kernel_eligible` admits
        the Pallas paged-decode kernel, ``"gather"`` otherwise. The serving
        bucket catalog (``serving/catalog.py`` :func:`validate_ladder`) uses
        this to warn when a declared verify-t rung silently lands on the
        dense-gather fallback — the ladder should only promise buckets the
        fast path actually serves."""
        return "kernel" if self._paged_kernel_eligible(t, tree) else "gather"

    def _mlp_block(self, lp: Params, h: jax.Array) -> jax.Array:
        """Post-attention feed-forward on the normed hidden (b,T,H).
        Overridden by :class:`MixtralDecode` with the MoE block."""
        from neuronx_distributed_llama3_2_tpu.models.llama import LlamaMLP

        return LlamaMLP(self.config)(lp["mlp"], h)

    def _cache_attention(self, q, k_all, v_all, pos_block, ha, positions=None, tree=None):
        """q (b,T,N,D) against full cache rows (b,S_max,NKV,D) with the mask
        ``cache_index <= position + t`` (block-causal across the fresh block,
        full visibility of the committed prefix; garbage rows beyond the
        write frontier are masked out — reference manual prior+active softmax
        combine, attention_base.py:141-167, done here as one masked softmax).

        GQA runs as grouped einsums (q reshaped (b,T,NKV,G,D)) rather than
        ``jnp.repeat`` of the cache: decode is cache-bandwidth-bound and the
        repeat would materialize an N/NKV-times-larger K/V read (4x on
        Llama-3.2 geometry)."""
        b, t, n, d = q.shape
        s_max = k_all.shape[1]
        nkv = k_all.shape[2]
        g = n // nkv
        qg = q.reshape(b, t, nkv, g, d)
        scores = jnp.einsum("bskd,btkgd->bkgts", k_all, qg) * (d ** -0.5)
        scores = scores.reshape(b, n, t, s_max)
        scores = constrain(scores, P(BATCH_AXES, ha, None, None))
        scores = scores.astype(jnp.float32)
        j = jax.lax.iota(jnp.int32, s_max)[None, None, :]  # (1,1,S_max)
        if tree is None:
            mask = j <= pos_block[:, :, None]  # (b,T,S_max)
        else:
            # committed prefix: rows < position; in-block: the candidate
            # tree's ancestor mask over rows [position, position + T)
            u = j - positions[:, None, None]  # (b,1,S_max) offset into block
            prefix_ok = j < positions[:, None, None]
            in_block = (u >= 0) & (u < t)
            anc = tree[1]  # (T,T) static tree or (b,T,T) per-lane
            if anc.ndim == 2:
                anc = jnp.broadcast_to(anc[None, :, :], (q.shape[0], t, t))
            u_cl = jnp.clip(u, 0, t - 1)
            tree_ok = jnp.take_along_axis(anc, u_cl, axis=2)
            mask = prefix_ok | (in_block & tree_ok)
        scores = jnp.where(mask[:, None, :, :], scores, jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        pg = probs.reshape(b, nkv, g, t, s_max)
        out = jnp.einsum("bkgts,bskd->btkgd", pg, v_all).reshape(b, t, n, d)
        return constrain(out, P(BATCH_AXES, None, ha, None))


@dataclasses.dataclass(frozen=True)
class MixtralDecode(LlamaDecode):
    """Decode-mode Mixtral: LlamaDecode attention/cache machinery with the
    dense MLP swapped for the MoE block (reference Mixtral inference model,
    ``examples/inference/mixtral/neuron_modeling_mixtral.py``, whose attention
    is the Llama base + MoE feed-forward).

    Inference never drops tokens — the training config's capacity factor is
    ignored here — and every block, token-gen and prefill alike, runs the
    batched all-experts path (:meth:`..moe.ExpertMLPs.forward_all_experts`):
    decode streams each layer's expert stack once, fused with the layer
    scan's slice, and big-bucket MoE prefill pays all-experts FLOPs. The
    reference's selective expert loading for token-gen (expert_mlps.py:267,
    dispatch :298-357) is taken at no shape: over the layer scan its gather
    copies the whole stack before it reads a slice (``ExpertMLPs.__call__``
    carries the measured table). Routing is per-token, so decode routing is
    identical to the training model's. Expert parallelism is not supported
    in decode (the reference's Mixtral inference is TP-only as well).
    """

    # shardlint SL002 — see LlamaDecode; additionally branches on ep size
    __layout_deps__ = LlamaDecode.__layout_deps__ + (
        "get_expert_model_parallel_size",
    )

    def _moe(self):
        """The layer's expert block as inference runs it: the training
        config's capacity factor ignored, so ``ExpertMLPs.__call__`` takes the
        no-drop (all-experts) dispatch (single dispatch site)."""
        from neuronx_distributed_llama3_2_tpu.moe.model import MoE

        return MoE(dataclasses.replace(self.config.moe_config(), capacity_factor=None))

    def _mlp_block(self, lp: Params, h: jax.Array, routes=None) -> jax.Array:
        """``routes``: ``MoE.route``'s result where the model routes from
        another tensor than ``h`` (:class:`SmallThinkerDecode`), else None."""
        from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

        if (
            parallel_state.model_parallel_is_initialized()
            and parallel_state.get_expert_model_parallel_size() > 1
        ):
            raise NotImplementedError(
                "MixtralDecode does not support expert parallelism: decode "
                "under an ep>1 mesh would allgather every EP-sharded expert "
                "weight per token. Serve MoE models with tp/dp sharding."
            )
        y, _, _ = self._moe()(lp["moe"], h, routes=routes)
        return y


@dataclasses.dataclass(frozen=True)
class SarvamDecode(MixtralDecode):
    """Decode-mode latent attention (MLA, :mod:`..models.sarvam`) over a
    :class:`LatentCache`: what a token leaves in the cache is the one row
    ``[c ‖ k_r]``, written after the norm and the rotation, and every program
    reads those rows — never keys or values by head.

    Which form of the attention a program runs follows from its shape alone:
    ``pctx`` (``context_encode``) attends over the fresh block expanded;
    a block over cached rows (``psfx``, ``pdecode``) runs absorbed where that
    costs fewer FLOPs (:func:`..models.sarvam.absorbed_is_cheaper`: one
    decode row, a short chunk) and expanded otherwise (a 512-row chunk
    up-projects the cached rows through ``W_UKV``). Prefill attention runs
    over query blocks of at most 512 rows, so no program holds a
    (heads, S, S) tensor.

    The leading dense layers are a stack of their own ahead of the expert
    layers' scan, the pool's layer index running through both.

    Which read a decode step (one fresh row a lane under a block table) takes
    follows from what the program can see (:meth:`decode_read`): where Pallas
    kernels run on one device, :func:`..kernels.paged_attention_pallas.
    latent_decode_walk` reads each lane's live blocks where they lie in the
    pool — a row is key and value at once — between ``W_UK`` folded into q and
    ``W_UV`` over its output; everywhere else (a block of rows, the dense
    cache, a mesh, the ``"reference"`` kernel mode) the rung's blocks are
    gathered through the table and :func:`..models.sarvam.latent_attention`
    runs over them, the walk's plain twin. ``paged_flash_decode`` reads k/v
    by head and is never eligible; tree (speculative) blocks and a quantized
    pool are refused.

    Two families run on it. Sarvam (this class): the query is one matrix and
    the residual is the plain ``x + F(norm(x))`` (:meth:`_latent_layer`).
    Xing4.0 (:class:`XingDecode`): the query goes through a normed latent —
    a fact of the config that ``LatentAttention.project`` reads, so
    :meth:`_latent_attention` is shared as it stands — and the residual is a
    multi-stream one, so it overrides :meth:`_latent_layer` and the carry."""

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.sarvam import SarvamForCausalLM

        return SarvamForCausalLM(self.config)

    # -- cache ------------------------------------------------------------

    @property
    def pool_row_width(self) -> int:
        """The cache's minor axis: the row's 576 values in whole lanes of 128
        (640). A TPU tiles a bf16 array's two minor axes (16, 128); at 576 the
        device's default layout, which avoids padding, makes the *block* axis
        minor — every program then re-tiled the whole pool on its way in and
        out (two pool-sized copies a call, AOT for a v5e, PR 33). At 640 the
        default is row-major, the pool is a donated carry written in place,
        and the bytes are those the row-major tiling would have padded to."""
        return -(-self.config.cache_row_width // 128) * 128

    def init_cache(self, max_batch: int, max_len: int, dtype: Any = None) -> LatentCache:
        c = self.config
        shape = (c.num_layers, max_batch, max_len, self.pool_row_width)
        return LatentCache(kv=jnp.zeros(shape, dtype or c.dtype))

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype: Any = None,
        kv_cache_dtype: Optional[str] = None,
    ) -> LatentCache:
        if kv_cache_dtype not in (None, "bf16"):
            raise NotImplementedError(
                f"kv_cache_dtype={kv_cache_dtype!r}: a latent (MLA) pool has no "
                "quantized form — its row is one vector shared by every head, "
                "and the per-(row, head) scale tiles of quantization/kv_cache "
                "do not describe it"
            )
        return self.init_cache(num_blocks, block_size, dtype)

    def cache_row_dims(self) -> Tuple[int, int, int]:
        return 1, 1, self.pool_row_width

    def paged_cache_specs(self, quantized: bool = False) -> LatentCache:
        """The latent row is shared by every head: replicated over tp."""
        return LatentCache(kv=P())

    def cache_specs(self, max_batch: Optional[int] = None) -> LatentCache:
        return LatentCache(kv=P())

    def forbidden_gather_shapes(self, batch: int, kv_limit: int):
        return {(batch, kv_limit, self.pool_row_width)}

    def _paged_kernel_eligible(self, t: int, tree) -> bool:
        return False

    def decode_read(self, kind: CacheKind, quantized: bool = False) -> str:
        """``"kernel"`` where :func:`_kernels_on_one_device` (a latent pool has
        no quantized form and no window): one row a lane is then attended by
        ``latent_decode_walk`` over the lane's live blocks. What the program
        can see decides, no option: a block of several rows (``psfx``), the
        dense cache, a mesh and the ``"reference"`` mode keep the block-wise
        gather and ``latent_attention``."""
        return "kernel" if not quantized and _kernels_on_one_device() else "gather"

    # -- forward ----------------------------------------------------------

    def _run_layers(
        self, params, cache, x, sin, cos, pos_block, positions, slots,
        *, context_encode: bool, tree=None, kv_limit=None, block_tables=None,
        row_live=None,
    ):
        if tree is not None:
            raise NotImplementedError("tree verification over a latent cache")
        c = self.config
        tap = routing_tap.current()

        def layer_body(carry, layer_in):
            x, pool = carry
            lp, layer = layer_in
            x, pool = self._latent_layer(
                lp, x, pool, layer, sin, cos, pos_block, slots,
                context_encode=context_encode, kv_limit=kv_limit,
                block_tables=block_tables,
            )
            return (x, pool), (None if tap is None else tap.take_layer())

        # the dense layers' stack, then the expert layers', one carry and one
        # run of layer indices through both
        carry, first = (x, cache.kv), 0
        for name in ("dense_layers", "layers"):
            if name not in params:
                continue
            count = jax.tree.leaves(params[name])[0].shape[0]
            carry, counts = jax.lax.scan(
                layer_body, carry,
                (params[name], first + jnp.arange(count, dtype=jnp.int32)),
            )
            if tap is not None:
                tap.commit(counts, count)
            first += count
        return carry[0], LatentCache(kv=carry[1])

    def _latent_layer(
        self, lp, x, pool, layer, sin, cos, pos_block, slots,
        *, context_encode: bool, kv_limit=None, block_tables=None,
    ):
        """One decoder layer over the latent cache, the plain residual
        ``x + F(norm(x))`` around both sub-layers: (x, pool)."""
        norm = make_norm(self.config)
        attn_out, pool = self._latent_attention(
            lp, norm(lp["attn_norm"], x), pool, layer, sin, cos, pos_block, slots,
            context_encode=context_encode, kv_limit=kv_limit, block_tables=block_tables,
        )
        x = x + attn_out
        return x + self._feed_forward(lp, norm(lp["mlp_norm"], x)), pool

    def _feed_forward(self, lp, h):
        """The layer's feed-forward over normed h: experts where the layer
        has them, the dense SwiGLU in a leading layer."""
        ffn = MixtralDecode._mlp_block if "moe" in lp else LlamaDecode._mlp_block
        return ffn(self, lp, h)

    def _latent_attention(
        self, lp, h, pool, layer, sin, cos, pos_block, slots,
        *, context_encode: bool, kv_limit=None, block_tables=None,
    ):
        """The attention sub-layer of normed h (b, t, H) over the latent
        cache: pool (L, num_blocks, block_size, W) under ``block_tables``,
        else (L, B, S_max, W); writes the fresh rows at layer ``layer`` and
        attends (see the class). Returns (the block's output, pool)."""
        from neuronx_distributed_llama3_2_tpu.models.sarvam import (
            LatentAttention,
            absorb_output,
            absorb_query,
            absorbed_is_cheaper,
            latent_attention,
        )

        c = self.config
        attn = LatentAttention(c)
        t = h.shape[1]
        kv_b = lp["attn"]["kv_b"]["kernel"]
        with jax.named_scope("attn"):
            q, rows = attn.project(lp["attn"], h, sin, cos, pos_block)
            with jax.named_scope("kv_write"):
                # the row's values, then zeros up to the pool's whole lanes
                stored = jnp.pad(
                    rows.astype(pool.dtype),
                    ((0, 0), (0, 0), (0, pool.shape[-1] - rows.shape[-1])),
                )
                if block_tables is None:
                    pool = pool.at[layer, slots[:, None], pos_block].set(stored)
                else:
                    # every layer's rows as one run: never pool[layer]
                    nl, nb, bs, w = pool.shape
                    flat = pool.reshape(nl * nb * bs, w)
                    base = layer * (nb * bs)
                    wr_phys = (
                        base
                        + jnp.take_along_axis(block_tables, pos_block // bs, axis=1) * bs
                        + pos_block % bs
                    )
                    flat = flat.at[wr_phys].set(stored)
                    pool = flat.reshape(pool.shape)
            if (
                t == 1 and block_tables is not None and not context_encode
                and self.decode_read(self.cache_kinds[0]) == "kernel"
            ):
                # one row a lane: the lane's live blocks are read where they
                # lie, nothing gathered
                from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
                    latent_decode_walk,
                )

                q_abs = absorb_query(c, kv_b, q)
                with jax.named_scope("sdpa"):
                    o_lat = latent_decode_walk(
                        q_abs[:, 0], pool, block_tables, pos_block[:, 0], layer,
                        rank=c.kv_lora_rank, sm_scale=c.softmax_scale(), kv_limit=kv_limit)
                att = absorb_output(c, kv_b, o_lat[:, None])
                return attn.output(lp["attn"], att), pool
            if context_encode:
                # the fresh block alone, expanded (what the training model runs)
                seen, absorbed = rows, False
            else:
                with jax.named_scope("kv_read"):
                    if block_tables is None:
                        seen = pool[layer, slots, :kv_limit]
                    else:
                        # gathered a block at a time: a block's rows lie together
                        # (block_size · W values), so the gather moves
                        # limit / block_size slices a lane and not ``limit`` rows
                        # (row by row it ran at a tenth of the bandwidth: 15 of a
                        # decode step's 32 ms; chip run, PR 33)
                        limit = kv_limit if kv_limit is not None else block_tables.shape[1] * bs
                        nblk = -(-limit // bs)
                        blocks = flat.reshape(nl * nb, bs, w)[layer * nb + block_tables[:, :nblk]]
                        seen = blocks.reshape(blocks.shape[0], nblk * bs, w)[:, :limit]   # (b, limit, W)
                    seen = seen[..., :c.cache_row_width]
                absorbed = absorbed_is_cheaper(c, t)
            att = latent_attention(c, kv_b, q, seen, pos_block, absorbed=absorbed)
            return attn.output(lp["attn"], att), pool


@dataclasses.dataclass(frozen=True)
class XingDecode(SarvamDecode):
    """Decode-mode Xing4.0 (:mod:`..models.xing`): :class:`SarvamDecode`'s
    latent cache, pool row, decode read (the block walk or the block-wise
    gather, as :meth:`decode_read` says), form rule and write path
    unchanged — the query comes through its latent inside
    ``LatentAttention.project`` — under a multi-stream residual. The layer
    loop's carry is (b, t, ``hc_mult``, H): the embedding enters as equal
    streams, each sub-layer reads the streams' ``H_pre`` collapse and its
    output is spread back by ``H_post`` beside the ``H_res`` mix
    (:class:`..models.xing.HyperConnection`), and the final norm reads the
    streams' sum. The coefficients are a token's own, so a bucket's padding
    rows never touch a live row's streams.

    Weights replicate by their specs: ``tp > 1`` is refused here, at
    construction, not left to fail in a program. Tree blocks are refused as
    over any latent cache, so the model's next-token-prediction module (a
    drafter) is not served."""

    # shardlint SL002 — see LlamaDecode: the refusal below reads the same
    # parallel state the inherited traces do
    __layout_deps__ = MixtralDecode.__layout_deps__

    def __post_init__(self):
        from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

        if (
            parallel_state.model_parallel_is_initialized()
            and parallel_state.get_tensor_model_parallel_size() > 1
        ):
            raise NotImplementedError(
                "XingDecode under tp > 1: the multi-stream residual and the query "
                "latent are not worked out under tensor parallelism (every weight "
                "replicates by its spec); serve this family with tp = 1"
            )

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.xing import XingForCausalLM

        return XingForCausalLM(self.config)

    def residual_row_bytes(self) -> int:
        return int(self.config.residual_row_bytes)

    def _run_layers(self, params, cache, x, *args, **kwargs):
        from neuronx_distributed_llama3_2_tpu.models.xing import (
            enter_streams,
            leave_streams,
        )

        streams, cache = super()._run_layers(
            params, cache, enter_streams(self.config, x), *args, **kwargs)
        return leave_streams(streams), cache

    def _latent_layer(
        self, lp, x, pool, layer, sin, cos, pos_block, slots,
        *, context_encode: bool, kv_limit=None, block_tables=None,
    ):
        """One decoder layer over the latent cache, x the streams
        (b, t, n, H), a hyper-connection around each sub-layer."""
        from neuronx_distributed_llama3_2_tpu.models.xing import HyperConnection

        hc = HyperConnection(self.config)
        norm = make_norm(self.config)
        x, pool = hc.around(
            lp["attn_hc"], x,
            lambda u: self._latent_attention(
                lp, norm(lp["attn_norm"], u), pool, layer, sin, cos, pos_block, slots,
                context_encode=context_encode, kv_limit=kv_limit, block_tables=block_tables,
            ),
        )
        x, _ = hc.around(
            lp["mlp_hc"], x,
            lambda u: (self._feed_forward(lp, norm(lp["mlp_norm"], u)), None))
        return x, pool


def _kernels_on_one_device() -> bool:
    """Where a decode model's own bare Mosaic call runs: wherever Pallas
    kernels run (:func:`..kernels.mode.prefer_pallas` — the CPU tier's
    ``"reference"`` mode keeps the plain twin) on one device. On a
    multi-device mesh the cache shards by kv head and a bare Mosaic call
    cannot be partitioned."""
    from neuronx_distributed_llama3_2_tpu.kernels.mode import prefer_pallas
    from neuronx_distributed_llama3_2_tpu.parallel import (
        state as parallel_state,
    )

    if (
        parallel_state.model_parallel_is_initialized()
        and parallel_state.get_parallel_state().mesh.size > 1
    ):
        return False
    return prefer_pallas()


@dataclasses.dataclass(frozen=True)
class RetentionDecode(LlamaDecode):
    """Decode-mode power retention (:mod:`..models.brumby`) over a
    :class:`StateCache`: no program reads a row of the past.

    Which form a program runs follows from its shape: one token a lane
    (``pdecode``) is the recurrent form; a block of rows is the chunked form —
    from the **zero state** under ``context_encode`` (``pctx``: a freshly
    allocated block still holds its last owner's past, and a state is
    read-modify-write), from the block's state otherwise (``psfx``).
    ``row_live`` is the count of real rows of a padded block: rows at or past
    it leave the state untouched. ``kv_limit`` is accepted and means nothing.

    The states ride the layer loop as its carry. The chunked form slices each
    lane's out, updates it and writes it back at ``[layer, block]`` inside a
    loop over lanes; the recurrent form is one pass over the named blocks a
    layer (:func:`..kernels.retention_step_pallas.retention_step_paged`) where
    :meth:`uses_state_kernel` says the kernel runs, and the same lane loop around
    ``retention_step`` where it does not. Either way a donated pool is updated
    in place and a program's temporaries are one lane's state, never the pool.
    Tree (speculative) blocks and a quantized pool are refused; a rejected
    draft cannot be taken back out of a state."""

    # shardlint SL002 — see LlamaDecode: uses_state_kernel reads the mesh
    # (through _kernels_on_one_device)
    __layout_deps__ = LlamaDecode.__layout_deps__

    cache_is_positional = False

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.brumby import BrumbyForCausalLM

        return BrumbyForCausalLM(self.config)

    # -- cache ------------------------------------------------------------

    def init_cache(self, max_batch: int, max_len: int = 0, dtype: Any = None) -> StateCache:
        from neuronx_distributed_llama3_2_tpu.models.brumby import STATE_DTYPE

        c = self.config
        lead = (c.num_layers, max_batch, c.num_kv_heads, c.feature_width)
        dtype = dtype or STATE_DTYPE
        return StateCache(s=jnp.zeros(lead + (c.head_dim,), dtype), z=jnp.zeros(lead, dtype))

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype: Any = None,
        kv_cache_dtype: Optional[str] = None,
    ) -> StateCache:
        """``num_blocks`` states; ``block_size`` is not a dimension of them."""
        if kv_cache_dtype not in (None, "bf16"):
            raise NotImplementedError(
                f"kv_cache_dtype={kv_cache_dtype!r}: a retention state has no "
                "quantized form — it is a running sum of thousands of updates, "
                "not rows with a scale each"
            )
        return self.init_cache(num_blocks, dtype=dtype)

    def paged_cache_specs(self, quantized: bool = False) -> StateCache:
        """States shard by kv head over tp, like the k/v pools."""
        ha = _head_axis(self.config.num_kv_heads)
        return StateCache(s=P(None, None, ha), z=P(None, None, ha))

    def cache_specs(self, max_batch: Optional[int] = None) -> StateCache:
        return self.paged_cache_specs()

    def forbidden_gather_shapes(self, batch: int, kv_limit: int):
        return set()

    def _paged_kernel_eligible(self, t: int, tree) -> bool:
        return False

    def uses_state_kernel(self) -> bool:
        """Whether the recurrent form runs the one-pass state kernel
        (:func:`_kernels_on_one_device`); ``retention_step`` where it does not
        (on a mesh no cell runs it; never compiled for the chip)."""
        return _kernels_on_one_device()

    # -- forward ----------------------------------------------------------

    def _rope_rows(self, pos_block: jax.Array):
        """(sin, cos) of the rows ``pos_block`` (b, t) themselves, (b·t, d):
        a lane's memory has no last row, so there is no table length to size."""
        c = self.config
        if c.rope_scaling is not None:
            raise NotImplementedError("rope_scaling with retention layers")
        inv = 1.0 / (c.rope_theta ** (jnp.arange(0, c.head_dim, 2, dtype=jnp.float32) / c.head_dim))
        freqs = pos_block.reshape(-1, 1).astype(jnp.float32) * inv
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        return jnp.sin(emb), jnp.cos(emb)

    def forward(
        self, params: Params, cache: StateCache, tokens: jax.Array, positions: jax.Array,
        slots: Optional[jax.Array] = None, *, context_encode: bool = False,
        return_hidden: bool = False, tree=None, kv_limit: Optional[int] = None,
        block_tables: Optional[jax.Array] = None, row_live: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, StateCache]:
        """tokens (b, T) at rows ``positions ..`` over the states (see the
        class); returns (logits (b, T, V) or the normed hidden, the cache
        updated)."""
        if tree is not None:
            raise NotImplementedError("tree verification over a retention state")
        from neuronx_distributed_llama3_2_tpu.kernels.retention_step_pallas import (
            retention_step_paged,
        )
        from neuronx_distributed_llama3_2_tpu.models.brumby import (
            RetentionAttention,
            retention_chunks,
            retention_step,
        )

        c = self.config
        model = self._model()
        attn, norm = RetentionAttention(c), make_norm(c)
        b, t = tokens.shape
        pos_block = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        sin, cos = self._rope_rows(pos_block)
        rows = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)      # into sin / cos
        # the state a lane reads and writes: the block its table's first
        # entry names, or its slot
        if block_tables is not None:
            index = block_tables[:, 0]
        else:
            index = slots if slots is not None else jnp.arange(b, dtype=jnp.int32)
        recurrent = t == 1 and not context_encode and row_live is None
        one_pass = recurrent and self.uses_state_kernel()
        form = "step" if recurrent else "chunk"
        live = jnp.full((b,), t, jnp.int32) if row_live is None else row_live
        eps = c.retention_eps

        def layer_body(carry, layer_in):
            x, s_pool, z_pool = carry
            lp, layer = layer_in
            h = norm(lp["attn_norm"], x)
            with jax.named_scope("attn"):
                q, k, v, log_g = attn.project(lp["attn"], h, sin, cos, rows)

                def lane(i, lane_carry):
                    s_pool, z_pool, ys = lane_carry
                    at = (layer, index[i], 0, 0, 0)
                    # the state's way out of the pool and back into it is part
                    # of the pass over it: under the form's own scope
                    with jax.named_scope(form):
                        if context_encode:
                            s_in = jnp.zeros(s_pool.shape[2:], s_pool.dtype)
                            z_in = jnp.zeros(z_pool.shape[2:], z_pool.dtype)
                        else:
                            s_in = jax.lax.dynamic_slice(s_pool, at, (1, 1) + s_pool.shape[2:])[0, 0]
                            z_in = jax.lax.dynamic_slice(z_pool, at[:4], (1, 1) + z_pool.shape[2:])[0, 0]
                    qi, ki, vi, gi = (
                        jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                        for a in (q, k, v, log_g)
                    )
                    if recurrent:
                        y, s_out, z_out = retention_step(
                            s_in, z_in, qi[0], ki[0], vi[0], gi[0], eps)
                        y = y[None]
                    else:
                        y, s_out, z_out = retention_chunks(
                            s_in, z_in, qi, ki, vi, gi, live[i], eps)
                    with jax.named_scope(form):
                        return (
                            jax.lax.dynamic_update_slice(s_pool, s_out[None, None], at),
                            jax.lax.dynamic_update_slice(z_pool, z_out[None, None], at[:4]),
                            jax.lax.dynamic_update_index_in_dim(ys, y, i, 0),
                        )

                with jax.named_scope("retention"):
                    if one_pass:
                        y, s_pool, z_pool = retention_step_paged(
                            s_pool, z_pool, index, layer,
                            q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], eps)
                        y = y[:, None]
                    else:
                        s_pool, z_pool, y = jax.lax.fori_loop(
                            0, b, lane, (s_pool, z_pool, jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)))
                attn_out = attn.output(lp["attn"], y)
            x = x + attn_out
            h = norm(lp["mlp_norm"], x)
            return (x + self._mlp_block(lp, h), s_pool, z_pool), None

        x = model._embed()(params["embed"], tokens)
        x = constrain(x, P(BATCH_AXES, None, None))
        (x, s_new, z_new), _ = jax.lax.scan(
            layer_body, (x, cache.s, cache.z),
            (params["layers"], jnp.arange(c.num_layers, dtype=jnp.int32)),
        )
        x = norm(params["final_norm"], x)
        new_cache = StateCache(s=s_new, z=z_new)
        if return_hidden:
            return x, new_cache
        return model._logits(params, x), new_cache


@dataclasses.dataclass(frozen=True)
class LagunaDecode(MixtralDecode):
    """Decode-mode Laguna (:mod:`..models.laguna`): window and full attention
    layers in one stack, over a cache of two kinds (:class:`MixedKVCache`).

    A full layer reads and writes the block pool through the lane's
    ``block_tables`` like every other family, bounded by ``kv_limit``. A window
    layer reads and writes the window pool through ``window_tables`` — the
    lane's ring (never ``kv_limit`` rows of it) — and where no
    ``window_tables`` is given, through ``block_tables`` too. **One indexing
    rule serves both**: the row of position ``p`` is block ``(p // block_size)
    mod table width`` of the table, row ``p mod block_size``; a table as wide
    as the context never wraps. Read back, row ``r`` of a table of ``R`` rows
    holds, for a query at ``i``, the position ``i - ((i - r) mod R)`` — the
    newest one at or before ``i`` that lands there — and the mask admits it
    where it is not negative and, in a window layer, less than
    ``sliding_window`` behind ``i``. A ring of ``window - 1 + T`` rows keeps a
    block of ``T`` fresh rows (bucket padding included) off every row a live
    query of that block still sees.

    A row that ``block_tables`` sends to the null block — bucket padding past
    the allocated frontier, an idle lane of the decode batch — goes to the
    window pool's null block too, so a lane mid-prefill is not written into
    by the decode program that runs beside it.

    Both pools ride the layer loop as its carry, the layer folded into the
    row index: a donated cache is updated in place. The weights are one stack
    a layer shape, run in the published order (``models.laguna.layer_runs``).
    The dense slot cache (``InferenceEngine.generate``) keeps every layer at
    full length and the window is a mask only. ``paged_flash_decode`` has no
    lower bound and is never eligible; a layer's read of one row a lane is
    the block walk where :meth:`decode_read` says ``"kernel"`` — a full
    layer's over the lane's live blocks, a window layer's over the blocks of
    its ring that hold the window — and everywhere else the ring is gathered
    whole and the mask does the rest. Tree (speculative) blocks are refused."""

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.laguna import LagunaForCausalLM

        return LagunaForCausalLM(self.config)

    # -- cache ------------------------------------------------------------

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        c = self.config
        return (
            CacheKind("full", c.layers_of("full"), None),
            CacheKind("window", c.layers_of("window"), c.sliding_window),
        )

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype: Any = None,
        kv_cache_dtype: Optional[str] = None, window_blocks: Optional[int] = None,
    ) -> MixedKVCache:
        """``num_blocks`` sizes the full kind; ``window_blocks`` the window
        kind (as many again where not given: a table as wide as the context
        then serves both, ``benchmarks/check.py``'s call)."""
        c = self.config

        def pool(layers: int, blocks: int) -> PagedKVCache:
            # a k/v pool's shape is its layers, kv heads and head width alone
            sized = LlamaConfig(
                num_layers=layers, num_heads=c.num_kv_heads, num_kv_heads=c.num_kv_heads,
                head_dim=c.head_dim, dtype=c.dtype)
            return LlamaDecode(sized).init_paged_cache(blocks, block_size, dtype, kv_cache_dtype)

        return MixedKVCache(
            full=pool(c.layers_of("full"), num_blocks),
            window=pool(c.layers_of("window"), window_blocks or num_blocks),
        )

    def paged_cache_specs(self, quantized: bool = False) -> MixedKVCache:
        one = LlamaDecode.paged_cache_specs(self, quantized)
        return MixedKVCache(full=one, window=one)

    def forbidden_gather_shapes(self, batch: int, kv_limit: int):
        return set()

    def _paged_kernel_eligible(self, t: int, tree) -> bool:
        return False

    def decode_read(self, kind: CacheKind, quantized: bool = False) -> str:
        """``"kernel"`` for either kind over an unquantized pool, where
        :func:`_kernels_on_one_device`: one row a lane is then attended by
        :func:`..kernels.paged_attention_pallas.paged_decode_walk` — a full
        layer's over the lane's live blocks, a window layer's over the blocks
        of its ring that hold the window. What the program can see decides, no
        option: a block of several rows (``psfx``), an int8 pool, a mesh and
        the ``"reference"`` mode keep the block-wise gather and
        ``masked_attention``, the walk's plain twin."""
        return "kernel" if self._walks(quantized) else "gather"

    # -- forward ----------------------------------------------------------

    def forward(
        self, params: Params, cache: Any, tokens: jax.Array, positions: jax.Array,
        slots: Optional[jax.Array] = None, *, context_encode: bool = False,
        return_hidden: bool = False, tree=None, kv_limit: Optional[int] = None,
        block_tables: Optional[jax.Array] = None, row_live: Optional[jax.Array] = None,
        window_tables: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Any]:
        """tokens (b, T) at rows ``positions ..`` over ``cache`` — a
        :class:`MixedKVCache` under ``block_tables`` (and ``window_tables``,
        see the class), else the dense :class:`KVCache` of every layer;
        returns (logits (b, T, V) or the normed hidden, the cache updated)."""
        if tree is not None:
            raise NotImplementedError("tree verification over a ring of window rows")
        from neuronx_distributed_llama3_2_tpu.models.laguna import (
            FULL, WINDOW, layer_runs, scan_run,
        )

        c = self.config
        model = self._model()
        b, t = tokens.shape
        pos_block = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        paged = block_tables is not None
        if paged:
            bs = cache.full.block_size
            rope_len = block_tables.shape[1] * bs
            tables = {FULL: block_tables, WINDOW: block_tables}
            null_rows = None
            if window_tables is not None:
                tables[WINDOW] = window_tables
                null_rows = jnp.take_along_axis(block_tables, pos_block // bs, axis=1) == 0
            pools = {kind: _pool_pair(getattr(cache, kind)) for kind in (FULL, WINDOW)}
        else:
            if getattr(cache, "k_scale", None) is not None:
                raise ValueError("quantized KV storage is paged-only")
            if slots is None:
                slots = jnp.arange(b, dtype=jnp.int32)
            rope_len = cache.max_len
            pools = {"all": (cache.k, cache.v)}
        ropes = model._ropes(rope_len)
        norm = make_norm(c)
        tap = routing_tap.current()

        x = model._embed()(params["embed"], tokens)
        x = constrain(x, P(BATCH_AXES, None, None))
        for run in layer_runs(c):
            attn = model._layer(run.kind, run.sparse)._attn()
            sin, cos = ropes[run.kind]
            # the dense cache holds every layer; a pool the layers of its kind
            held, first = (run.kind, run.kind_first) if paged else ("all", run.layer)
            own_ring = paged and run.kind == WINDOW and window_tables is not None

            def body(carry, lp, j, run=run, attn=attn, sin=sin, cos=cos,
                     held=held, first=first, own_ring=own_ring):
                x, pools = carry
                h = norm(lp["attn_norm"], x)
                routes = self._early_routes(lp, h) if run.sparse else None
                with jax.named_scope("attn"), jax.named_scope(run.kind):
                    q, k, v = attn.project(lp["attn"], h, sin, cos, pos_block)
                    att, kc, vc = self._attend(
                        q, k, v, *pools[held], first + j, pos_block, slots,
                        window=attn.window(), context_encode=context_encode,
                        table=tables[run.kind] if paged else None,
                        limit=None if own_ring else kv_limit,
                        null_rows=null_rows if own_ring else None,
                    )
                    attn_out = attn.output(lp["attn"], h, att)
                x = x + attn_out
                h = norm(lp["mlp_norm"], x)
                if run.sparse:
                    x = x + MixtralDecode._mlp_block(self, lp, h, routes)
                else:
                    x = x + LlamaDecode._mlp_block(self, lp, h)
                return (x, {**pools, held: (kc, vc)}), (
                    None if tap is None or not run.sparse else tap.take_layer())

            (x, pools), counts = scan_run(body, (x, pools), params[run.stack], run)
            if tap is not None and run.sparse:
                tap.commit(counts, run.count)
        x = norm(params["final_norm"], x)
        if paged:
            new_cache = MixedKVCache(
                full=_pool_of(pools[FULL]), window=_pool_of(pools[WINDOW]))
        else:
            new_cache = type(cache)(*pools["all"])
        if return_hidden:
            return x, new_cache
        return model._logits(params, x), new_cache

    def _early_routes(self, lp: Params, h: jax.Array):
        """A layer's routes where the model makes them from its normed input
        ``h``, ahead of attention (:class:`SmallThinkerDecode`); None where the
        expert block routes the tensor it is given."""
        return None

    def _attend(
        self, q, k, v, kc, vc, layer, pos_block, slots, *, window, context_encode: bool,
        table, limit, null_rows,
    ):
        """Write the fresh rows k, v (b, T, NKV, D) of layer ``layer`` at
        ``pos_block`` and attend q (b, T, N, D) — over the fresh block alone
        under ``context_encode``, else over the rows read back (see the
        class). kc / vc: one kind's whole pool (L, blocks, block_size, NKV, D),
        a (payload, scale) pair each where quantized, read through ``table``
        (b, W) — or, ``table`` None, the dense cache (L, B, S, NKV, D) at
        ``slots``. ``limit`` bounds the rows read (None: all the table's, all
        the cache's), a block at a time through the view of a payload pool in
        which a block is its ``block_size * NKV`` rows of ``D`` — or, one row
        a lane where :meth:`_walks`, by the block walk over the blocks that
        hold the rows the lane sees; ``null_rows`` (b, T) sends those rows'
        writes to the null block (and tells the walk its null lanes). Returns
        (att (b, T, N, D), kc, vc)."""
        from neuronx_distributed_llama3_2_tpu.models.laguna import masked_attention, visible

        if table is None:
            with jax.named_scope("kv_write"):
                kc = kc.at[layer, slots[:, None], pos_block].set(k.astype(kc.dtype))
                vc = vc.at[layer, slots[:, None], pos_block].set(v.astype(vc.dtype))
            if not context_encode:
                with jax.named_scope("kv_read"):
                    k = kc[layer, slots, :limit].astype(q.dtype)
                    v = vc[layer, slots, :limit].astype(q.dtype)
                    ring_rows = kc.shape[2]
        else:
            quantized = isinstance(kc, tuple)
            walks = q.shape[1] == 1 and not context_encode and self._walks(quantized)
            (kc, ksc), (vc, vsc) = (kc, vc) if quantized else ((kc, None), (vc, None))
            nl, nb, bs = kc.shape[:3]
            width = table.shape[1]
            ring_rows = width * bs

            def rows(a):      # every layer's rows in one run: never a[layer]
                return a.reshape((nl * nb * bs,) + a.shape[3:])

            def blocks(a):
                # a block as its rows of the minor axis: a payload's bs * NKV
                # rows of D — whole rows whatever NKV is; asked for
                # (bs, NKV, D) slices, the compiler re-tiled a pool of under
                # 8 kv heads a layer a call — and a scale array's bs of NKV
                return a.reshape((nl * nb, -1) + a.shape[-1:])

            with jax.named_scope("kv_write"):
                block = jnp.take_along_axis(table, (pos_block // bs) % width, axis=1)
                if null_rows is not None:
                    block = jnp.where(null_rows, 0, block)
                at = (layer * nb + block) * bs + pos_block % bs
                if quantized:
                    from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (
                        kv_dequantize,
                        kv_quantize,
                    )

                    kq, ks = kv_quantize(k, kc.dtype)
                    vq, vs = kv_quantize(v, vc.dtype)
                    ksc = rows(ksc).at[at].set(ks).reshape(ksc.shape)
                    vsc = rows(vsc).at[at].set(vs).reshape(vsc.shape)
                    # the fresh block the prefill softmax sees is the round
                    # trip a later chunk reads back
                    k, v = kv_dequantize(kq, ks, q.dtype), kv_dequantize(vq, vs, q.dtype)
                else:
                    kq, vq = k.astype(kc.dtype), v.astype(vc.dtype)
                kc = rows(kc).at[at].set(kq).reshape(kc.shape)
                vc = rows(vc).at[at].set(vq).reshape(vc.shape)
            if walks:
                # one row a lane: the blocks that hold the rows it sees — the
                # lane's live ones, or the window's of its ring — are read
                # where they lie, nothing gathered
                from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
                    paged_decode_walk,
                )

                with jax.named_scope("sdpa"):
                    att = paged_decode_walk(
                        q[:, 0], kc, vc, table, pos_block[:, 0], layer, kv_limit=limit,
                        window=window,
                        null_lanes=None if null_rows is None else null_rows[:, 0])
                return att[:, None], kc, vc
            if not context_encode:
                with jax.named_scope("kv_read"):
                    # gathered a block at a time: a block's rows lie together
                    limit = ring_rows if limit is None else min(limit, ring_rows)
                    at = layer * nb + table[:, : -(-limit // bs)]

                    def read(a):
                        got = blocks(a)[at]                   # (b, blocks, block rows, ...)
                        return got.reshape((got.shape[0], -1) + a.shape[3:])[:, :limit]

                    if quantized:
                        k = kv_dequantize(read(kc), read(ksc), q.dtype)
                        v = kv_dequantize(read(vc), read(vsc), q.dtype)
                    else:
                        k, v = read(kc).astype(q.dtype), read(vc).astype(q.dtype)
            if quantized:
                kc, vc = (kc, ksc), (vc, vsc)
        with jax.named_scope("sdpa"):
            if context_encode:
                k_pos = pos_block[:, None, :]
            else:
                # the position row r holds, as a query at i reads it
                r = jnp.arange(k.shape[1], dtype=jnp.int32)
                k_pos = pos_block[..., None] - (pos_block[..., None] - r) % ring_rows
            att = masked_attention(q, k, v, visible(pos_block, k_pos, window))
        return att, kc, vc


@dataclasses.dataclass(frozen=True)
class SmallThinkerDecode(LagunaDecode):
    """Decode-mode SmallThinker (:mod:`..models.smallthinker`):
    :class:`LagunaDecode`'s two kinds of cache, ring rule, ``_attend`` and
    layer loop, with this family's layer — a full layer's q and k carry no
    position (the model's ``_ropes`` gives that kind no table), no output
    gate — and its one change to the loop: **the layer's experts are routed
    from its normed input, before the attention block** (``moe/router`` ahead
    of ``attn`` in a layer's trace), and the post-attention state is
    dispatched by those routes. The window (4,096 as published) is 8 x
    Laguna's: a decode step walks up to 257 blocks of a lane's 288-block ring."""

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.smallthinker import (
            SmallThinkerForCausalLM,
        )

        return SmallThinkerForCausalLM(self.config)

    def _early_routes(self, lp: Params, h: jax.Array):
        return self._moe().route(lp["moe"], h)


@dataclasses.dataclass(frozen=True)
class JambaDecode(LlamaDecode):
    """Decode-mode Jamba (:mod:`..models.jamba`): Mamba-1 state-space layers
    and attention layers in one stack, over a :class:`HybridCache`.

    An attention layer writes and reads the block pool through the lane's
    ``block_tables``, bounded by ``kv_limit``, a block at a time — **with no
    rotary table**: q and k are cached and attended as projected. A
    state-space layer reads and writes its lane's slot of the state kind:
    the one ``state_tables`` names, or, where none is given, the one the
    table's first block names (a table as wide as the context then serves
    both kinds: ``benchmarks/check.py``'s call). A lane whose first fresh row
    ``block_tables`` sends to the null block — an idle lane of the decode
    batch, a lane mid-prefill beside it, a warm-up call — goes to the null
    slot, so the decode program never writes into a state a prefill is
    building.

    Which form a state-space layer runs follows from the block's shape. One
    token a lane (``pdecode``) is the step form: where
    :meth:`uses_state_kernel`, **one Mosaic call a layer that visits the live
    lanes' slots where they lie** (:mod:`..kernels.ssm_step_pallas`: ``h`` in,
    updated, ``y`` taken from it, back to the same slot; a lane on the null
    slot moves nothing), the lanes' tails gathered and scattered beside it in
    lane order; elsewhere (a mesh, the ``"reference"`` mode) one pass a layer
    over **every slot where it lies** — the lanes' rows go to their slots, the
    mixer runs in slot order — or, for lanes fewer than half the slots, a
    gather of theirs. Either way a slot no lane names comes back bit for bit.
    A block of rows is the scan over its lane's slot (:meth:`chunk_scan`: one
    Mosaic call a layer on one device, a ``lax.scan`` elsewhere) — from the
    **zero state and a zero tail** under ``context_encode`` (``pctx``: a slot
    still holds its last request's past), from the slot's otherwise
    (``psfx``). ``row_live`` is the count of real rows of a padded block: rows
    at or past it leave the state *and* the convolution's tail untouched.

    Both kinds ride the layer loop as its carry, the layer folded into the
    row index: a donated cache is updated in place and a program's
    temporaries are the lanes' states of one layer, never the pool. Tree
    (speculative) blocks and a quantized pool are refused; a rejected draft
    cannot be taken back out of a state. ``tp > 1`` is not run."""

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.jamba import JambaForCausalLM

        return JambaForCausalLM(self.config)

    # -- cache ------------------------------------------------------------

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        from neuronx_distributed_llama3_2_tpu.models.jamba import ATTENTION, MAMBA

        c = self.config
        return (
            CacheKind("rows", c.layers_of(ATTENTION), None),
            CacheKind("state", c.layers_of(MAMBA), 0, state=True),
        )

    def cache_row_dims(self) -> Tuple[int, int, int]:
        return 2, 1, self.config.num_kv_heads * self.config.head_dim

    def _state(self, slots: int, dtype: Any = None) -> SsmState:
        from neuronx_distributed_llama3_2_tpu.models.jamba import MAMBA, STATE_DTYPE

        c = self.config
        lead = (c.layers_of(MAMBA), slots)
        return SsmState(
            h=jnp.zeros(lead + (c.mamba_d_state, c.d_inner), dtype or STATE_DTYPE),
            tail=jnp.zeros(
                lead + (TAIL_ROWS, tail_width((c.mamba_d_conv - 1) * c.d_inner)), c.dtype),
        )

    def _rows(self, lead: Tuple[int, int], dtype: Any = None) -> PagedKVCache:
        from neuronx_distributed_llama3_2_tpu.models.jamba import ATTENTION

        c = self.config
        shape = (c.layers_of(ATTENTION),) + lead + (c.num_kv_heads * c.head_dim,)
        dtype = dtype or c.dtype
        return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))

    def init_cache(self, max_batch: int, max_len: int, dtype: Any = None) -> HybridCache:
        """The dense slot cache: rows (L_a, B, S_max, NKV · D), a state a slot."""
        return HybridCache(rows=self._rows((max_batch, max_len), dtype), state=self._state(max_batch))

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype: Any = None,
        kv_cache_dtype: Optional[str] = None, state_blocks: Optional[int] = None,
    ) -> HybridCache:
        """``num_blocks`` sizes the attention layers' pool; ``state_blocks``
        the slots of the state kind (as many again where not given). ``dtype``
        is both kinds': a state in less than float32 is what the benchmark's
        check has to fail."""
        if kv_cache_dtype not in (None, "bf16"):
            raise NotImplementedError(
                f"kv_cache_dtype={kv_cache_dtype!r}: a state-space layer's state has no "
                "quantized form — it is a running sum of every row so far, "
                "not rows with a scale each"
            )
        return HybridCache(
            rows=self._rows((num_blocks, block_size), dtype),
            state=self._state(state_blocks or num_blocks, dtype),
        )

    def paged_cache_specs(self, quantized: bool = False) -> HybridCache:
        return HybridCache(
            rows=PagedKVCache(k=P(), v=P()), state=SsmState(h=P(), tail=P()))

    def cache_specs(self, max_batch: Optional[int] = None) -> HybridCache:
        return self.paged_cache_specs()

    def forbidden_gather_shapes(self, batch: int, kv_limit: int):
        return set()

    def _paged_kernel_eligible(self, t: int, tree) -> bool:
        return False

    def uses_state_kernel(self) -> bool:
        """Whether the step form (one token a lane: ``pdecode``) runs
        :func:`..kernels.ssm_step_pallas.ssm_state_step` — one Mosaic call a
        state-space layer that visits the live lanes' slots where they lie —
        which it does where :func:`_kernels_on_one_device` (and the channels
        are whole lanes); a mesh and the ``"reference"`` mode keep the pass
        over every slot around ``selective_step``."""
        from neuronx_distributed_llama3_2_tpu.kernels.ssm_step_pallas import state_step_fits

        return _kernels_on_one_device() and state_step_fits(self.config.d_inner)

    def decode_read(self, kind: CacheKind, quantized: bool = False) -> str:
        """The state kind: ``"kernel"`` where :meth:`uses_state_kernel` — a
        visit a live lane's slot — else ``"pass"``, one pass a layer over the
        lanes' slots, every lane's, live or not. The rows: ``"gather"``, a
        block at a time through the table."""
        if kind.state:
            return "kernel" if self.uses_state_kernel() else "pass"
        return "gather"

    def chunk_scan(self) -> str:
        """How a block of rows (``pctx`` / ``psfx``) goes through a
        state-space layer's recurrence: ``"kernel"`` — one
        :func:`..kernels.ssm_scan_pallas.ssm_chunk_scan` a layer — where
        :func:`_kernels_on_one_device`, else ``"loop"``, a ``lax.scan`` a row
        (a mesh, the ``"reference"`` mode). ``pdecode``'s own kernel is
        :meth:`uses_state_kernel`'s."""
        return "kernel" if _kernels_on_one_device() else "loop"

    # -- forward ----------------------------------------------------------

    def forward(
        self, params: Params, cache: HybridCache, tokens: jax.Array, positions: jax.Array,
        slots: Optional[jax.Array] = None, *, context_encode: bool = False,
        return_hidden: bool = False, tree=None, kv_limit: Optional[int] = None,
        block_tables: Optional[jax.Array] = None, row_live: Optional[jax.Array] = None,
        state_tables: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, HybridCache]:
        """tokens (b, T) at rows ``positions ..`` over ``cache`` (see the
        class); returns (logits (b, T, V) or the normed hidden, the cache
        updated)."""
        if tree is not None:
            raise NotImplementedError("tree verification over a state-space layer's state")
        from neuronx_distributed_llama3_2_tpu.kernels.ssm_step_pallas import visits
        from neuronx_distributed_llama3_2_tpu.models.jamba import (
            ATTENTION, MAMBA, MambaMixer, layer_runs,
        )
        from neuronx_distributed_llama3_2_tpu.models.laguna import scan_run
        from neuronx_distributed_llama3_2_tpu.models.llama import LlamaAttention

        c = self.config
        model = self._model()
        mixer, attn, norm = MambaMixer(c), LlamaAttention(c), make_norm(c)
        b, t = tokens.shape
        pos_block = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        if block_tables is None:
            index = slots = jnp.arange(b, dtype=jnp.int32) if slots is None else slots
        elif state_tables is None:
            index = block_tables[:, 0]
        else:
            first = jnp.take_along_axis(
                block_tables, positions[:, None] // cache.block_size, axis=1)[:, 0]
            index = jnp.where(first == 0, 0, state_tables[:, 0])
        live = jnp.full((b,), t, jnp.int32) if row_live is None else row_live
        form = "step" if t == 1 else "scan"
        scan_kernel = self.chunk_scan() == "kernel"
        state_slots = cache.state.h.shape[1]
        # one token a lane (a decode batch, ``benchmarks/check.py``'s one lane
        # over the engine's pool): where the kernel runs, a visit a live
        # lane's slot — a lane on the null slot (idle, or mid-prefill beside
        # the batch) is not live; the dense cache has no null slot. Elsewhere
        # lanes that are most of the slots pass over every slot of a layer
        # where it lies, read once and written once, nothing gathered. A
        # block of rows of one lane (a prefill) takes its slot out and puts it
        # back, and so do a few lanes over many slots off the kernel
        step = t == 1 and not context_encode
        step_kernel = step and self.uses_state_kernel()
        every_slot = step and not step_kernel and 2 * b >= state_slots
        if step_kernel:
            alive = live > 0 if block_tables is None else (live > 0) & (index != 0)
            alive_rows = alive.astype(jnp.int32)
            lane_walk, count = visits(alive)
            slot_walk = index[lane_walk]
        # a slot's tail values, and the places of its folded rows past them
        values = (c.mamba_d_conv - 1) * c.d_inner
        unused = math.prod(cache.state.tail.shape[2:]) - values

        def flat(a):        # every layer's slots in one run: never a[layer]
            return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

        def fold(tail):     # (n, K - 1, D) -> a slot's rows of whole lanes, the places past the values zero
            padded = jnp.pad(tail.reshape(tail.shape[0], -1), ((0, 0), (0, unused)))
            return padded.reshape((tail.shape[0],) + cache.state.tail.shape[2:])

        def unfold(rows):   # and back
            flat_rows = rows.reshape(rows.shape[0], -1)[:, :values]
            return flat_rows.reshape(rows.shape[0], c.mamba_d_conv - 1, c.d_inner)

        def mamba(carry, lp, j, first):
            x, rows, (h_pool, tail_pool) = carry
            hn = norm(lp["attn_norm"], x)
            layer = first + j
            with jax.named_scope("attn"), jax.named_scope("ssm"):
                # the states' way out of the pool and back into it is part of
                # the pass over them: under the form's own scope
                if every_slot:
                    # the layer's slots where they lie, in slot order: the
                    # lanes' rows go to their slots, a slot no lane names has
                    # no live row and comes back as it was, bit for bit
                    named = jnp.zeros((state_slots,), jnp.int32).at[index].set(1)
                    by_slot = jnp.zeros((state_slots,) + hn.shape[1:], hn.dtype).at[index].set(hn)
                    u, g = mixer.project(lp[MAMBA], by_slot)
                    with jax.named_scope(form):
                        h_in = jax.lax.dynamic_index_in_dim(h_pool, layer, 0, keepdims=False)
                        tail_in = unfold(
                            jax.lax.dynamic_index_in_dim(tail_pool, layer, 0, keepdims=False))
                    out, h_out, tail_out = mixer.mix(lp[MAMBA], u, g, h_in, tail_in, named)
                    with jax.named_scope(form):
                        h_pool = jax.lax.dynamic_update_index_in_dim(h_pool, h_out, layer, 0)
                        tail_pool = jax.lax.dynamic_update_index_in_dim(
                            tail_pool, fold(tail_out), layer, 0)
                    out = out[index]
                elif step_kernel:
                    # the tails out and back in lane order, a lane that is
                    # not live's as it came; ``h`` stays where it lies
                    at = layer * state_slots + index
                    u, g = mixer.project(lp[MAMBA], hn)
                    with jax.named_scope(form):
                        tail_in = unfold(flat(tail_pool)[at])
                    out, h_flat, tail_out = mixer.mix(
                        lp[MAMBA], u, g, flat(h_pool), tail_in, alive_rows,
                        walk=(layer * state_slots + slot_walk, lane_walk, count))
                    with jax.named_scope(form):
                        h_pool = h_flat.reshape(h_pool.shape)
                        tail_pool = flat(tail_pool).at[at].set(fold(tail_out)).reshape(tail_pool.shape)
                else:
                    at = layer * state_slots + index
                    u, g = mixer.project(lp[MAMBA], hn)
                    with jax.named_scope(form):
                        h_in, tail_in = self._carried(
                            flat(h_pool), flat(tail_pool), at, context_encode, unfold)
                    out, h_out, tail_out = mixer.mix(
                        lp[MAMBA], u, g, h_in, tail_in, live, kernel=scan_kernel)
                    with jax.named_scope(form):
                        h_pool = flat(h_pool).at[at].set(h_out).reshape(h_pool.shape)
                        tail_pool = flat(tail_pool).at[at].set(fold(tail_out)).reshape(tail_pool.shape)
            return x + out, rows, (h_pool, tail_pool)

        def attention(carry, lp, j, first):
            x, (kc, vc), state = carry
            hn = norm(lp["attn_norm"], x)
            with jax.named_scope("attn"):
                with jax.named_scope("qkv"):
                    q, k, v = attn._qkv()(lp[ATTENTION]["qkv"], hn)
                    q = q.reshape(b, t, c.num_heads, c.head_dim)
                    k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
                    # no rotary table: q and k go on as projected
                    q, k = attn._apply_rope(q, k, None, None, pos_block)
                    k = k.reshape(b, t, -1)
                att, kc, vc = self._attend_rows(
                    q, k, v, kc, vc, first + j, pos_block, slots,
                    context_encode=context_encode, table=block_tables, limit=kv_limit)
                with jax.named_scope("o_proj"):
                    out = attn._o()(lp[ATTENTION]["o"], att.reshape(b, t, -1))
            return x + out, (kc, vc), state

        mixers = {MAMBA: mamba, ATTENTION: attention}
        x = model._embed()(params["embed"], tokens)
        x = constrain(x, P(BATCH_AXES, None, None))
        carry = (x, (cache.rows.k, cache.rows.v), tuple(cache.state))
        for run in layer_runs(c):

            def body(carry, lp, j, run=run):
                x, rows, state = mixers[run.kind](carry, lp, j, run.kind_first)
                hn = norm(lp["mlp_norm"], x)
                return (x + self._mlp_block(lp, hn), rows, state), None

            carry, _ = scan_run(body, carry, params[run.stack], run)
        x, rows, state = carry
        x = norm(params["final_norm"], x)
        new_cache = HybridCache(rows=PagedKVCache(*rows), state=SsmState(*state))
        if return_hidden:
            return x, new_cache
        return model._logits(params, x), new_cache

    def _carried(self, h_flat, tail_flat, at, fresh: bool, unfold):
        """What a block of rows starts from, a lane: zeros where ``fresh``
        (``pctx``: the slot still holds its last request's past), else the
        state and the tail (``unfold`` of its rows) at ``at`` of the pools
        seen as one run of slots. h (b, N, D), tail (b, K − 1, D)."""
        from neuronx_distributed_llama3_2_tpu.models.jamba import MambaMixer

        if fresh:
            return MambaMixer(self.config).zero_state(at.shape[0], h_flat.dtype, tail_flat.dtype)
        return h_flat[at], unfold(tail_flat[at])

    def _attend_rows(
        self, q, k, v, kc, vc, layer, pos_block, slots, *, context_encode: bool, table, limit,
    ):
        """Write the fresh rows k, v (b, T, NKV · D) of attention layer
        ``layer`` at ``pos_block`` and attend q (b, T, N, D) — over the fresh
        block alone under ``context_encode``, else over the rows read back.
        kc / vc: the whole pool (L_a, blocks, block_size, NKV · D) read through
        ``table`` (b, W) a block at a time — a block's rows lie together — or,
        ``table`` None, the dense cache (L_a, B, S, NKV · D) at ``slots``.
        ``limit`` bounds the rows read. Returns (att (b, T, N, D), kc, vc)."""
        from neuronx_distributed_llama3_2_tpu.models.laguna import masked_attention, visible
        from neuronx_distributed_llama3_2_tpu.models.llama import core_attention

        c = self.config
        b, t = pos_block.shape

        def heads(a):
            return a.reshape(a.shape[:2] + (c.num_kv_heads, c.head_dim)).astype(q.dtype)

        with jax.named_scope("kv_write"):
            if table is None:
                kc = kc.at[layer, slots[:, None], pos_block].set(k.astype(kc.dtype))
                vc = vc.at[layer, slots[:, None], pos_block].set(v.astype(vc.dtype))
            else:
                nl, nb, bs, width = kc.shape
                block = jnp.take_along_axis(table, pos_block // bs, axis=1)
                at = (layer * nb + block) * bs + pos_block % bs
                kc = kc.reshape(-1, width).at[at].set(k.astype(kc.dtype)).reshape(kc.shape)
                vc = vc.reshape(-1, width).at[at].set(v.astype(vc.dtype)).reshape(vc.shape)
        if context_encode:
            with jax.named_scope("sdpa"):
                return core_attention(q, heads(k), heads(v), causal=True), kc, vc
        with jax.named_scope("kv_read"):
            if table is None:
                k_all, v_all = kc[layer, slots, :limit], vc[layer, slots, :limit]
            else:
                limit = table.shape[1] * bs if limit is None else limit
                at = layer * nb + table[:, : -(-limit // bs)]

                def read(a):
                    got = a.reshape(nl * nb, bs, width)[at]             # (b, blocks, bs, W)
                    return got.reshape(b, -1, width)[:, :limit]

                k_all, v_all = read(kc), read(vc)
        with jax.named_scope("sdpa"):
            seen = visible(pos_block, jnp.arange(k_all.shape[1], dtype=jnp.int32), None)
            return masked_attention(q, heads(k_all), heads(v_all), seen), kc, vc


# rows of a chunk's sparse read a loop trip attends over: 32 blocks of 64
SPARSE_TILE_ROWS = 2048
# slots of the Lightning state where :meth:`SalaDecode.init_paged_cache` is
# given no count (``benchmarks/check.py``'s pools): the null slot and one a
# lane up to 32 lanes — 2.1 MB a slot a layer at the published widths, where
# one a *block* would be gigabytes
DEFAULT_STATE_SLOTS = 33


@dataclasses.dataclass(frozen=True)
class SalaDecode(LlamaDecode):
    """Decode-mode MiniCPM-SALA (:mod:`..models.minicpm_sala`): block-sparse
    softmax attention layers and Lightning linear-attention layers in one
    stack named layer by layer, over a :class:`HybridCache` of
    :class:`SparseRows` and a :class:`MatrixState`, paged only.

    A **sparse layer** writes its fresh k / v rows through the lane's
    ``block_tables`` and then the pooled keys of the kernels those rows
    complete — a kernel's first rows may lie in an earlier call's, so its rows
    are read back from the pool. Then it selects
    (:func:`..models.minicpm_sala.select_blocks` over the lane's pooled keys,
    bounded by ``kv_limit``) and reads: one token a lane (``pdecode``) gathers
    **the chosen blocks alone** — ``sparse_topk`` a kv group whatever the
    context — and a block of rows (``pctx`` / ``psfx``) walks the context a
    tile at a time with a running max and sum, the selection a per-(row,
    block) mask, so no (heads, rows, context) array exists at any context:
    one :func:`..kernels.sparse_chunk_pallas.sparse_chunk_attend` a layer over
    the rung's rows gathered in order, a tile's scores never out of VMEM,
    where :meth:`chunk_read` says ``"kernel"`` and the shape fits; else
    :func:`..models.minicpm_sala.attend_tiles`, a ``lax.scan`` over tiles of
    :data:`SPARSE_TILE_ROWS` rows (a mesh, the ``"reference"`` mode: the
    kernel's plain twin). ``pctx`` reads its own rows back through the
    table like any other call: the rule that picks a row's blocks is the same
    at every row, so the result does not depend on how a prompt was chunked.
    No rotary table touches these layers.

    A **Lightning layer** reads and writes its lane's slot of the state kind
    (``state_tables``'; where none is given lane ``i``'s is slot ``1 + i``):
    one token a lane is the step form as a pass over every slot of a layer
    where it lies (a slot no live lane names comes back bit for bit); a block
    of rows is the chunk form — from the **zero state** under
    ``context_encode`` (a slot still holds its last request's past), from the
    slot's otherwise. ``row_live`` is the count of real rows of a padded
    block: rows at or past it leave the state untouched, and complete no
    kernel. A lane whose first fresh row ``block_tables`` sends to the null
    block goes to the null slot (see :class:`JambaDecode`).

    Both kinds ride the layer loop as its carry, the layer folded into the
    row index. Refused, with the reason: tree (speculative) blocks and a
    quantized pool (a state has neither rejected rows to take back nor a scale
    a row), the dense slot cache (the selection is addressed through a block
    table), ``tp > 1`` (two kv heads, and a bare gather of chosen blocks a kv
    group, are not partitioned), and a pool whose block is not the selection's."""

    # shardlint SL002 — see LlamaDecode: the refusal below reads the same
    # parallel state the inherited traces do
    __layout_deps__ = LlamaDecode.__layout_deps__

    def __post_init__(self):
        from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

        if (
            parallel_state.model_parallel_is_initialized()
            and parallel_state.get_tensor_model_parallel_size() > 1
        ):
            raise NotImplementedError(
                "SalaDecode under tp > 1: the sparse layers have two kv heads and "
                "gather a kv group's chosen blocks whole; neither is partitioned")

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import SalaForCausalLM

        return SalaForCausalLM(self.config)

    # -- cache ------------------------------------------------------------

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import LIGHTNING, SPARSE

        c = self.config
        return (
            CacheKind("rows", c.layers_of(SPARSE), None),
            CacheKind("state", c.layers_of(LIGHTNING), 0, state=True),
        )

    def cache_row_dims(self) -> Tuple[int, int, int]:
        return 2, 1, self.config.num_kv_heads * self.config.head_dim

    def init_cache(self, max_batch: int, max_len: int, dtype: Any = None):
        raise NotImplementedError(
            "SalaDecode has no dense slot cache: a sparse layer's selection is addressed "
            "through a block table — serve it with the paged engine")

    def init_paged_cache(
        self, num_blocks: int, block_size: int, dtype: Any = None,
        kv_cache_dtype: Optional[str] = None, state_blocks: Optional[int] = None,
    ) -> HybridCache:
        """``num_blocks`` sizes the sparse layers' pool, pooled keys included;
        ``state_blocks`` the slots of the state kind (where not given, as many
        as the blocks up to :data:`DEFAULT_STATE_SLOTS`). ``dtype`` is both
        kinds': a state in less than float32 is what the benchmark's check has
        to fail."""
        from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (
            LIGHTNING, SPARSE, STATE_DTYPE,
        )

        c = self.config
        if kv_cache_dtype not in (None, "bf16"):
            raise NotImplementedError(
                f"kv_cache_dtype={kv_cache_dtype!r}: a Lightning layer's state is a running "
                "sum of every row so far, not rows with a scale each, and the pooled "
                "keys are means of rows")
        if block_size != c.sparse_block_size:
            raise ValueError(
                f"block_size {block_size}: a pool block is one selection block "
                f"({c.sparse_block_size} rows), so that a choice of blocks is a choice "
                "of table entries")
        width = c.num_kv_heads * c.head_dim
        rows = (c.layers_of(SPARSE), num_blocks, c.num_kv_heads, block_size, c.head_dim)
        kernels = num_blocks * c.kernels_per_block
        tile = math.lcm(16, c.kernels_per_block)
        pooled = (c.layers_of(SPARSE), tile * -(-kernels // tile), width)
        slots = state_blocks or min(num_blocks, DEFAULT_STATE_SLOTS)
        state = (c.layers_of(LIGHTNING), slots, c.lightning_heads, c.head_dim, c.head_dim)
        row_dtype = dtype or c.dtype
        return HybridCache(
            rows=SparseRows(
                k=jnp.zeros(rows, row_dtype), v=jnp.zeros(rows, row_dtype),
                pooled=jnp.zeros(pooled, row_dtype)),
            state=MatrixState(s=jnp.zeros(state, dtype or STATE_DTYPE)),
        )

    def paged_cache_specs(self, quantized: bool = False) -> HybridCache:
        return HybridCache(rows=SparseRows(k=P(), v=P(), pooled=P()), state=MatrixState(s=P()))

    def cache_specs(self, max_batch: Optional[int] = None) -> HybridCache:
        return self.paged_cache_specs()

    def forbidden_gather_shapes(self, batch: int, kv_limit: int):
        return set()

    def _paged_kernel_eligible(self, t: int, tree) -> bool:
        return False

    def decode_read(self, kind: CacheKind, quantized: bool = False) -> str:
        """Both kinds ``"gather"``: the lanes' states through their slots, and
        of the rows the chosen blocks alone, through the table."""
        return "gather"

    def chunk_scan(self) -> str:
        """How a block of rows goes through a Lightning layer: ``"chunk"``,
        the matmul form over the block and the carried state."""
        return "chunk"

    def chunk_read(self) -> str:
        """How a block of rows (``pctx`` / ``psfx``) reads a sparse layer's
        context: ``"kernel"`` — one
        :func:`..kernels.sparse_chunk_pallas.sparse_chunk_attend` a layer —
        where :func:`_kernels_on_one_device`, else ``"tiles"``,
        ``attend_tiles``' ``lax.scan`` (a mesh, the ``"reference"`` mode).
        ``pdecode`` gathers its chosen blocks either way."""
        return "kernel" if _kernels_on_one_device() else "tiles"

    def _chunk_limit(self, t: int, limit: Optional[int]) -> int:
        bs = self.config.sparse_block_size
        return bs * -(-t // bs) if limit is None else limit

    def _chunk_kernel_takes(self, t: int, limit: int) -> bool:
        from neuronx_distributed_llama3_2_tpu.kernels.sparse_chunk_pallas import chunk_attend_fits

        return self.chunk_read() == "kernel" and chunk_attend_fits(t, limit, self.config.sparse_block_size)

    def chunk_tiles(self, t: int, start: int, limit: Optional[int]) -> Tuple[bool, int, int]:
        from neuronx_distributed_llama3_2_tpu.kernels.sparse_chunk_pallas import kv_tile

        limit = self._chunk_limit(t, limit)
        if self._chunk_kernel_takes(t, limit):
            tile = kv_tile(limit)
            return True, min((start + t - 1) // tile + 1, limit // tile), limit // tile
        tiles = -(-limit // SPARSE_TILE_ROWS)           # the scan computes every tile of the rung
        return False, tiles, tiles

    def selected_rows(self, context: int) -> Tuple[int, int]:
        c = self.config
        bs, p = c.sparse_block_size, context - 1
        behind = p // bs + 1
        first = max(p - c.sparse_window + 1, 0) // bs
        forced = min(behind, behind - first + min(c.sparse_init_blocks, first))
        taken = min(behind, c.sparse_topk)
        # every taken block whole but the one the query's own row lies in
        return (taken - 1) * bs + p % bs + 1, forced

    # -- forward ----------------------------------------------------------

    def forward(
        self, params: Params, cache: HybridCache, tokens: jax.Array, positions: jax.Array,
        slots: Optional[jax.Array] = None, *, context_encode: bool = False,
        return_hidden: bool = False, tree=None, kv_limit: Optional[int] = None,
        block_tables: Optional[jax.Array] = None, row_live: Optional[jax.Array] = None,
        state_tables: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, HybridCache]:
        """tokens (b, T) at rows ``positions ..`` over ``cache`` (see the
        class); returns (logits (b, T, V) or the normed hidden, the cache
        updated)."""
        if tree is not None:
            raise NotImplementedError("tree verification over a Lightning layer's state")
        if block_tables is None:
            raise NotImplementedError("SalaDecode is paged only: pass block_tables")
        from neuronx_distributed_llama3_2_tpu.models.laguna import scan_run
        from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (
            LIGHTNING, SPARSE, SalaMixer, layer_runs, lightning_chunk, lightning_slopes,
            lightning_step,
        )

        c = self.config
        model = self._model()
        norm = make_norm(c)
        mixers = {kind: SalaMixer(c, kind) for kind in (SPARSE, LIGHTNING)}
        b, t = tokens.shape
        bs = cache.block_size
        pos_block = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        state_slots = cache.state.s.shape[1]
        if state_tables is None:
            if state_slots < b + 1:
                raise ValueError(
                    f"{state_slots} state slots for {b} lanes and no state_tables: "
                    "lane i's slot is 1 + i")
            own = 1 + jnp.arange(b, dtype=jnp.int32)
        else:
            own = state_tables[:, 0]
        first = jnp.take_along_axis(block_tables, positions[:, None] // bs, axis=1)[:, 0]
        index = jnp.where(first == 0, 0, own)
        live = jnp.full((b,), t, jnp.int32) if row_live is None else row_live
        # one token a lane passes over every slot of a layer where it lies,
        # read once and written once, nothing gathered (:class:`JambaDecode`'s
        # pass); a block of rows of one lane takes its slot out and puts it back
        step = t == 1 and not context_encode
        if step:        # the slots some live lane names; never the null slot
            named = jnp.zeros((state_slots,), bool).at[index].set(live > 0).at[0].set(False)
        width = block_tables.shape[1] * bs
        # rows a sparse layer may read: the rung, or — a context-encode call
        # reads its own rows back — the block's own, in whole blocks
        limit = self._chunk_limit(t, None) if context_encode else min(kv_limit or width, width)
        sin, cos = self._rope_tables(width)
        slopes = lightning_slopes(c.lightning_heads)
        scale = c.residual_scale

        def lightning(carry, lp, j, first_layer):
            x, rows, (s_pool,) = carry
            hn = norm(lp["attn_norm"], x)
            with jax.named_scope("attn"):
                q, k, v = mixers[LIGHTNING].project(lp["attn"], hn, sin, cos, pos_block)
                # a state's way out of its slot and back is part of the form
                with jax.named_scope("lightning"):
                    if step:
                        with jax.named_scope("step"):
                            # the layer's slots where they lie, in slot order:
                            # the lanes' rows go to their slots, a slot no live
                            # lane names comes back as it was, bit for bit
                            to_slots = lambda a: jnp.zeros(  # noqa: E731
                                (state_slots,) + a.shape[2:], a.dtype).at[index].set(a[:, 0])
                            o, s_out = lightning_step(
                                to_slots(q), to_slots(k), to_slots(v),
                                jax.lax.dynamic_index_in_dim(s_pool, first_layer + j, 0, keepdims=False),
                                named, slopes)
                            o = o[index][:, None]
                            s_pool = jax.lax.dynamic_update_index_in_dim(s_pool, s_out, first_layer + j, 0)
                    else:
                        with jax.named_scope("chunk"):
                            flat = s_pool.reshape((-1,) + s_pool.shape[2:])     # every layer's slots in one run
                            at = (first_layer + j) * state_slots + index
                            s_in = jnp.zeros((b,) + flat.shape[1:], flat.dtype) if context_encode else flat[at]
                            o, s_out = lightning_chunk(q, k, v, s_in, live, slopes)
                            s_pool = flat.at[at].set(s_out).reshape(s_pool.shape)
                out = mixers[LIGHTNING].output(lp["attn"], hn, o)
            return x + scale * out, rows, (s_pool,)

        def sparse(carry, lp, j, first_layer):
            x, rows, state = carry
            hn = norm(lp["attn_norm"], x)
            with jax.named_scope("attn"):
                q, k, v = mixers[SPARSE].project(lp["attn"], hn, None, None, pos_block)
                att, rows = self._attend_sparse(
                    q, k, v, rows, first_layer + j, pos_block, live, block_tables, limit)
                out = mixers[SPARSE].output(lp["attn"], hn, att)
            return x + scale * out, rows, state

        bodies = {LIGHTNING: lightning, SPARSE: sparse}
        x = model.embed(params, tokens)
        x = constrain(x, P(BATCH_AXES, None, None))
        carry = (x, tuple(cache.rows), tuple(cache.state))
        for run in layer_runs(c):

            def body(carry, lp, j, run=run):
                x, rows, state = bodies[run.kind](carry, lp, j, run.kind_first)
                hn = norm(lp["mlp_norm"], x)
                return (x + scale * self._mlp_block(lp, hn), rows, state), None

            carry, _ = scan_run(body, carry, params[run.stack], run)
        x, rows, state = carry
        x = norm(params["final_norm"], x)
        new_cache = HybridCache(rows=SparseRows(*rows), state=MatrixState(*state))
        if return_hidden:
            return x, new_cache
        return model._logits(params, x), new_cache

    def _attend_sparse(self, q, k, v, rows, layer, pos_block, live, table, limit: int):
        """One sparse layer over the pool: write the fresh rows k, v (b, T,
        NKV, D) at ``pos_block`` and the pooled keys they complete, select, and
        attend q (b, T, N, D) over the chosen blocks of the first ``limit``
        rows. ``rows``: the pool's (k, v, pooled). Returns (att (b, T, N, D),
        rows)."""
        from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (
            attend_tiles, block_mask, pool_keys, select_blocks,
        )

        c = self.config
        kc, vc, pc = rows
        b, t = pos_block.shape
        nl, nb, nkv, bs, d = kc.shape
        width, per = nkv * d, c.kernels_per_block
        heads = jnp.arange(nkv, dtype=jnp.int32)

        def rows_at(at):        # rows ``at`` (b, ...) of the layer's blocks, a kv head each: (b, ..., NKV)
            return ((layer * nb + block_of(at // bs))[..., None] * nkv + heads) * bs + (at % bs)[..., None]
        stride, size, kernel_rows = c.kernel_stride, c.kernel_size, pc.shape[1]
        wide = table.shape[1]

        def block_of(at):       # table entry of logical block ``at`` (b, ...), past the table the null block
            got = jnp.take_along_axis(table, jnp.minimum(at, wide - 1).reshape(b, -1), axis=1)
            return jnp.where(at < wide, got.reshape(at.shape), 0)

        with jax.named_scope("kv_write"):
            at = rows_at(pos_block)
            kc = kc.reshape(-1, d).at[at].set(k.astype(kc.dtype)).reshape(kc.shape)
            vc = vc.reshape(-1, d).at[at].set(v.astype(vc.dtype)).reshape(vc.shape)
        with jax.named_scope("sparse"):
            with jax.named_scope("pool_keys"):
                # the kernels whose last row is one of the live fresh rows: at
                # most one a stride; their rows are read back from the pool
                start = pos_block[:, 0]
                j = jnp.maximum((start - size + stride) // stride, 0)[:, None] + jnp.arange(
                    -(-t // stride), dtype=jnp.int32)[None, :]
                last = stride * j + size - 1
                done = (last >= start[:, None]) & (last < (start + live)[:, None])
                of = stride * j[..., None] + jnp.arange(size, dtype=jnp.int32)      # (b, n, size)
                got = jnp.moveaxis(kc.reshape(-1, d)[rows_at(of)], 2, 3)           # (b, n, NKV, size, D)
                at = layer * kernel_rows + jnp.where(done, block_of(j // per) * per + j % per, 0)
                pc = pc.reshape(-1, width).at[at].set(pool_keys(got).reshape(b, -1, width)).reshape(pc.shape)
            blocks = -(-limit // bs)
            ids = table[:, :blocks]
            with jax.named_scope("select"):
                at = layer * kernel_rows + ids[..., None] * per + jnp.arange(per, dtype=jnp.int32)
                pooled = pc.reshape(-1, width)[at.reshape(b, -1)].reshape(b, -1, nkv, d)
                chosen, taken = select_blocks(q, pooled.astype(q.dtype), pos_block, c)
            with jax.named_scope("read"):
                k_blocks, v_blocks = (a.reshape(nl * nb * nkv, bs, d) for a in (kc, vc))
                if t == 1:
                    # one token a lane: the chosen blocks alone, a kv group
                    # its own head of each
                    kk = chosen.shape[-1]
                    at = (layer * nb + jnp.take_along_axis(
                        ids, chosen.reshape(b, -1), axis=1).reshape(b, nkv, kk)) * nkv + heads[:, None]

                    def group_rows(pool):       # (b, NKV, kk · bs, D)
                        return pool[at].reshape(b, nkv, kk * bs, d).astype(q.dtype)

                    at_rows = (chosen[:, 0, :, :, None] * bs + jnp.arange(bs, dtype=jnp.int32))
                    seen = taken[:, 0, :, :, None] & (at_rows <= pos_block[:, :, None, None])
                    qg = q.reshape(b, nkv, c.num_heads // nkv, d)
                    s = jnp.einsum(
                        "bkgd,bksd->bkgs", qg, group_rows(k_blocks),
                        preferred_element_type=jnp.float32) * d ** -0.5
                    s = jnp.where(seen.reshape(b, nkv, 1, kk * bs), s, jnp.float32(-1e30))
                    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                    att = jnp.einsum("bkgs,bksd->bkgd", w, group_rows(v_blocks))
                    att = att.reshape(b, 1, c.num_heads, d)
                elif self._chunk_kernel_takes(t, blocks * bs):
                    # the rung's rows in order, a kv head's together, gathered
                    # a block at a time; the scores stay in the kernel
                    from neuronx_distributed_llama3_2_tpu.kernels.sparse_chunk_pallas import (
                        sparse_chunk_attend,
                    )

                    at = (layer * nb + ids)[:, None, :] * nkv + heads[:, None]      # (b, NKV, blocks)
                    k_rung, v_rung = (
                        pool[at].reshape(b, nkv, blocks * bs, d).astype(q.dtype)
                        for pool in (k_blocks, v_blocks))
                    att = sparse_chunk_attend(
                        q, k_rung, v_rung, block_mask(chosen, taken, blocks), pos_block[:, 0], bs)
                else:
                    tile = min(blocks, max(SPARSE_TILE_ROWS // bs, 1))
                    tiles = -(-blocks // tile)
                    ids = jnp.pad(ids, ((0, 0), (0, tiles * tile - blocks)))

                    def read(i):        # a tile's blocks, every kv head's: (b, tile · bs, NKV, D)
                        at = (layer * nb + jax.lax.dynamic_slice_in_dim(
                            ids, i * tile, tile, axis=1))[..., None] * nkv + heads
                        return tuple(
                            jnp.swapaxes(pool[at], 2, 3).reshape(b, tile * bs, nkv, d).astype(q.dtype)
                            for pool in (k_blocks, v_blocks))

                    att = attend_tiles(
                        q, pos_block, block_mask(chosen, taken, blocks), read, tiles, tile, bs)
        return att, (kc, vc, pc)


def _pool_pair(pool: PagedKVCache):
    """A pool as the layer loop carries it: (k, v), each a (payload, scale)
    pair where quantized."""
    if pool.quantized:
        return (pool.k, pool.k_scale), (pool.v, pool.v_scale)
    return pool.k, pool.v


def _pool_of(pair) -> PagedKVCache:
    k, v = pair
    if isinstance(k, tuple):
        return PagedKVCache(k=k[0], v=v[0], k_scale=k[1], v_scale=v[1])
    return PagedKVCache(k=k, v=v)


@dataclasses.dataclass(frozen=True)
class GPTNeoXDecode(LlamaDecode):
    """Decode-mode GPT-NeoX/Pythia/CodeGen: the shared KV-cache machinery
    (:meth:`LlamaDecode._attend_with_cache`) under the family's block
    structure — parallel (or Pythia-sequential) residual, LayerNorm with
    bias, biased projections, partial rotary in either convention.
    Beyond-reference capability: the reference ships no GPT-NeoX/CodeGen
    inference model at all (its inference zoo is Llama/Mixtral/DBRX,
    SURVEY §2.7)."""

    def _model(self):
        from neuronx_distributed_llama3_2_tpu.models.gptneox import (
            GPTNeoXForCausalLM,
        )

        return GPTNeoXForCausalLM(self.config)

    def _decode_layer(
        self, lp, x, kc, vc, layer, sin, cos, pos_block, positions, slots,
        *, context_encode: bool, tree=None, kv_limit=None, block_tables=None,
        row_live=None,
    ):
        from neuronx_distributed_llama3_2_tpu.models.gptneox import (
            GPTNeoXAttention,
            GPTNeoXMLP,
        )

        c = self.config
        attn = GPTNeoXAttention(c)
        norm = make_norm(c)
        b, t, _ = x.shape

        h1 = norm(lp["attn_norm"], x)
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                q, k, v = attn._qkv()(lp["attn"]["qkv"], h1)
                if c.clip_qkv is not None:
                    # inherited LlamaConfig knob; the training forward clamps
                    # (llama.py LlamaAttention), so decode must too
                    q = jnp.clip(q, -c.clip_qkv, c.clip_qkv)
                    k = jnp.clip(k, -c.clip_qkv, c.clip_qkv)
                    v = jnp.clip(v, -c.clip_qkv, c.clip_qkv)
                q = q.reshape(b, t, c.num_heads, c.head_dim)
                k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
                v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
            with jax.named_scope("rope"):
                q, k = attn._apply_rope(q, k, sin, cos, pos_block)

            att, kc, vc = self._attend_with_cache(
                q, k, v, kc, vc, layer, slots, pos_block, positions,
                context_encode=context_encode, tree=tree, kv_limit=kv_limit,
                block_tables=block_tables, row_live=row_live,
            )
            att = att.reshape(b, t, c.num_heads * c.head_dim)
            with jax.named_scope("o_proj"):
                attn_out = attn._o()(lp["attn"]["o"], att)

        mlp = GPTNeoXMLP(c)
        if c.parallel_residual:
            # x + attn(ln1 x) + mlp(ln2 x) — CodeGen shares ln1 (gptneox.py
            # GPTNeoXDecoderLayer, the single source of the block semantics)
            h2 = h1 if c.shared_layernorm else norm(lp["mlp_norm"], x)
            return x + attn_out + mlp(lp["mlp"], h2), kc, vc
        x = x + attn_out
        h2 = norm(lp["mlp_norm"], x)
        return x + mlp(lp["mlp"], h2), kc, vc


def decode_model_for(config) -> LlamaDecode:
    """Pick the decode-model class for a training config (the engine-side
    analogue of the reference's per-family NeuronXxxForCausalLM dispatch)."""
    from neuronx_distributed_llama3_2_tpu.models.bert import BertConfig
    from neuronx_distributed_llama3_2_tpu.models.brumby import BrumbyConfig
    from neuronx_distributed_llama3_2_tpu.models.gptneox import GPTNeoXConfig
    from neuronx_distributed_llama3_2_tpu.models.jamba import JambaConfig
    from neuronx_distributed_llama3_2_tpu.models.laguna import LagunaConfig
    from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import SalaConfig
    from neuronx_distributed_llama3_2_tpu.models.mixtral import MixtralConfig
    from neuronx_distributed_llama3_2_tpu.models.sarvam import SarvamConfig
    from neuronx_distributed_llama3_2_tpu.models.smallthinker import SmallThinkerConfig
    from neuronx_distributed_llama3_2_tpu.models.xing import XingConfig

    if isinstance(config, BertConfig):
        raise NotImplementedError(
            "BERT is a bidirectional encoder — there is no KV-cache decode; "
            "use BertForPreTraining's forward directly"
        )
    if isinstance(config, GPTNeoXConfig):
        return GPTNeoXDecode(config)
    if isinstance(config, XingConfig):
        return XingDecode(config)
    if isinstance(config, SarvamConfig):
        return SarvamDecode(config)
    if isinstance(config, BrumbyConfig):
        return RetentionDecode(config)
    if isinstance(config, JambaConfig):
        return JambaDecode(config)
    if isinstance(config, SalaConfig):
        return SalaDecode(config)
    if isinstance(config, LagunaConfig):
        return LagunaDecode(config)
    if isinstance(config, SmallThinkerConfig):
        return SmallThinkerDecode(config)
    if isinstance(config, MixtralConfig):
        return MixtralDecode(config)
    return LlamaDecode(config)
