"""The decode block walk (``kernels/paged_attention_pallas.py``
``paged_decode_walk``), interpreted on the CPU: against its plain twin — the
block-wise gather of the whole rung and ``masked_attention``, which
``LagunaDecode._attend`` keeps everywhere the walk does not run — through a
permuted table with ragged positions, and what "the lane's live blocks only"
has to mean: a lane on the null block, blocks past a frontier that are never
read, the layer's offset into the run of ``L · num_blocks`` blocks, a group
size that does not divide the walk. The same against
``LlamaDecode._cache_attention`` over the rows ``_attend_paged`` gathers, at
the head groupings of the families that decode through it (Mixtral's 32 on 8,
OLMoE's 16 on 16), with two lanes sharing a prefix's blocks. Then which read a
``pdecode`` holds in each kernel mode, for laguna and for the Llama family.
And the walk under a window — a lower bound: against the gather of the lane's
whole ring and ``masked_attention(visible(…, window))`` through permuted rings
wrapped more than twice, at SmallThinker's and Laguna's head groupings.

Scope: the kernel and the predicate. The engines that hold it are
``tests/test_laguna_serving.py`` and ``tests/test_smallthinker_serving.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference.model import CacheKind, LlamaDecode, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
    paged_decode_walk, walk_fits, walk_group, window_walk_group,
)
from neuronx_distributed_llama3_2_tpu.models.laguna import (
    LAGUNA_CONFIGS, LagunaForCausalLM, layer_runs, masked_attention, visible,
)
from neuronx_distributed_llama3_2_tpu.models.llama import LLAMA_CONFIGS, LlamaConfig, LlamaForCausalLM
from neuronx_distributed_llama3_2_tpu.models.mixtral import MIXTRAL_CONFIGS, MixtralForCausalLM
from neuronx_distributed_llama3_2_tpu.parallel.state import initialize_model_parallel

LAYERS, BLOCKS, BS, NKV, GROUPS, D = 2, 40, 4, 2, 3, 16
WIDTH = 8                                   # blocks a table row: a rung of 32 rows
RUNG = WIDTH * BS
# 0, 15, 16: a block's first row, its last, the next block's first — here BS is 4,
# so also 3 and 4; one short of the rung; lane 4 idles on the null block
POSITIONS = (0, 15, 16, RUNG - 2, 9, 3, 4)
NULL_LANE = 4


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")


def make(dtype, seed=0, heads=(NKV * GROUPS, NKV), positions=POSITIONS, null_lane=NULL_LANE, shared=0):
    """(q, k_pool, v_pool, tables, positions): every live lane its own blocks,
    scattered; past its frontier the null block, as the engine's table has it;
    lane 1's first ``shared`` blocks are lane 0's (a prefix both were given)."""
    n, nkv = heads
    rng = np.random.default_rng(seed)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal((LAYERS, BLOCKS, BS, nkv, D)), dtype) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((len(positions), n, D)), dtype)
    free = rng.permutation(np.arange(1, BLOCKS))
    tables = np.zeros((len(positions), WIDTH), np.int32)
    for lane, pos in enumerate(positions):
        if lane != null_lane:
            blocks = pos // BS + 1
            tables[lane, :blocks], free = free[:blocks], free[blocks:]
    tables[1, :shared] = tables[0, :shared]
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(positions, jnp.int32)


@jax.jit
def twin(q, k_pool, v_pool, tables, positions, layer):
    """``LagunaDecode._attend``'s read of a full layer at one row a lane."""
    at = layer * BLOCKS + tables

    def read(a):
        got = a.reshape((LAYERS * BLOCKS,) + a.shape[2:])[at]
        return got.reshape((got.shape[0], RUNG) + got.shape[3:])

    k_pos = jnp.arange(RUNG, dtype=jnp.int32)[None, None, :]
    return masked_attention(q[:, None], read(k_pool), read(v_pool), visible(positions[:, None], k_pos, None))[:, 0]


_WALK = jax.jit(paged_decode_walk, static_argnames=("kv_limit", "group"))


def walk(q, k_pool, v_pool, tables, positions, layer, group):
    # the layer is an operand: the tests of one dtype and group share a compile
    return _WALK(q, k_pool, v_pool, tables, positions, jnp.int32(layer), kv_limit=RUNG, group=group)


def live_lanes(a, null_lane=NULL_LANE):
    return jnp.delete(a, null_lane, axis=0)


@pytest.mark.parametrize("dtype,tol,group,layer", [
    (jnp.float32, 2e-6, 3, 0), (jnp.float32, 2e-6, 3, 1), (jnp.float32, 2e-6, 8, 1),
    (jnp.bfloat16, 2e-2, 3, 0), (jnp.bfloat16, 2e-2, 3, 1),
], ids=["f32-group3-layer0", "f32-group3-layer1", "f32-group8-layer1", "bf16-group3-layer0", "bf16-group3-layer1"])
def test_the_walk_is_the_gather_and_masked_attention_through_a_permuted_table(dtype, tol, group, layer):
    """Ragged positions, a group of 3 over walks of 1, 4, 5 and 8 blocks (none a
    multiple), a layer's blocks at ``index + layer · num_blocks``. float32 to
    round-off; bfloat16 to the rounding of the scores and of p, which the two
    place differently."""
    operands = make(dtype)
    got = walk(*operands, layer, group)
    want = twin(*operands, jnp.int32(layer))
    assert got.dtype == dtype and got.shape == want.shape
    err = jnp.max(jnp.abs(live_lanes(got).astype(jnp.float32) - live_lanes(want).astype(jnp.float32)))
    assert float(err) <= tol * float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    # the other layer's blocks of the same index were not what was read
    other = twin(*operands, jnp.int32(1 - layer))
    assert float(jnp.max(jnp.abs(live_lanes(got).astype(jnp.float32) - live_lanes(other).astype(jnp.float32)))) > 0.1


@pytest.mark.parametrize("group", [3, 8], ids=lambda g: f"group{g}")
def test_blocks_past_a_lanes_frontier_are_never_read(group):
    """Every block no lane's walk reaches, and the null block, hold 1e30: the
    output of the live lanes is the clean pool's bit for bit. (The twin reads
    them all and multiplies them by p == 0.)"""
    q, k_pool, v_pool, tables, positions = make(jnp.float32)
    clean = walk(q, k_pool, v_pool, tables, positions, 1, group)
    reached = np.zeros((LAYERS, BLOCKS), bool)
    for lane, pos in enumerate(POSITIONS):
        if lane != NULL_LANE:
            reached[1, np.asarray(tables[lane, :pos // BS + 1])] = True
    spoil = jnp.asarray(~reached)[:, :, None, None, None]
    dirty = walk(q, jnp.where(spoil, 1e30, k_pool), jnp.where(spoil, 1e30, v_pool), tables, positions, 1, group)
    assert bool((live_lanes(dirty) == live_lanes(clean)).all())
    assert bool(jnp.isfinite(dirty[NULL_LANE]).all())        # 1e30 · weights that sum to 1: a number


def test_rows_of_the_last_block_past_the_position_are_masked():
    """Within the frontier's own block the rows after ``position`` are stale:
    changing them changes nothing."""
    q, k_pool, v_pool, tables, positions = make(jnp.float32)
    lane = 6
    assert POSITIONS[lane] == BS                             # block 1 holds row 4; its rows 5..7 are stale
    block = int(tables[lane, 1])
    stale_k = k_pool.at[0, block, 1:].set(7.0)
    stale_v = v_pool.at[0, block, 1:].set(-7.0)
    a = walk(q, k_pool, v_pool, tables, positions, 0, 8)
    b = walk(q, stale_k, stale_v, tables, positions, 0, 8)
    assert bool((a == b).all())


def test_a_lane_on_the_null_block_walks_one_block_whatever_its_position():
    """An idle lane keeps stepping its position (``decode_step``, up to
    ``pos_cap``): the walk is bounded by its table's first entry, and the live
    lanes read what they read without it."""
    q, k_pool, v_pool, tables, positions = make(jnp.float32)
    far = positions.at[NULL_LANE].set(RUNG - 1)
    a = walk(q, k_pool, v_pool, tables, positions, 0, 3)
    b = walk(q, k_pool, v_pool, tables, far, 0, 3)
    assert bool((live_lanes(a) == live_lanes(b)).all())
    # block 0's rows alone, all of them visible from the far position
    only = masked_attention(
        q[NULL_LANE:NULL_LANE + 1, None], k_pool[0, :1].reshape(1, BS, NKV, D), v_pool[0, :1].reshape(1, BS, NKV, D),
        jnp.ones((1, 1, BS), bool))[0, 0]
    np.testing.assert_allclose(b[NULL_LANE], only, rtol=2e-6, atol=2e-6)


def test_query_heads_that_do_not_divide_are_refused():
    q, k_pool, v_pool, tables, positions = make(jnp.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        paged_decode_walk(q[:, :5], k_pool, v_pool, tables, positions, 0)


# ---------------------------------------------------------------------------
# the Llama family's groupings, against LlamaDecode's own gather and attention
# ---------------------------------------------------------------------------

# (query heads, kv heads): Mixtral's, and OLMoE's (one query head a kv head)
GROUPINGS = {"32on8": (32, 8), "16on16": (16, 16)}
# lanes 0 and 1 share their first two blocks (a prefix the radix cache gave
# both) and part at row 8; lane 2 idles on the null block; lane 4 is at row 0
SHARED_POSITIONS = (21, 13, 30, 30, 0)
SHARED_BLOCKS, SHARED_NULL = 2, 2


@jax.jit
def llama_twin(q, k_pool, v_pool, tables, positions, layer):
    """``LlamaDecode._attend_paged``'s read at one row a lane: the rung's rows
    gathered through the table, then ``_cache_attention``."""
    n, nkv = q.shape[1], k_pool.shape[3]
    model = LlamaDecode(LlamaConfig(num_heads=n, num_kv_heads=nkv, head_dim=D))
    j = jnp.arange(RUNG, dtype=jnp.int32)
    at = layer * BLOCKS * BS + tables[:, j // BS] * BS + (j % BS)[None, :]

    def read(a):
        return a.reshape((LAYERS * BLOCKS * BS,) + a.shape[3:])[at]

    return model._cache_attention(q[:, None], read(k_pool), read(v_pool), positions[:, None], None)[:, 0]


@pytest.mark.parametrize("dtype,tol,group", [
    (jnp.float32, 2e-6, None), (jnp.float32, 2e-6, 3), (jnp.bfloat16, 2e-2, None),
], ids=["f32-derived", "f32-group3", "bf16-derived"])
@pytest.mark.parametrize("heads", GROUPINGS)
def test_the_walk_is_llamas_gather_and_cache_attention_at_its_families_groupings(heads, dtype, tol, group):
    """Mixtral's and OLMoE's query-on-kv-head groupings, the group a trip
    derived from the pool's shape or 3 (over walks of 1, 4, 6 and 8 blocks),
    layer 1's blocks, two lanes reading the same two pool blocks."""
    operands = make(dtype, heads=GROUPINGS[heads], positions=SHARED_POSITIONS, null_lane=SHARED_NULL,
                    shared=SHARED_BLOCKS)
    got = walk(*operands, 1, group)
    want = llama_twin(*operands, jnp.int32(1))
    assert got.dtype == dtype and got.shape == want.shape == (len(SHARED_POSITIONS), GROUPINGS[heads][0], D)

    def live(a):
        return live_lanes(a, SHARED_NULL).astype(jnp.float32)

    assert float(jnp.max(jnp.abs(live(got) - live(want)))) <= tol * float(jnp.max(jnp.abs(live(want))))
    other = llama_twin(*operands, jnp.int32(0))
    assert float(jnp.max(jnp.abs(live(got) - live(other)))) > 0.1
    assert bool(jnp.isfinite(got[SHARED_NULL].astype(jnp.float32)).all())


def test_the_group_a_trip_follows_the_pools_shape():
    """As many blocks as hold 4,096 (row, kv head) pairs: laguna's and
    Mixtral's 32 at 8 kv heads of 16-row blocks, OLMoE's 16 at 16, never 0."""
    assert walk_group(16, 8) == 32 and walk_group(16, 16) == 16
    assert walk_group(16, 1) == 256 and walk_group(64, 128) == 1
    # Mosaic takes rows of one register's lanes alone; the interpreter any
    assert walk_fits(128) and walk_fits(D)


# ---------------------------------------------------------------------------
# under a window: the walk has a lower bound, and the table is a ring
# ---------------------------------------------------------------------------

# a window of 22 rows over blocks of 4 spans at most 7 blocks; a ring of 8 blocks
# (32 rows >= window - 1 + a block) is what the engine would give a lane
WINDOW, RING = 22, 8
RING_ROWS = RING * BS
# shorter than the window; exactly the window (22 rows: the last is 21); one more;
# then rings wrapped twice and three times with the row at a block's first row
# (64, 96), its last (67) and inside (70, 109); lane 3 is a null lane
RING_POSITIONS = (5, WINDOW - 1, WINDOW, 40, 64, 67, 70, 96, 109)
RING_NULL = 3
# (query heads, kv heads): SmallThinker's 7 a kv head on 4, Laguna's 6 on 8
RING_HEADS = {"28on4": (28, 4), "48on8": (48, 8)}


def make_rings(dtype, heads, seed=0):
    """(q, k_pool, v_pool, rings, positions, null_lanes): a ring of ``RING``
    scattered blocks a lane, never block 0 — a lane's row going to the null
    block is said beside the table, as ``LagunaDecode.forward`` says it."""
    n, nkv = heads
    rng = np.random.default_rng(seed)
    blocks = 1 + len(RING_POSITIONS) * RING
    k_pool, v_pool = (jnp.asarray(rng.standard_normal((LAYERS, blocks, BS, nkv, D)), dtype) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((len(RING_POSITIONS), n, D)), dtype)
    rings = rng.permutation(np.arange(1, blocks)).reshape(len(RING_POSITIONS), RING).astype(np.int32)
    null = np.arange(len(RING_POSITIONS)) == RING_NULL
    return q, k_pool, v_pool, jnp.asarray(rings), jnp.asarray(RING_POSITIONS, jnp.int32), jnp.asarray(null)


@jax.jit
def ring_twin(q, k_pool, v_pool, rings, positions, layer):
    """``LagunaDecode._attend``'s read of a window layer at one row a lane: the
    lane's ring gathered whole, the position a ring row holds, ``visible``."""
    blocks = k_pool.shape[1]
    at = layer * blocks + rings

    def read(a):
        got = a.reshape((LAYERS * blocks,) + a.shape[2:])[at]
        return got.reshape((got.shape[0], RING_ROWS) + got.shape[3:])

    pos = positions[:, None]
    k_pos = pos[..., None] - (pos[..., None] - jnp.arange(RING_ROWS, dtype=jnp.int32)) % RING_ROWS
    return masked_attention(q[:, None], read(k_pool), read(v_pool), visible(pos, k_pos, WINDOW))[:, 0]


_RING_WALK = jax.jit(paged_decode_walk, static_argnames=("kv_limit", "group", "window"))


def ring_walk(q, k_pool, v_pool, rings, positions, null, layer, group, window=WINDOW):
    return _RING_WALK(q, k_pool, v_pool, rings, positions, jnp.int32(layer), group=group, window=window, null_lanes=null)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [3, 7, None], ids=lambda g: f"group{g}")
@pytest.mark.parametrize("heads", RING_HEADS)
def test_the_windowed_walk_is_the_ring_gather_and_masked_attention(heads, group, dtype, tol):
    """Rings wrapped up to three times, each lane's blocks scattered; a group of
    3 puts a 7-block walk's first and last block in different trips (3 + 3 + 1),
    7 and the derived group (None) take it in one; layer 1's blocks. Contexts
    shorter than the window, the window exactly, one more, rows at both edges
    of a block. float32 to round-off, bfloat16 to the rounding of the scores
    and of p."""
    *operands, null = make_rings(dtype, RING_HEADS[heads])
    got = ring_walk(*operands, null, 1, group)
    want = ring_twin(*operands, jnp.int32(1))
    assert got.dtype == dtype and got.shape == want.shape

    def live(a):
        return live_lanes(a, RING_NULL).astype(jnp.float32)

    assert float(jnp.max(jnp.abs(live(got) - live(want)))) <= tol * float(jnp.max(jnp.abs(live(want))))
    # neither the other layer's blocks nor a read with no lower bound
    assert float(jnp.max(jnp.abs(live(got) - live(ring_twin(*operands, jnp.int32(0)))))) > 0.1
    unbounded = ring_walk(*operands, null, 1, group, window=None)
    assert float(jnp.max(jnp.abs(live(got)[2:] - live(unbounded)[2:]))) > 1e-3


@pytest.mark.parametrize("group", [3, None], ids=lambda g: f"group{g}")
def test_blocks_outside_a_lanes_window_are_never_read(group):
    """Every block outside ``[first, first + live)`` of a lane's walk — the
    rest of its ring, the null lane's whole ring — holds NaN: the live lanes'
    output is the clean pool's bit for bit, and the null lane reads block 0."""
    q, k_pool, v_pool, rings, positions, null = make_rings(jnp.float32, RING_HEADS["28on4"])
    clean = ring_walk(q, k_pool, v_pool, rings, positions, null, 1, group)
    reached = np.zeros(k_pool.shape[:2], bool)
    reached[1, 0] = True
    for lane, pos in enumerate(RING_POSITIONS):
        if lane != RING_NULL:
            first = max(0, pos - WINDOW + 1) // BS
            reached[1, np.asarray(rings[lane])[np.arange(first, pos // BS + 1) % RING]] = True
    assert reached[1].sum() < 1 + (len(RING_POSITIONS) - 1) * RING          # some ring blocks are outside
    spoil = jnp.asarray(~reached)[:, :, None, None, None]
    dirty = ring_walk(q, jnp.where(spoil, jnp.nan, k_pool), jnp.where(spoil, jnp.nan, v_pool), rings, positions, null, 1, group)
    assert bool((live_lanes(dirty, RING_NULL) == live_lanes(clean, RING_NULL)).all())
    assert bool(jnp.isfinite(dirty).all())


@pytest.mark.parametrize("said", ["beside_the_table", "by_the_table"])
def test_a_null_lane_under_a_window_walks_one_block_whatever_its_position(said):
    """A lane whose row goes to the null block — said beside the table where
    the lane has a ring of its own, by the table's first entry otherwise —
    walks block 0 alone, all of its rows visible from a far position, and the
    live lanes read what they read without it."""
    q, k_pool, v_pool, rings, positions, null = make_rings(jnp.float32, RING_HEADS["48on8"])
    if said == "by_the_table":
        rings, null = rings.at[RING_NULL].set(0), None
    a = ring_walk(q, k_pool, v_pool, rings, positions, null, 0, 3)
    b = ring_walk(q, k_pool, v_pool, rings, positions.at[RING_NULL].set(1000), null, 0, 3)
    assert bool((live_lanes(a, RING_NULL) == live_lanes(b, RING_NULL)).all())
    nkv = RING_HEADS["48on8"][1]
    only = masked_attention(
        q[RING_NULL:RING_NULL + 1, None], k_pool[0, :1].reshape(1, BS, nkv, D), v_pool[0, :1].reshape(1, BS, nkv, D),
        jnp.ones((1, 1, BS), bool))[0, 0]
    np.testing.assert_allclose(b[RING_NULL], only, rtol=2e-6, atol=2e-6)


def test_a_table_as_wide_as_the_context_takes_the_same_rule_under_a_rung():
    """``benchmarks/check.py``'s call: one table, never wrapped, ``kv_limit``
    rows of it — the window's blocks are columns ``first …`` of it, no modulo
    reached, and the walk is the gather of the rung under ``visible``."""
    q, k_pool, v_pool, tables, positions = make(jnp.float32, positions=(0, 15, 16, RUNG - 2, 9, 3, 25), null_lane=NULL_LANE)
    window = 10
    got = _RING_WALK(q, k_pool, v_pool, tables, positions, jnp.int32(1), kv_limit=RUNG, group=2, window=window)
    at = BLOCKS + tables

    def read(a):
        got = a.reshape((LAYERS * BLOCKS,) + a.shape[2:])[at]
        return got.reshape((got.shape[0], RUNG) + got.shape[3:])

    k_pos = jnp.arange(RUNG, dtype=jnp.int32)[None, None, :]
    want = masked_attention(q[:, None], read(k_pool), read(v_pool), visible(positions[:, None], k_pos, window))[:, 0]
    np.testing.assert_allclose(live_lanes(got), live_lanes(want), rtol=2e-5, atol=2e-6)


def scalar_operands(window):
    """(scalar-prefetch operand shapes, every primitive of the wrapper) of a
    ``paged_decode_walk`` call over 3 lanes and a table 8 blocks wide."""
    q, pool = jnp.zeros((3, NKV * GROUPS, D)), jnp.zeros((LAYERS, BLOCKS, BS, NKV, D))
    jaxpr = jax.make_jaxpr(lambda *a: paged_decode_walk(*a, 1, window=window))(
        q, pool, pool, jnp.ones((3, WIDTH), jnp.int32), jnp.zeros((3,), jnp.int32)).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    count = call.params["grid_mapping"].num_index_operands
    return [v.aval.shape for v in call.invars[:count]], {e.primitive.name for e in jaxpr.eqns}


def test_without_a_window_the_kernel_has_the_three_scalar_operands_it_had():
    """Table, live blocks, position and no lower-bound term, nor any of the
    arithmetic that makes one ahead of the call; a window adds two a lane: the
    walk's first column of the ring and the first row seen."""
    shapes, wrapper = scalar_operands(None)
    assert shapes == [(3, WIDTH), (3,), (3,)]
    assert not wrapper & {"max", "sub", "mul", "rem", "gather"}
    shapes, wrapper = scalar_operands(10)
    assert shapes == [(3, WIDTH), (3,), (3,), (3,), (3,)] and "gather" not in wrapper


def test_a_windows_group_is_its_blocks_over_the_nearest_whole_trips():
    """SmallThinker's 257 blocks of 16 rows at 4 kv heads go 65 a trip in 4
    trips and Laguna's 33 at 8 in one, where ``walk_group`` (64, 32) would spend
    a whole score tile on the odd block; a short window is one trip."""
    assert window_walk_group(257, 16, 4) == 65 and window_walk_group(33, 16, 8) == 33
    assert window_walk_group(7, 4, 4) == 7 and window_walk_group(1, 16, 8) == 1
    assert window_walk_group(96, 16, 8) == 32 and window_walk_group(100, 16, 8) == 34


# ---------------------------------------------------------------------------
# which read a decode program holds
# ---------------------------------------------------------------------------

TINY = dataclasses.replace(LAGUNA_CONFIGS["tiny-laguna"], max_seq_len=128)


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_the_kernel_mode_decides_which_read_a_decode_program_holds(mode, monkeypatch):
    """``interpret``: a ``pdecode`` holds one ``pallas_call`` a layer, full or
    window, and neither a (lanes, rung, kv heads, head) array of the full kind's
    rows nor a (lanes, ring rows, …) one of the window kind's; ``reference``
    keeps both gathers (the CPU tier's twin). A block of rows (``psfx``) and an
    int8 pool keep them in either mode."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    full, window = model.cache_kinds
    walks = mode == "interpret"
    assert model.decode_read(full) == model.decode_read(window) == ("kernel" if walks else "gather")
    assert model.decode_read(window, quantized=True) == model.decode_read(full, quantized=True) == "gather"
    assert model.decode_read(CacheKind("rows", 5, None)) == model.decode_read(full)
    params = jax.eval_shape(LagunaForCausalLM(TINY).init, jax.random.key(0))
    lanes, rung, bs = 3, 64, 4
    gathered = f"[{lanes},{rung},{TINY.num_kv_heads},{TINY.head_dim}]"
    ring = f"[{lanes},{6 * bs},{TINY.num_kv_heads},{TINY.head_dim}]"

    def programs(pool):
        tables = jnp.zeros((lanes, rung // bs), jnp.int32)
        rings = jnp.zeros((lanes, 6), jnp.int32)
        step = jax.make_jaxpr(lambda p, c: model.decode_step(
            p, c, jnp.zeros((lanes,), jnp.int32), jnp.zeros((lanes,), jnp.int32), tables,
            kv_limit=rung, window_tables=rings))(params, pool)
        chunk = jax.make_jaxpr(lambda p, c: model.forward(
            p, c, jnp.zeros((lanes, 8), jnp.int32), jnp.zeros((lanes,), jnp.int32), block_tables=tables,
            kv_limit=rung, window_tables=rings))(params, pool)
        return str(step), str(chunk)

    step, chunk = programs(jax.eval_shape(lambda: model.init_paged_cache(20, bs, window_blocks=19)))
    # the window layers' ring is 24 rows, never the rung: each shape is one kind's alone;
    # one call a run of layers (a scan's body holds its layers' one): full, 3 x window, full
    assert step.count("pallas_call") == (len(layer_runs(TINY)) if walks else 0)
    assert (gathered in step) == (ring in step) == (not walks)
    assert "pallas_call" not in chunk and gathered in chunk and ring in chunk
    step, _ = programs(jax.eval_shape(lambda: model.init_paged_cache(20, bs, kv_cache_dtype="int8", window_blocks=19)))
    assert "pallas_call" not in step and gathered in step and ring in step


FAMILIES = {
    "llama": (LLAMA_CONFIGS["tiny"], LlamaForCausalLM),
    "mixtral": (MIXTRAL_CONFIGS["tiny-moe"], MixtralForCausalLM),
}


@pytest.mark.parametrize("mode", ["reference", "interpret"])
@pytest.mark.parametrize("family", FAMILIES)
def test_the_kernel_mode_decides_which_read_a_llama_family_decode_program_holds(family, mode, monkeypatch):
    """``LlamaDecode`` / ``MixtralDecode``: in ``interpret`` a ``pdecode`` holds
    the walk and no (lanes, rung, kv heads, head) copy, ``use_paged_kernel`` or
    not; ``reference`` keeps the gather. A block of several rows, a tree, an
    int8 pool and a mesh keep what they had in either mode."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    config, net = FAMILIES[family]
    model = decode_model_for(config)
    (rows,) = model.cache_kinds
    walks = mode == "interpret"
    assert model.decode_read(rows) == ("kernel" if walks else "gather")
    assert model.decode_read(rows, quantized=True) == "gather"
    # a kind with a window would be walked too (no such family decodes through LlamaDecode today)
    assert model.decode_read(CacheKind("ring", 1, 8)) == model.decode_read(rows)
    asked = decode_model_for(dataclasses.replace(config, use_paged_kernel=True))
    assert asked.decode_read(rows) == asked.decode_read(rows, quantized=True) == "kernel"
    params = jax.eval_shape(net(config).init, jax.random.key(0))
    lanes, rung, bs = 3, 64, 4
    gathered = f"[{lanes},{rung},{config.num_kv_heads},{config.head_dim}]"
    tables = jnp.zeros((lanes, rung // bs), jnp.int32)
    zeros = jnp.zeros((lanes,), jnp.int32)

    def step(model, pool):
        return str(jax.make_jaxpr(lambda p, c: model.decode_step(p, c, zeros, zeros, tables, kv_limit=rung))(params, pool))

    def block(model, pool, t, tree=None):
        return str(jax.make_jaxpr(lambda p, c: model.forward(
            p, c, jnp.zeros((lanes, t), jnp.int32), zeros, block_tables=tables, kv_limit=rung, tree=tree))(params, pool))

    pool = jax.eval_shape(lambda: model.init_paged_cache(20, bs))
    got = step(model, pool)
    assert ("paged_decode_walk" in got) == walks and (gathered in got) == (not walks)
    # where the static-grid kernel was asked for, one row a lane is still the walk's
    got = step(asked, pool)
    assert ("paged_decode_walk" in got) == walks and ("paged_flash_decode" in got) == (not walks)
    assert gathered not in got
    # several rows, and one row under a tree: never the walk
    one_node = (jnp.zeros((1,), jnp.int32), jnp.ones((1, 1), bool))
    for got in (block(model, pool, 8), block(model, pool, 1, one_node)):
        assert "pallas_call" not in got and gathered in got
    assert "paged_decode_walk" not in block(asked, pool, 2) and "paged_decode_walk" not in block(asked, pool, 1, one_node)
    got = step(model, jax.eval_shape(lambda: model.init_paged_cache(20, bs, kv_cache_dtype="int8")))
    assert "pallas_call" not in got and gathered in got
    # a mesh of more than one device: the pool shards by kv head
    initialize_model_parallel(tensor_model_parallel_size=2, devices=jax.devices()[:2])
    assert model.decode_read(rows) == "gather" and asked.decode_read(rows) == "kernel"
    assert "paged_decode_walk" not in step(model, pool)
