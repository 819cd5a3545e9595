"""The latent decode walk (``kernels/paged_attention_pallas.py``
``latent_decode_walk``), interpreted on the CPU: against its plain twin — the
block-wise gather of the whole rung and ``latent_attention(..., absorbed=True)``
over it, which ``SarvamDecode._latent_attention`` keeps everywhere the walk
does not run — through a permuted table with ragged positions, and what "the
lane's live blocks only" has to mean: a lane on the null block, blocks past a
frontier that are never read, stale rows of a live block, whatever the pool's
padding columns hold, the layer's offset into the run of ``L · num_blocks``
blocks, group sizes that do not divide the walk. Then which read a
``pdecode`` holds in each kernel mode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import latent_decode_walk
from neuronx_distributed_llama3_2_tpu.models.sarvam import (
    SARVAM_CONFIGS, SarvamForCausalLM, latent_attention,
)
from neuronx_distributed_llama3_2_tpu.models.xing import XING_CONFIGS, XingForCausalLM

# tiny-sarvam's widths: a row of 32 + 8 values in a pool row of one lane of 128
CFG = SARVAM_CONFIGS["tiny-sarvam"]
R, DR, DN, DV, W = CFG.kv_lora_rank, CFG.qk_rope_head_dim, CFG.qk_nope_head_dim, CFG.v_head_dim, 128
LAYERS, BLOCKS, BS = 2, 48, 4
WIDTH = 8                                   # blocks a table row: a rung of 32 rows
RUNG = WIDTH * BS
# 0, 15, 16: a block's first row, its last, the next block's first — here BS is 4,
# so also 3 and 4; one short of the rung; lane 4 idles on the null block
POSITIONS = (0, 15, 16, RUNG - 2, 9, 3, 4)
NULL_LANE = 4


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")


def make(dtype, heads=4, seed=0):
    """(q, kv_b, pool, tables, positions): every live lane its own blocks,
    scattered; past its frontier the null block, as the engine's table has it;
    the pool's rows hold values in every column, the padding too."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((LAYERS, BLOCKS, BS, W)), dtype)
    q = jnp.asarray(rng.standard_normal((len(POSITIONS), 1, heads, DN + DR)), dtype)
    kv_b = jnp.asarray(rng.standard_normal((R, heads, DN + DV)) * R ** -0.5, dtype)
    free = rng.permutation(np.arange(1, BLOCKS))
    tables = np.zeros((len(POSITIONS), WIDTH), np.int32)
    for lane, pos in enumerate(POSITIONS):
        if lane != NULL_LANE:
            blocks = pos // BS + 1
            tables[lane, :blocks], free = free[:blocks], free[blocks:]
    return q, kv_b, pool, jnp.asarray(tables), jnp.asarray(POSITIONS, jnp.int32)


def config(heads):
    return dataclasses.replace(CFG, num_heads=heads, num_kv_heads=heads)


@jax.jit
def twin(q, kv_b, pool, tables, positions, layer):
    """``SarvamDecode._latent_attention``'s read at one row a lane where the
    walk does not run: (lanes, N, d_v)."""
    seen = pool.reshape(LAYERS * BLOCKS, BS, W)[layer * BLOCKS + tables]
    seen = seen.reshape(seen.shape[0], RUNG, W)[..., :R + DR]
    return latent_attention(config(q.shape[2]), kv_b, q, seen, positions[:, None], absorbed=True)[:, 0]


@jax.jit
def o_lat_twin(q_abs, pool, tables, positions, layer):
    """What the kernel itself returns, from gathered rows in float32."""
    seen = pool.reshape(LAYERS * BLOCKS, BS, W)[layer * BLOCKS + tables]
    seen = seen.reshape(seen.shape[0], RUNG, W).astype(jnp.float32)
    scores = jnp.einsum("bnd,bsd->bns", q_abs.astype(jnp.float32), seen[..., :R + DR]) * CFG.softmax_scale()
    scores = jnp.where(jnp.arange(RUNG)[None, None] <= positions[:, None, None], scores, -1e30)
    return jnp.einsum("bns,bsr->bnr", jax.nn.softmax(scores, axis=-1), seen[..., :R])


@jax.jit
def _absorbed(q, kv_b):
    from neuronx_distributed_llama3_2_tpu.models.sarvam import absorb_query

    return absorb_query(config(q.shape[2]), kv_b, q)[:, 0]


_WALK = jax.jit(latent_decode_walk, static_argnames=("rank", "sm_scale", "kv_limit", "group"))


def walk_lat(q_abs, pool, tables, positions, layer, group):
    # the layer is an operand: the tests of one dtype and group share a compile
    return _WALK(q_abs, pool, tables, positions, jnp.int32(layer), rank=R, sm_scale=CFG.softmax_scale(),
                 kv_limit=RUNG, group=group)


def walk(q, kv_b, pool, tables, positions, layer, group):
    """The block as the model runs it: ``W_UK`` into q, the walk, ``W_UV``."""
    from neuronx_distributed_llama3_2_tpu.models.sarvam import absorb_output

    o_lat = walk_lat(_absorbed(q, kv_b), pool, tables, positions, layer, group)
    return absorb_output(config(q.shape[2]), kv_b, o_lat[:, None])[:, 0]


def live_lanes(a):
    return jnp.delete(a, NULL_LANE, axis=0)


def f32(a):
    return a.astype(jnp.float32)


@pytest.mark.parametrize("dtype,tol,group,layer", [
    (jnp.float32, 2e-6, 2, 0), (jnp.float32, 2e-6, 2, 1), (jnp.float32, 2e-6, 4, 0), (jnp.float32, 2e-6, 8, 1),
    (jnp.bfloat16, 2e-2, 2, 0), (jnp.bfloat16, 2e-2, 2, 1),
], ids=["f32-group2-layer0", "f32-group2-layer1", "f32-group4-layer0", "f32-group8-layer1",
        "bf16-group2-layer0", "bf16-group2-layer1"])
def test_the_walk_is_the_gather_and_absorbed_attention_through_a_permuted_table(dtype, tol, group, layer):
    """Ragged positions 0 / 3 / 4 / 9 / 15 / 16 / rung − 2, a group of 2 over
    walks of 1, 2, 3, 4, 5 and 8 blocks (one block to four groups), a layer's
    blocks at ``index + layer · num_blocks``. float32 to round-off; bfloat16 to the
    rounding of the scores and of p, which the two place differently."""
    operands = make(dtype)
    got = walk(*operands, layer, group)
    want = twin(*operands, jnp.int32(layer))
    assert got.dtype == dtype and got.shape == want.shape == (len(POSITIONS), 4, DV)
    err = jnp.max(jnp.abs(f32(live_lanes(got)) - f32(live_lanes(want))))
    assert float(err) <= tol * float(jnp.max(jnp.abs(f32(want))))
    # the other layer's blocks of the same index were not what was read
    other = twin(*operands, jnp.int32(1 - layer))
    assert float(jnp.max(jnp.abs(f32(live_lanes(got)) - f32(live_lanes(other))))) > 0.05


@pytest.mark.parametrize("heads", [32, 64], ids=lambda n: f"heads{n}")
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6), (jnp.bfloat16, 2e-2)], ids=["f32", "bf16"])
def test_the_cells_head_counts(heads, dtype, tol):
    """32 heads (xing) and 64 (sarvam) at the tiny row: ``o_lat`` against the
    float32 softmax over gathered rows."""
    q, kv_b, pool, tables, positions = make(dtype, heads)
    q_abs = _absorbed(q, kv_b)
    got = walk_lat(q_abs, pool, tables, positions, 1, 4)
    want = o_lat_twin(q_abs, pool, tables, positions, jnp.int32(1))
    assert got.shape == (len(POSITIONS), heads, R) and got.dtype == dtype
    err = jnp.max(jnp.abs(f32(live_lanes(got)) - live_lanes(want)))
    assert float(err) <= tol * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("group", [3, 4], ids=lambda g: f"group{g}")
@pytest.mark.parametrize("blocks", range(1, WIDTH + 1), ids=lambda n: f"walk{n}")
def test_walks_of_one_block_to_two_groups_and_one_and_more(blocks, group):
    """Every lane at the last row of its ``blocks``-th block: every count of a
    last group's copies, from one part-filled buffer to two whole ones and a
    block and past that."""
    q, kv_b, pool, tables, _ = make(jnp.float32)
    lane = 3                                                 # the lane whose table is full
    positions = jnp.full((len(POSITIONS),), blocks * BS - 1, jnp.int32)
    q_abs = _absorbed(q, kv_b)
    tables = jnp.broadcast_to(tables[lane], tables.shape)
    got = walk_lat(q_abs, pool, tables, positions, 0, group)
    want = o_lat_twin(q_abs, pool, tables, positions, jnp.int32(0))
    np.testing.assert_allclose(got, want, rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("group", [2, 8], ids=lambda g: f"group{g}")
def test_blocks_past_a_lanes_frontier_are_never_read(group):
    """Every block no lane's walk reaches, and the null block, hold 1e30: the
    output of the live lanes is the clean pool's bit for bit. (The twin reads
    them all and multiplies them by p == 0.)"""
    q, kv_b, pool, tables, positions = make(jnp.float32)
    q_abs = _absorbed(q, kv_b)
    clean = walk_lat(q_abs, pool, tables, positions, 1, group)
    reached = np.zeros((LAYERS, BLOCKS), bool)
    for lane, pos in enumerate(POSITIONS):
        if lane != NULL_LANE:
            reached[1, np.asarray(tables[lane, :pos // BS + 1])] = True
    spoil = jnp.asarray(~reached)[:, :, None, None]
    dirty = walk_lat(q_abs, jnp.where(spoil, 1e30, pool), tables, positions, 1, group)
    assert bool((live_lanes(dirty) == live_lanes(clean)).all())
    assert bool(jnp.isfinite(dirty[NULL_LANE]).all())        # 1e30 · weights that sum to 1: a number


def test_rows_of_the_last_block_past_the_position_are_masked():
    """Within the frontier's own block the rows after ``position`` are stale:
    1e30 there changes no bit."""
    q, kv_b, pool, tables, positions = make(jnp.float32)
    q_abs = _absorbed(q, kv_b)
    lane = 6
    assert POSITIONS[lane] == BS                             # block 1 holds row 4; its rows 5..7 are stale
    stale = pool.at[0, int(tables[lane, 1]), 1:].set(1e30)
    a = walk_lat(q_abs, pool, tables, positions, 0, 8)
    b = walk_lat(q_abs, stale, tables, positions, 0, 8)
    assert bool((a == b).all())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_what_the_pools_padding_columns_hold_changes_nothing(dtype):
    """The query is zero past the row's values, so the columns the write path
    zero-fills (576–639 at the published widths, 40–127 here) could hold
    anything finite: the output is the same bits."""
    q, kv_b, pool, tables, positions = make(dtype)
    q_abs = _absorbed(q, kv_b)
    garbage = pool.at[..., R + DR:].set(jnp.asarray(
        np.random.default_rng(9).standard_normal(pool.shape[:-1] + (W - R - DR,)) * 1e3, dtype))
    a = walk_lat(q_abs, pool, tables, positions, 1, 4)
    b = walk_lat(q_abs, garbage, tables, positions, 1, 4)
    assert bool((a == b).all())


def test_a_lane_on_the_null_block_walks_one_block_whatever_its_position():
    """An idle lane keeps stepping its position (``decode_step``, up to
    ``pos_cap``): the walk is bounded by its table's first entry, and the live
    lanes read what they read without it."""
    q, kv_b, pool, tables, positions = make(jnp.float32)
    q_abs = _absorbed(q, kv_b)
    far = positions.at[NULL_LANE].set(RUNG - 1)
    a = walk_lat(q_abs, pool, tables, positions, 0, 2)
    b = walk_lat(q_abs, pool, tables, far, 0, 2)
    assert bool((live_lanes(a) == live_lanes(b)).all())
    # block 0's rows alone, all of them visible from the far position
    only = o_lat_twin(q_abs, pool, jnp.zeros_like(tables), jnp.full_like(positions, BS - 1), jnp.int32(0))
    np.testing.assert_allclose(b[NULL_LANE], only[NULL_LANE], rtol=5e-6, atol=5e-6)


def test_a_query_wider_than_the_pools_row_is_refused():
    q, kv_b, pool, tables, positions = make(jnp.float32)
    with pytest.raises(ValueError, match="pool row"):
        latent_decode_walk(jnp.zeros((len(POSITIONS), 4, W + 1)), pool, tables, positions, 0, rank=R, sm_scale=1.0)


def test_a_group_of_more_than_eight_blocks_takes_its_copies_eight_at_a_time():
    """A wider table: walks of 1 to 21 blocks in groups of 12 — one run of
    eight and a rest of four, a rest alone, two whole groups and one block."""
    rng = np.random.default_rng(4)
    width, lanes = 24, 6
    pool = jnp.asarray(rng.standard_normal((LAYERS, 80, BS, W)), jnp.float32)
    q_abs = jnp.asarray(rng.standard_normal((lanes, 4, R + DR)), jnp.float32)
    blocks = (1, 7, 8, 12, 13, 21)
    free = rng.permutation(np.arange(1, 80))
    tables = np.zeros((lanes, width), np.int32)
    for lane, n in enumerate(blocks):
        tables[lane, :n], free = free[:n], free[n:]
    positions = jnp.asarray([n * BS - 2 for n in blocks], jnp.int32)
    got = latent_decode_walk(q_abs, pool, jnp.asarray(tables), positions, 1, rank=R,
                             sm_scale=CFG.softmax_scale(), group=12)
    seen = pool.reshape(LAYERS * 80, BS, W)[80 + tables].reshape(lanes, width * BS, W)
    scores = jnp.einsum("bnd,bsd->bns", q_abs, seen[..., :R + DR]) * CFG.softmax_scale()
    scores = jnp.where(jnp.arange(width * BS)[None, None] <= positions[:, None, None], scores, -1e30)
    want = jnp.einsum("bns,bsr->bnr", jax.nn.softmax(scores, axis=-1), seen[..., :R])
    np.testing.assert_allclose(got, want, rtol=5e-6, atol=5e-6)


# ---------------------------------------------------------------------------
# which read a decode program holds
# ---------------------------------------------------------------------------

FAMILIES = {
    "sarvam": (dataclasses.replace(SARVAM_CONFIGS["tiny-sarvam"], max_seq_len=64), SarvamForCausalLM),
    "xing": (dataclasses.replace(XING_CONFIGS["tiny-xing"], max_seq_len=64), XingForCausalLM),
}


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_the_kernel_mode_decides_which_read_a_decode_program_holds(mode, family, monkeypatch):
    """``interpret``: a ``pdecode`` holds the walk in both layer stacks' scan
    bodies — one traced kernel — and no (lanes, rung, pool row) array of
    gathered rows;
    ``reference`` keeps the gather (the CPU tier's twin). A block of rows
    (``psfx``), ``pctx`` and the dense slot cache keep it in either mode."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    tiny, causal = FAMILIES[family]
    model = decode_model_for(tiny)
    walks = mode == "interpret"
    (kind,) = model.cache_kinds
    assert model.decode_read(kind) == ("kernel" if walks else "gather")
    assert model.decode_read(kind, quantized=True) == "gather"
    assert model.paged_dispatch_path(1) == "gather"         # the k/v-by-head kernel: never
    params = jax.eval_shape(causal(tiny).init, jax.random.key(0))
    lanes, rung, bs = 3, 64, 4
    (gathered,) = model.forbidden_gather_shapes(lanes, rung)
    gathered = "[" + ",".join(str(d) for d in gathered) + "]"
    tables = jnp.zeros((lanes, rung // bs), jnp.int32)
    zeros = jnp.zeros((lanes,), jnp.int32)
    pool = jax.eval_shape(lambda: model.init_paged_cache(20, bs))
    step = str(jax.make_jaxpr(lambda p, c: model.decode_step(p, c, zeros, zeros, tables, kv_limit=rung))(params, pool))
    chunk = str(jax.make_jaxpr(lambda p, c: model.forward(
        p, c, jnp.zeros((lanes, 8), jnp.int32), zeros, block_tables=tables, kv_limit=rung))(params, pool))
    ctx = str(jax.make_jaxpr(lambda p, c: model.forward(
        p, c, jnp.zeros((lanes, 8), jnp.int32), zeros, block_tables=tables, context_encode=True))(params, pool))
    # the dense stack's scan body and the expert stack's each call the walk,
    # and the walk is a jit of its own: the program holds one kernel
    assert step.count("pallas_call") == (1 if walks else 0)
    assert step.count("name=_latent_walk") == (2 if walks else 0)
    assert (gathered in step) == (not walks)
    assert "pallas_call" not in chunk and gathered in chunk
    assert "pallas_call" not in ctx
    dense = jax.eval_shape(lambda: model.init_cache(lanes, rung))
    slot = str(jax.make_jaxpr(lambda p, c: model.forward(
        p, c, jnp.zeros((lanes, 1), jnp.int32), zeros, kv_limit=rung))(params, dense))
    assert "pallas_call" not in slot
