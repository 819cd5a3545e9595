"""The sparse layers' chunk-read kernel (``kernels/sparse_chunk_pallas.py``),
interpreted on the CPU in float32: against ``attend_tiles`` — its plain twin,
which walks 2,048-row tiles where the kernel walks 512 — at the published head
shape and tiny-sala's, over what a block of rows under a block mask has to get
right: a row with fewer blocks behind it than the selection takes, a kv tile
no row names, the diagonal block (a row sees positions at or before its own
only, whatever the mask names), both prefill buckets, a rung that is not whole
2,048-row tiles, two sequences at different positions. Then through
``SalaDecode._attend_sparse`` over a pool whose table is permuted, padded rows
past ``row_live`` beside real ones; what the kernel refuses and where the call
then goes; and which read a prefill program holds in each kernel mode. (The
engine's chunks through the kernel are ``tests/test_minicpm_sala_serving.py``'s;
the compiled call on a described v5e is ``tests/test_weight_placement.py``'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.kernels.sparse_chunk_pallas import (
    chunk_attend_fits, kv_tile, query_tile, sparse_chunk_attend,
)
from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (
    SALA_CONFIGS, attend_tiles, block_mask, select_blocks,
)
from tests.test_minicpm_sala_serving import TINY, params  # noqa: F401

PUBLISHED = (32, 2, 128, 64)          # query heads, kv heads, d, rows a block
SMALL = (4, 2, 16, 4)                 # tiny-sala's
# name: (heads shape, t, rung, first position of each sequence, what the mask is made of)
CASES = {
    # 1,500 .. 1,627 of a 2,048-row rung: three kv tiles and one wholly past the rows (skipped)
    "published-128-rows-mid-rung": (PUBLISHED, 128, 2048, (1500,), "random"),
    # pctx: the block's own rows alone, every row with fewer than 64 blocks behind it
    "published-own-rows-from-zero": (PUBLISHED, 128, 128, (0,), "selected"),
    # the selection's own lists at 64 of 104 blocks a row: ``taken`` false nowhere, blocks dropped
    "published-selected-6656": (PUBLISHED, 128, 6656, (6500,), "selected"),
    "published-512-rows": ((8, 2, 128, 64), 512, 1024, (512,), "random"),
    # 65 kv tiles of 512, 16.25 of the twin's
    "rung-33280": ((4, 2, 128, 64), 128, 33280, (33100,), "random"),
    "a-kv-tile-no-row-names": (PUBLISHED, 128, 2048, (1900,), "tile-1-dropped"),
    # the mask names blocks a row cannot see yet: the positions drop them
    "blocks-ahead-of-the-row-named": (SMALL, 16, 128, (40,), "all"),
    "tiny-two-sequences": (SMALL, 16, 128, (40, 97), "random"),
    "tiny-selected-few-blocks-behind": (SMALL, 16, 64, (8,), "selected"),
    "tiny-one-tile-rung": (SMALL, 8, 8, (0,), "all"),
}


def make(name):
    (n, nkv, d, bs), t, limit, starts, kind = CASES[name]
    b, blocks = len(starts), limit // bs
    keys = jax.random.split(jax.random.key(len(name)), 4)
    q = jax.random.normal(keys[0], (b, t, n, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, nkv, limit, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, nkv, limit, d), jnp.float32)
    start = jnp.asarray(starts, jnp.int32)
    q_pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
    own = (q_pos // bs)[:, :, None, None]                                  # (b, t, 1, 1)
    at = jnp.arange(blocks)
    if kind == "selected":
        c = SALA_CONFIGS["minicpm-sala" if bs == 64 else "tiny-sala"]
        pooled = jax.random.normal(keys[3], (b, blocks * c.kernels_per_block, nkv, d), jnp.float32)
        chosen, taken = select_blocks(q, pooled, q_pos, c)
        mask = block_mask(chosen, taken, blocks)
        if blocks > c.sparse_topk:
            assert int(mask.sum(-1).max()) == c.sparse_topk                # the selection drops blocks
        else:
            assert not bool(taken.all())                                   # fewer behind a row than it takes
    elif kind == "all":
        mask = jnp.ones((b, t, nkv, blocks), bool)
    else:
        mask = (jax.random.bernoulli(keys[3], 0.3, (b, t, nkv, blocks)) | (at == own)) & (at <= own)
        if kind == "tile-1-dropped":
            per = kv_tile(limit) // bs
            mask = mask & ~((at >= per) & (at < 2 * per))
    return q, k, v, mask, start, q_pos, bs


def twin(q, k, v, mask, q_pos, bs):
    """``attend_tiles`` as ``SalaDecode`` drives it: 2,048-row tiles, the last one padded."""
    blocks = mask.shape[-1]
    tile = min(blocks, max(2048 // bs, 1))
    tiles = -(-blocks // tile)
    pad = ((0, 0), (0, tiles * tile * bs - k.shape[2]), (0, 0), (0, 0))
    rows_k, rows_v = (jnp.pad(jnp.swapaxes(a, 1, 2), pad) for a in (k, v))
    read = lambda i: tuple(  # noqa: E731
        jax.lax.dynamic_slice_in_dim(a, i * tile * bs, tile * bs, axis=1) for a in (rows_k, rows_v))
    return attend_tiles(q, q_pos, mask, read, tiles, tile, bs)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")


@pytest.mark.parametrize("name", CASES)
def test_the_kernel_matches_the_tile_walk(interpreted, name):
    q, k, v, mask, start, q_pos, bs = make(name)
    assert chunk_attend_fits(q.shape[1], k.shape[2], bs)
    got = sparse_chunk_attend(q, k, v, mask, start, bs)
    want = twin(q, k, v, mask, q_pos, bs)
    assert got.shape == want.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_a_row_sees_no_position_past_its_own(interpreted):
    """Under a mask that names every block, moving the keys and values past a
    row's own position moves nothing of the row: the diagonal block is cut by
    position, the blocks ahead whole."""
    q, k, v, mask, start, q_pos, bs = make("blocks-ahead-of-the-row-named")
    got = sparse_chunk_attend(q, k, v, mask, start, bs)
    row = 5                                                                # position 45: inside block 11
    ahead = jnp.arange(k.shape[2]) > q_pos[0, row]
    moved = sparse_chunk_attend(
        q, jnp.where(ahead[None, None, :, None], 7.0, k), jnp.where(ahead[None, None, :, None], -3.0, v),
        mask, start, bs)
    assert bool((moved[0, :row + 1] == got[0, :row + 1]).all())
    assert float(jnp.abs(moved[0, row + 1:] - got[0, row + 1:]).max()) > 1e-3


def test_bfloat16_operands_keep_float32_state(interpreted):
    """As served: bfloat16 q, k, v — the dots in bfloat16 with float32
    accumulation, the weights cast to v's dtype — within bfloat16's rounding of
    the twin on the same operands."""
    q, k, v, mask, start, q_pos, bs = make("published-128-rows-mid-rung")
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    got = sparse_chunk_attend(q, k, v, mask, start, bs)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32), twin(q, k, v, mask, q_pos, bs).astype(jnp.float32), rtol=0.02, atol=0.02)


# ---------------------------------------------------------------------------
# through the decode model: the pool, the table, the padded rows
# ---------------------------------------------------------------------------

def attend_through_the_model(monkeypatch, mode, t, limit, starts, live, tables):
    """``SalaDecode._attend_sparse`` on layer 1 of a two-layer pool of random
    rows: (att, the pool's three leaves after the write)."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    c = TINY
    b = len(starts)
    keys = jax.random.split(jax.random.key(t + limit), 4)
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(a.ndim + a.shape[1]), a.shape, a.dtype),
        model.init_paged_cache(80, c.sparse_block_size, state_blocks=2)).rows
    q = jax.random.normal(keys[0], (b, t, c.num_heads, c.head_dim), jnp.float32)
    k = jax.random.normal(keys[1], (b, t, c.num_kv_heads, c.head_dim), jnp.float32)
    v = jax.random.normal(keys[2], (b, t, c.num_kv_heads, c.head_dim), jnp.float32)
    pos = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)
    return jax.jit(lambda *a: model._attend_sparse(*a, limit))(
        q, k, v, tuple(pool), jnp.int32(1), pos, jnp.asarray(live, jnp.int32), jnp.asarray(tables, jnp.int32))


@pytest.mark.parametrize("t,limit,starts,live", [
    (16, 128, (64, 96), (16, 5)), (8, 64, (40,), (3,)), (32, 32, (0,), (32,))],
    ids=["two-lanes-one-padded", "a-padded-bucket-of-8", "own-rows-from-zero"])
def test_a_permuted_table_and_padded_rows_through_the_pool(monkeypatch, t, limit, starts, live):
    """The rung's blocks gathered through a permuted table, a lane whose rows
    past ``row_live`` are padding beside one whose rows are all real: the
    kernel's rows are the tile walk's, padding and all, and the pool's leaves
    are written the same."""
    rng = np.random.default_rng(t)
    width = 128 // TINY.sparse_block_size + 8
    tables = np.stack([1 + rng.permutation(79)[:width] for _ in starts])
    got, pool = attend_through_the_model(monkeypatch, "interpret", t, limit, starts, live, tables)
    want, pool_want = attend_through_the_model(monkeypatch, "reference", t, limit, starts, live, tables)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for a, b in zip(pool, pool_want):
        assert bool((a == b).all())


# ---------------------------------------------------------------------------
# what the kernel refuses, and which read a program holds
# ---------------------------------------------------------------------------

def test_shapes_the_kernel_cannot_take_are_refused():
    bs = 64
    # both buckets over all eight rungs of ``sala-longctx-steady``, and pctx's own rows
    for t in (128, 512):
        assert chunk_attend_fits(t, t, bs)
        assert all(chunk_attend_fits(t, rung, bs)
                   for rung in (2048, 4096, 8192, 12288, 16384, 20480, 26624, 33280))
    assert (query_tile(512), query_tile(128), query_tile(16), kv_tile(33280), kv_tile(64)) == (128, 128, 16, 512, 64)
    assert not chunk_attend_fits(10, 128, 4)          # rows that are not whole sublanes
    assert not chunk_attend_fits(192, 2048, bs)       # nor whole query tiles
    assert not chunk_attend_fits(128, 2048 + 64, bs)  # a rung no kv tile divides
    assert not chunk_attend_fits(128, 96, bs)         # a kv tile that is not whole blocks
    assert not chunk_attend_fits(128, 2048, 2)        # a kv tile's flags wider than a vreg
    q, kv = jnp.zeros((1, 10, 4, 16)), jnp.zeros((1, 2, 128, 16))
    with pytest.raises(ValueError, match="whole query tiles"):
        sparse_chunk_attend(q, kv, kv, jnp.ones((1, 10, 2, 32), bool), jnp.zeros((1,), jnp.int32), 4)
    with pytest.raises(ValueError, match="whole query tiles"):     # a mask of another rung's blocks
        sparse_chunk_attend(q[:, :8], kv, kv, jnp.ones((1, 8, 2, 16), bool), jnp.zeros((1,), jnp.int32), 4)


def prefill_jaxpr(model, params, t, kv_limit, fresh):  # noqa: F811
    pool = model.init_paged_cache(40, TINY.sparse_block_size, state_blocks=3)
    tables = jnp.asarray([1 + np.arange(36)], jnp.int32)
    return str(jax.make_jaxpr(lambda p, c: model.forward(
        p, c, jnp.ones((1, t), jnp.int32), jnp.full((1,), 0 if fresh else 32, jnp.int32), context_encode=fresh,
        block_tables=tables, state_tables=jnp.asarray([[1]], jnp.int32), kv_limit=kv_limit,
        return_hidden=True))(params, pool))


@pytest.mark.parametrize("mode", ["reference", "interpret", "compiled"])
def test_the_kernel_mode_decides_which_read_a_prefill_program_holds(params, mode, monkeypatch):  # noqa: F811
    """``reference`` keeps the tile walk (the CPU tier's twin); ``interpret``
    and ``compiled`` hold one ``sparse_chunk_attend`` in each run of sparse
    layers, ``pctx`` and ``psfx`` alike; a shape the kernel refuses goes to the
    tile walk in any mode; a decode step gathers its chosen blocks; a model
    whose layers read every row never asks."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    kernel = mode != "reference"
    assert model.chunk_read() == ("kernel" if kernel else "tiles") and LlamaDecode(TINY).chunk_read() is None
    assert LlamaDecode(TINY).chunk_tiles(16, 32, 64) is None
    for t, kv_limit, fresh in ((16, 64, False), (16, None, True)):
        text = prefill_jaxpr(model, params, t, kv_limit, fresh)
        assert text.count("sparse_chunk_attend") == (2 if kernel else 0), (t, fresh)    # tiny-sala: L S L L S
    assert "sparse_chunk_attend" not in prefill_jaxpr(model, params, 10, 64, False)     # 10 rows: refused
    # what a prefill dispatch record says of it: (kernel, tiles to the last row, tiles in the rung)
    assert model.chunk_tiles(16, 32, 64) == (kernel, 1, 1) and model.chunk_tiles(16, 0, None) == (kernel, 1, 1)
    assert model.chunk_tiles(10, 32, 64) == (False, 1, 1)
    published = decode_model_for(SALA_CONFIGS["minicpm-sala"])
    assert published.chunk_tiles(512, 17000, 20480) == ((True, 35, 40) if kernel else (False, 10, 10))
    assert published.chunk_tiles(512, 32768, 33280) == ((True, 65, 65) if kernel else (False, 17, 17))
    pool = model.init_paged_cache(40, TINY.sparse_block_size, state_blocks=3)
    step = str(jax.make_jaxpr(lambda p, c: model.decode_step(
        p, c, jnp.asarray([5], jnp.int32), jnp.asarray([17], jnp.int32), jnp.asarray([1 + np.arange(36)], jnp.int32),
        kv_limit=64))(params, pool))
    assert "sparse_chunk_attend" not in step
