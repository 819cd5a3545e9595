"""A stack whose attention layers choose the blocks they read and whose other
layers keep a matrix state a lane (MiniCPM-SALA) under the paged serving
engine at the tiny size, float32 on the CPU: logits and tokens against the
plain reference's full forward (no cache, no pooled-key pool, no chunk form,
no tile) at contexts long enough that the selection drops blocks, and what
such a stack forces — pooled keys beside the rows through the same table, a
kernel whose rows straddle two calls, a result that does not depend on the
chunking, the zero state of a reused slot, the refusals."""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig, HybridCache, InferenceEngine, SalaDecode,
)
from neuronx_distributed_llama3_2_tpu.inference.model import (
    CacheKind, MatrixState, SparseRows, cache_block_bytes, cache_row_bytes, decode_model_for,
)
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import SALA_CONFIGS, SalaForCausalLM
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine, audit_engine
from tests.drained_policy import LOOPS, loop_policy

TOP = 128
TINY = dataclasses.replace(SALA_CONFIGS["tiny-sala"], max_seq_len=TOP)
BS, CHUNK = 4, 16                     # a pool block is one selection block
SIZES = {"lanes": 4, "block_size": BS, "max_seq_len": TOP, "pool_blocks": 144,
         "prefill_chunk_tokens": CHUNK, "prefill_buckets": [8, 16], "kv_buckets": [64, TOP]}
TOL = 1e-4
# the kernel mode the CPU tier names, and the one that runs the sparse layers'
# chunk read through ``kernels/sparse_chunk_pallas.py``, interpreted
MODES = ["reference", "interpret"]
STATE_BYTES = 3 * 4 * 16 * 16 * 4      # Lightning layers x heads x d x d, float32


def _tool(name):
    path = os.path.join(spec.HERE, "tools", name + ".py")
    mod = importlib.util.module_from_spec(importlib.util.spec_from_file_location(name, path))
    mod.__spec__.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    fam = spec.load_family("minicpm_sala")
    fam.model_config({"rehearsal": {"preset": "tiny-sala"}}, True)
    return fam


@pytest.fixture(scope="module")
def params():
    """Kernels and the embedding five times as large, as
    ``tests/test_minicpm_sala.py``'s: a wrong block moves the logits by percent."""
    params = jax.jit(SalaForCausalLM(TINY).init)(jax.random.key(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 5.0 if path[-1].key in ("kernel", "embedding", "gate_up")
        or path[-1].key.endswith("_kernel") else a, params)


def serving(params, new_tokens=6, policy=None, **paged):
    paged = {"block_size": BS, "num_blocks": 144, "prefill_chunk_tokens": CHUNK,
             "prefill_buckets": (8, 16), "kv_buckets": (64, TOP), **paged}
    engine = InferenceEngine(TINY, params, max_batch=4, max_seq_len=TOP, buckets=[16, 64, TOP])
    return PagedServingEngine(engine, GenerationConfig(max_new_tokens=new_tokens),
                              PagedConfig(**paged), policy=policy)


@functools.lru_cache(maxsize=None)
def _jitted_reference(fam):
    cfg = fam.reference_config(TINY)
    return jax.jit(lambda p, i: fam.reference.forward_logits(p, cfg, i))


def reference_logits(fam, params, sequence):
    """The reference at one fixed length (it is causal: padding after the last
    token changes no earlier row), so that it compiles once."""
    padded = jnp.asarray([list(sequence) + [0] * (TOP - len(sequence))])
    with jax.default_matmul_precision("highest"):
        return np.asarray(_jitted_reference(fam)(params, padded)[0, :len(sequence)])


def reference_tokens(fam, params, prompt, new_tokens):
    seq = list(prompt)
    for _ in range(new_tokens):
        seq.append(int(np.argmax(reference_logits(fam, params, seq)[-1])))
    return seq[len(prompt):]


def prompts_of(rng, lengths):
    return [rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in lengths]


def clean(srv):
    assert srv.allocator.leak_check() == [] and audit_engine(srv) == []


def as_the_engine_runs_it(model, params, pool, prompt, fed, *, chunk=CHUNK, buckets=(8, 16),
                          lane=0, lanes=3, first_block=1):
    """Logits of every real row of ``prompt + fed`` through the calls the
    engine's programs make, lengths and slots included: ``pctx`` over the
    first chunk, ``psfx`` over the later ones at the smallest kv rung — the
    last one padded to its bucket, its live length handed on — then
    ``pdecode`` steps in a batch of ``lanes`` whose other lanes are idle (null
    table, their own slot named). Returns (rows, the pool)."""
    blocks = -(-(len(prompt) + len(fed)) // BS)
    width = TOP // BS + -(-max(buckets) // BS)
    table = np.zeros((lanes, width), np.int32)
    table[lane, :blocks] = first_block + np.arange(blocks)
    slots = jnp.asarray(1 + np.arange(lanes, dtype=np.int32))[:, None]
    head = model._model()._logits
    rung = lambda n: next(r for r in (64, TOP) if r >= n)  # noqa: E731
    rows, programs = [], {}

    def program(fresh, kv):
        # one program a (kind of call, rung), as the engine has — and jitted
        # afresh every time this is called: a planted fault must be traced
        if (fresh, kv) not in programs:
            programs[fresh, kv] = jax.jit(lambda p, c, i, s, n: model.forward(
                p, c, i, s, None, context_encode=fresh, return_hidden=True,
                kv_limit=None if fresh else kv, block_tables=jnp.asarray(table[lane:lane + 1]),
                row_live=n, state_tables=slots[lane:lane + 1]))
        return programs[fresh, kv]

    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        bucket = next(b for b in buckets if b >= len(piece))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(piece)] = piece
        hidden, pool = program(start == 0, rung(min(start + bucket, TOP)))(
            params, pool, jnp.asarray(ids), jnp.full((1,), start, jnp.int32),
            jnp.asarray([len(piece)], jnp.int32))
        rows.append(head(params, hidden)[0, :len(piece)])
    mask = jnp.zeros((lanes,), jnp.int32).at[lane].set(1)
    step = jax.jit(lambda p, c, t, pos, kv: model.decode_step(
        p, c, t, pos, jnp.asarray(table), kv_limit=kv, state_tables=slots), static_argnums=4)
    for i, token in enumerate(fed):
        at = len(prompt) + i
        logits, _, pool = step(params, pool, mask * token, mask * at, rung(at + 1))
        rows.append(logits[lane:lane + 1])
    return np.asarray(jnp.concatenate(rows, axis=0)), pool


# ---------------------------------------------------------------------------
# the decode class, its cache and what the engine lays out
# ---------------------------------------------------------------------------

def test_the_family_gets_its_decode_class_and_a_cache_of_two_kinds_and_three_row_leaves():
    model = decode_model_for(TINY)
    assert isinstance(model, SalaDecode) and model.cache_is_positional and model.keeps_state
    assert model.cache_kinds == (CacheKind("rows", 2, None), CacheKind("state", 3, 0, state=True))
    assert [model.decode_read(kind) for kind in model.cache_kinds] == ["gather", "gather"]
    assert not model.uses_state_kernel() and model.chunk_scan() == "chunk" and model.chunk_read() == "tiles"
    pool = model.init_paged_cache(9, BS, state_blocks=5)
    assert isinstance(pool, HybridCache) and (pool.num_blocks, pool.block_size) == (9, BS)
    assert isinstance(pool.rows, SparseRows) and isinstance(pool.state, MatrixState)
    # rows: a block's rows of one kv head together; pooled keys: 2 a block of 4 rows, in whole tiles of 16
    assert pool.rows.k.shape == pool.rows.v.shape == (2, 9, 2, BS, 16) and pool.rows.positions == 2 * 9 * BS
    assert pool.rows.pooled.shape == (2, 32, 2 * 16) and pool.rows.pooled.dtype == pool.rows.k.dtype
    assert pool.state.s.shape == (3, 5, 4, 16, 16) and pool.state.s.dtype == jnp.float32
    assert model.init_paged_cache(9, BS).state.s.shape[1] == 9           # as many slots as blocks, up to 33
    assert model.init_paged_cache(64, BS).state.s.shape[1] == 33
    assert model.init_paged_cache(9, BS, jnp.bfloat16).state.s.dtype == jnp.bfloat16
    assert cache_block_bytes(pool.state) == STATE_BYTES == 3 * TINY.state_bytes_per_layer()
    assert cache_row_bytes(pool.rows) == (2 * 9 * BS + 32) * 32 * 4 // (9 * BS)      # k, v and the pooled keys' share
    tokens, at = jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32)
    with pytest.raises(NotImplementedError, match="tree verification"):
        model.forward({}, pool, tokens, at, tree=(jnp.zeros((2,), jnp.int32), jnp.ones((2, 2), bool)))
    with pytest.raises(NotImplementedError, match="paged only"):
        model.forward({}, pool, tokens, at)
    with pytest.raises(NotImplementedError, match="no dense slot cache"):
        model.init_cache(2, 64)
    with pytest.raises(ValueError, match="a pool block is one selection block"):
        model.init_paged_cache(9, 16)
    with pytest.raises(NotImplementedError, match="running sum of every row"):
        model.init_paged_cache(9, BS, kv_cache_dtype="int8")


@pytest.mark.parametrize("context,want", [
    (1, (1, 1)), (4, (4, 1)), (5, (5, 2)), (24, (24, 3)), (25, (21, 4)), (100, (24, 3)), (101, (21, 4))])
def test_rows_a_decode_step_reads_are_bounded(context, want):
    """A layer's rows a query at the last of ``context`` rows reads, and the
    blocks among them taken unscored (the first, the window's): at most 6
    blocks of 4 whatever the context."""
    assert decode_model_for(TINY).selected_rows(context) == want
    published = decode_model_for(SALA_CONFIGS["minicpm-sala"])
    assert published.selected_rows(4096) == (4096, 33) and published.selected_rows(33000) == (64 * 63 + 40, 34)


def test_the_engine_lays_a_slot_a_lane_beside_the_allocators_blocks(params):
    srv = serving(params)
    assert srv._lane_kind.state and srv._lane_blocks == 1 and srv._has_state and not srv._share_prefixes
    np.testing.assert_array_equal(srv._lane_tables, [[1], [2], [3], [4]])
    assert srv.cache.state.s.shape[1] == 1 + 4 and srv.cache.rows.k.shape[1] == 144
    assert srv.table_width == TOP // BS + CHUNK // BS
    clean(srv)


@pytest.mark.parametrize("knobs,word", [
    ({"spec_draft_tokens": 2}, "spec_draft_tokens > 0"),
    ({"fused_step": True}, "fused_step"),
    ({"spill_enabled": True, "host_tier_bytes": 1 << 20}, "spill_enabled"),
])
def test_what_a_state_cannot_undo_is_refused_at_construction(params, knobs, word):
    with pytest.raises(ValueError, match=f"{word} is not available for SalaDecode.*state layers keep a state a lane"):
        serving(params, **knobs)


# ---------------------------------------------------------------------------
# logits against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt,chunk,buckets", [
    (93, 16, (8, 16)), (93, 10, (10,)), (96, 32, (32,)), (5, 16, (8, 16))],
    ids=["padded-last-chunk", "chunks-that-cut-blocks-and-kernels", "whole-rungs", "under-a-bucket"])
@pytest.mark.parametrize("mode", MODES)
def test_chunks_however_cut_and_padded_match_the_reference(fam, params, n_prompt, chunk, buckets, mode, monkeypatch):
    """93 rows = 23 blocks behind the last row, of which it reads 6; chunks of
    16 (a kernel of 4 rows every 2 straddles every boundary), of 10 (a
    boundary inside a block and inside a kernel's stride), of 32; the last one
    padded; the request on lane 1 of three, lanes 0 and 2 idle beside it on
    the null table, whose slots come back bit for bit. Under ``interpret`` the
    chunks' sparse reads go through the chunk-read kernel — but the chunks of
    10 rows, which it refuses and the tile walk takes."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    assert model.chunk_tiles(max(buckets), 16, 64)[0] == (mode == "interpret" and chunk != 10)
    rng = np.random.default_rng(n_prompt + chunk)
    prompt, fed = rng.integers(1, 256, n_prompt).tolist(), rng.integers(1, 256, 5).tolist()
    pool = jax.tree.map(
        lambda a: 0.1 * jax.random.normal(jax.random.key(a.ndim), a.shape, a.dtype),
        model.init_paged_cache(40, BS, state_blocks=4))
    got, after = as_the_engine_runs_it(model, params, pool, prompt, fed, chunk=chunk, buckets=buckets, lane=1)
    np.testing.assert_allclose(got, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)
    for slot in (1, 3):                                                    # lanes 0 and 2
        assert bool((after.state.s[:, slot] == pool.state.s[:, slot]).all())
    assert float(jnp.abs(after.state.s[:, 2] - pool.state.s[:, 2]).max()) > 0


def test_the_pooled_keys_in_the_pool_are_the_means_of_the_rows_beside_them(params):
    model = decode_model_for(TINY)
    prompt = prompts_of(np.random.default_rng(8), (61,))[0]
    _, pool = as_the_engine_runs_it(model, params, model.init_paged_cache(40, BS, state_blocks=4), prompt, [3, 4])
    rows = np.asarray(jnp.swapaxes(pool.rows.k[:, 1:17], 2, 3)).reshape(2, 64, 32)   # blocks 1.. in order: positions 0..63
    pooled = np.asarray(pool.rows.pooled[:, 2:34])                         # block 1's first kernel is row 2
    for j in range(30):                                                    # kernels complete by row 62
        np.testing.assert_allclose(pooled[:, j], rows[:, 2 * j:2 * j + 4].mean(axis=1), rtol=1e-5, atol=1e-6)
    assert not pooled[:, 30].any()                                          # rows 60..63: not complete yet


def test_a_second_request_through_the_same_slot_and_blocks_matches_the_reference(fam, params):
    model = decode_model_for(TINY)
    first, second = prompts_of(np.random.default_rng(13), (90, 53))
    fed = [7, 11, 13, 17]
    _, pool = as_the_engine_runs_it(model, params, model.init_paged_cache(40, BS, state_blocks=4), first, fed)
    assert float(jnp.abs(pool.state.s[:, 1]).max()) > 0 and float(jnp.abs(pool.rows.pooled).max()) > 0
    got, _ = as_the_engine_runs_it(model, params, pool, second, fed)
    np.testing.assert_allclose(got, reference_logits(fam, params, second + fed), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("fault", ["no_selection", "unpooled", "no_decay", "rotary", "no_carry", "no_window"])
@pytest.mark.parametrize("mode", MODES)
def test_each_planted_fault_fails_the_comparison(fam, params, fault, mode, monkeypatch):
    """The faults ``benchmarks/tools/check_sala_variant.py`` plants on the
    chip, here against every row, where a sound run holds every row inside a
    hundredth of a percent — through the tile walk and through the chunk-read
    kernel alike."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    for owner, name, value in _tool("check_sala_variant").FAULTS[fault]():
        monkeypatch.setattr(owner, name, value)
    model = decode_model_for(TINY)
    prompt, fed = prompts_of(np.random.default_rng(13), (93,))[0], [7, 11, 13, 17]
    got, _ = as_the_engine_runs_it(model, params, model.init_paged_cache(40, BS, state_blocks=4), prompt, fed)
    want = reference_logits(fam, params, prompt + fed)
    error = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert error.max() > 30 * TOL, (fault, error.max())
    if fault in ("no_selection", "unpooled", "no_window"):                # while every block is taken, nothing shows
        assert error[:24].max() < TOL


def test_a_state_pool_in_bfloat16_moves_the_rows(params):
    model = decode_model_for(TINY)
    prompt, fed = prompts_of(np.random.default_rng(13), (93,))[0], [7, 11, 13, 17]
    plain, _ = as_the_engine_runs_it(model, params, model.init_paged_cache(40, BS, state_blocks=4), prompt, fed)
    low = model.init_paged_cache(40, BS, state_blocks=4)
    low = low._replace(state=MatrixState(s=low.state.s.astype(jnp.bfloat16)))
    got, _ = as_the_engine_runs_it(model, params, low, prompt, fed)
    moved = np.linalg.norm(got - plain, axis=-1) / np.linalg.norm(plain, axis=-1)
    # the first chunk starts from zeros and only writes the state; every later row reads it back rounded
    assert moved[:CHUNK].max() == 0.0 and np.median(moved[CHUNK:]) > 3 * TOL


def test_the_benchmarks_check_passes(fam, params):
    spec_ = {"prompt_tokens": 80, "decode_steps": 4, "tolerance": TOL, "cache_tolerance": TOL}
    srv = serving(params)
    got = check.serving_engine(srv, fam, TINY, spec_, SIZES, seed=3)
    assert got["ok"] and got["engine_tokens"]["near_reference_max"] == 1.0, got
    assert got["all_rows"]["max"] < TOL and got["cache"]["plain_pool_is_own"] and got["rows"] == 84
    clean(srv)


# ---------------------------------------------------------------------------
# tokens through the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loop", LOOPS)
def test_long_chunked_padded_prompts_give_the_references_tokens(fam, params, loop):
    """Six requests on four lanes (so two run through used slots and blocks),
    contexts to 110 rows, prompts whose last chunk fills none of the buckets,
    continuous batching, look-ahead and drained steps alike."""
    prompts = prompts_of(np.random.default_rng(3), (93, 37, 5, 101, 64, 77))
    srv = serving(params, new_tokens=8, policy=loop_policy(loop))
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == reference_tokens(fam, params, prompt, 8), (rid, len(prompt))
    m = srv.metrics
    assert m.prefill_chunks > 0 and m.state_resets == len(prompts) and m.state_kernel_steps == 0
    assert (m.decode_steps_async > 0) == (loop == "lookahead")
    clean(srv)


@pytest.mark.parametrize("mode", MODES)
def test_a_traced_engine_records_both_kinds_the_pooled_leaf_and_the_rows_a_step_reads(params, mode, monkeypatch):
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    kernel = mode == "interpret"
    srv = serving(params, trace_enabled=True, prewarm=True)
    for p in prompts_of(np.random.default_rng(2), (90, 33)):
        srv.submit(p)
    srv.run_to_completion()
    setup = srv.tracer.timeline()["setup"]
    row_bytes = cache_row_bytes(srv.cache.rows)
    assert setup["state_bytes_per_lane"] == STATE_BYTES and setup["cache_row_bytes"] == row_bytes
    assert row_bytes > 2 * 32 * 4                                          # k and v and the pooled keys' share
    assert setup["cache_kinds"] == {
        "rows": {"layers": 2, "rows_per_lane": None, "row_bytes": row_bytes, "decode_read": "gather",
                 "chunk_read": "kernel" if kernel else "tiles"},
        "state": {"layers": 3, "rows_per_lane": 0, "state_bytes": STATE_BYTES, "chunk_scan": "chunk",
                  "decode_read": "gather"},
    }
    records = [args for step in srv.tracer.timeline()["steps"] for ph, name, _, _, args in step["events"]
               if ph == "X" and name == "dispatch"]
    # the step passes over every slot of a layer where it lies: four lanes' and the null slot
    assert records and all(a["state_lanes"] == a["lanes"] and a["state_slots_passed"] == 5 for a in records)
    assert all(a["sparse_rows_cached"] == a["rows"] for a in records)
    # a step reads at most 6 blocks of 4 a lane a layer, whatever the lane holds
    assert all(a["sparse_rows_read"] <= 24 * a["lanes"] for a in records)
    assert any(a["sparse_rows_cached"] > 3 * a["sparse_rows_read"] for a in records)
    assert all(a["lanes"] <= a["sparse_blocks_forced"] <= 4 * a["lanes"] for a in records)
    # a prefill dispatch: the kv tiles of a sparse layer's read to the chunk's last row and in its rung — at
    # this size one tile holds any rung — and whether its program held the chunk-read kernel
    prefills = [args for step in srv.tracer.timeline()["steps"] for ph, name, _, _, args in step["events"]
                if ph == "X" and name in ("prefill", "prefill_chunk")]
    assert len(prefills) == 6 + 3 and all(a["sparse_tiles_walked"] == a["sparse_tiles_rung"] == 1 for a in prefills)
    assert srv.metrics.snapshot()["sparse_kernel_chunks"] == (len(prefills) if kernel else 0)
    clean(srv)
