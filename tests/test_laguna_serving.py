"""A stack whose layers cache different things (window layers beside full
ones) under the paged serving engine at the tiny size, float32 on the CPU: the
full layers' rows in the block pool, the window layers' in a ring of rows a
lane that the engine lays out itself. Logits and tokens against the plain
reference's full forward (which has no cache at all) with the ring wrapped
several times, and what the ring forces on the engine — bucket padding, a
reused lane, no prefix sharing, preemption, the refusals, the accounting."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    CacheKind, GenerationConfig, InferenceEngine, LagunaDecode, MixedKVCache, PagedKVCache,
)
from neuronx_distributed_llama3_2_tpu.inference.model import cache_row_bytes, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.models.laguna import LAGUNA_CONFIGS, LagunaForCausalLM
from neuronx_distributed_llama3_2_tpu.models.llama import LLAMA_CONFIGS
from neuronx_distributed_llama3_2_tpu.parallel.state import initialize_model_parallel
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine, audit_engine
from neuronx_distributed_llama3_2_tpu.serving.accounting import EngineDims, analytic_profile
from tests.drained_policy import LOOPS, loop_policy

TINY = dataclasses.replace(LAGUNA_CONFIGS["tiny-laguna"], max_seq_len=128)
BS, CHUNK, LANES = 4, 16, 4
RING_BLOCKS = 6                 # window 8 - 1 + top rung 16 = 23 rows -> 6 blocks of 4
RING = RING_BLOCKS * BS
SIZES = {"lanes": LANES, "block_size": BS, "max_seq_len": 128, "pool_blocks": 140,
         "prefill_chunk_tokens": CHUNK, "prefill_buckets": [8, 16], "kv_buckets": [128]}
TOL = 1e-4
ROW = 2 * 2 * 16 * 4            # k and v x kv heads x head x float32, a layer
# the kernel mode decides a layer's decode read, full or window: the gather and
# ``masked_attention`` ("reference", this tier's), or the block walk interpreted
MODES = ("reference", "interpret")


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("laguna")


@pytest.fixture(scope="module")
def params():
    return jax.jit(LagunaForCausalLM(TINY).init)(jax.random.key(0))


def engine(params, **kw):
    return InferenceEngine(TINY, params, max_batch=kw.pop("max_batch", LANES), max_seq_len=128,
                           buckets=[8, 16, 32, 128], **kw)


def serving(params, new_tokens=6, policy=None, max_batch=LANES, **paged):
    paged = {"block_size": BS, "num_blocks": 140, "prefill_chunk_tokens": CHUNK,
             "prefill_buckets": (8, 16), "kv_buckets": (128,), **paged}
    # programs compile on first use: a test builds the ones it dispatches
    return PagedServingEngine(engine(params, max_batch=max_batch), GenerationConfig(max_new_tokens=new_tokens),
                              PagedConfig(**paged), policy=policy)


_REFERENCE = {}


def reference_logits(fam, params, ids):
    """The reference's logits for ``ids``: one causal pass over 128 rows (one
    compile), the rows past ``ids`` padding that no earlier row sees."""
    if "fn" not in _REFERENCE:
        cfg = fam.reference_config(TINY)
        _REFERENCE["fn"] = jax.jit(lambda p, i: fam.reference.forward_logits(p, cfg, i))
    padded = np.zeros((1, 128), np.int32)
    padded[0, :len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REFERENCE["fn"](params, jnp.asarray(padded)))[0, :len(ids)]


def reference_tokens(fam, params, prompt, new_tokens):
    """Greedy continuation by the reference's full forward, one token at a time."""
    seq = list(prompt)
    for _ in range(new_tokens):
        seq.append(int(np.argmax(reference_logits(fam, params, seq)[-1])))
    return seq[len(prompt):]


def prompts_of(rng, lengths):
    return [rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in lengths]


def clean(srv):
    assert srv.allocator.leak_check() == [] and audit_engine(srv) == []


_JITTED = {}


def jitted(model):
    """``forward`` and ``decode_step`` of ``model`` under jit, once a model: the
    tests below share their shapes."""
    if model not in _JITTED:
        _JITTED[model] = (
            jax.jit(model.forward, static_argnames=("context_encode", "return_hidden", "kv_limit")),
            jax.jit(model.decode_step, static_argnames=("kv_limit",)))
    return _JITTED[model]


def ringed_logits(model, params, prompt, fed, pieces, lane=1, lanes=3, pad_last_to=None):
    """Logits of every position of ``prompt + fed``: the prompt written in
    ``pieces`` (a ``pctx`` then ``psfx`` calls, the last one padded with
    zeros to ``pad_last_to`` rows where given), then ``fed`` by ``decode_step``
    in a batch of ``lanes`` whose others idle — the full kind through a block
    table, the window kind through the lane's ring, as the engine calls."""
    n = len(prompt) + len(fed)
    blocks = -(-n // BS) + CHUNK // BS
    cache = model.init_paged_cache(1 + blocks, BS, window_blocks=1 + lanes * RING_BLOCKS)
    table = np.zeros((lanes, blocks), np.int32)
    table[lane] = 1 + np.arange(blocks)
    rings = (1 + np.arange(lanes * RING_BLOCKS, dtype=np.int32)).reshape(lanes, RING_BLOCKS)
    out, start = [], 0
    head = model._model()._logits
    forward, decode_step = jitted(model)
    with jax.default_matmul_precision("highest"):
        for i, size in enumerate(pieces):
            ids = list(prompt[start:start + size])
            if i == len(pieces) - 1 and pad_last_to:
                ids += [0] * (pad_last_to - size)
            # a padded row past the table's allocated frontier goes to the null block
            row = table[lane:lane + 1].copy()
            row[0, -(-(start + size) // BS):] = 0
            hidden, cache = forward(
                params, cache, jnp.asarray([ids], jnp.int32), jnp.asarray([start], jnp.int32), None,
                context_encode=start == 0, return_hidden=True, block_tables=jnp.asarray(row),
                kv_limit=None if start == 0 else 128, window_tables=jnp.asarray(rings[lane:lane + 1]))
            out.append(np.asarray(head(params, hidden))[0, :size])
            start += size
        assert start == len(prompt)
        for j, tok in enumerate(fed):
            tokens = np.zeros((lanes,), np.int32)
            positions = np.zeros((lanes,), np.int32)
            tokens[lane], positions[lane] = tok, len(prompt) + j
            logits, _, cache = decode_step(
                params, cache, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(table),
                kv_limit=128, window_tables=jnp.asarray(rings))
            out.append(np.asarray(logits)[lane:lane + 1])
    return np.concatenate(out), cache


def test_the_family_gets_its_decode_class_and_its_two_pools():
    model = decode_model_for(TINY)
    assert isinstance(model, LagunaDecode) and model.cache_is_positional
    assert model.cache_row_dims() == (2, 2, 16)
    assert model.cache_kinds == (CacheKind("full", 2, None), CacheKind("window", 3, 8))
    pool = model.init_paged_cache(9, BS, window_blocks=5)
    assert isinstance(pool, MixedKVCache) and isinstance(pool.full, PagedKVCache)
    assert pool.full.k.shape == (2, 9, BS, 2, 16) and pool.window.k.shape == (3, 5, BS, 2, 16)
    assert cache_row_bytes(pool.full) == cache_row_bytes(pool.window) == ROW
    # without a window count (benchmarks/check.py's call) a table as wide as the context serves both
    assert model.init_paged_cache(9, BS).window.k.shape == (3, 9, BS, 2, 16)
    assert model.init_paged_cache(9, BS, jnp.bfloat16).window.v.dtype == jnp.bfloat16
    quantized = model.init_paged_cache(9, BS, kv_cache_dtype="int8", window_blocks=5)
    assert quantized.full.quantized and quantized.window.quantized and quantized.window.k.dtype == jnp.int8
    assert not model._paged_kernel_eligible(1, None)
    # every other family keeps one kind, every row of it
    assert decode_model_for(LLAMA_CONFIGS["tiny"]).cache_kinds == (CacheKind("rows", 4, None),)
    with pytest.raises(NotImplementedError, match="tree verification"):
        model.forward({}, pool, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                      tree=(jnp.zeros((2,), jnp.int32), jnp.ones((2, 2), bool)))


def test_chunked_prefill_then_decode_match_the_reference_with_the_ring_wrapped(fam, params):
    """83 prompt tokens in chunks of 16 (the last 3), then 15 decode steps: 98
    positions through a ring of 24 rows — four times round."""
    rng = np.random.default_rng(5)
    prompt, fed = rng.integers(1, 256, 83).tolist(), rng.integers(1, 256, 15).tolist()
    assert (len(prompt) + len(fed)) // RING >= 3
    got, _ = ringed_logits(decode_model_for(TINY), params, prompt, fed, [16] * 5 + [3])
    np.testing.assert_allclose(got, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)


def test_a_bucket_padded_last_chunk_equals_an_unpadded_one(fam, params):
    """The padding rows of a chunk land in ring slots whose old rows are out
    of every live query's window: ``window - 1 + the top rung`` rows."""
    rng = np.random.default_rng(6)
    prompt, fed = rng.integers(1, 256, 67).tolist(), rng.integers(1, 256, 30).tolist()
    model = decode_model_for(TINY)
    pieces = [16, 16, 16, 16, 3]
    plain, _ = ringed_logits(model, params, prompt, fed, pieces)
    padded, cache = ringed_logits(model, params, prompt, fed, pieces, pad_last_to=16)
    np.testing.assert_allclose(padded, plain, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(padded, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)
    # the idle lanes of the decode batch wrote into the null block alone
    k = np.asarray(cache.window.k)
    assert np.abs(k[:, 1:1 + RING_BLOCKS]).max() == 0 and np.abs(k[:, 1 + 2 * RING_BLOCKS:]).max() == 0
    assert np.abs(k[:, 1 + RING_BLOCKS:1 + 2 * RING_BLOCKS]).min() > 0


def test_the_one_table_call_equals_the_two_table_call_below_the_ring(fam, params):
    """``benchmarks/check.py`` hands the model one table; a table as wide as
    the context never wraps, so a window layer reads it as it reads a ring."""
    srv = serving(params)
    rng = np.random.default_rng(8)
    prompt, fed = rng.integers(1, 256, 19).tolist(), rng.integers(1, 256, 4).tolist()
    assert len(prompt) + len(fed) < RING
    one = check.paged_logits(srv, srv.engine.params, srv.model.init_paged_cache(1 + 8, BS), prompt, fed, SIZES)
    two, _ = ringed_logits(srv.model, params, prompt, fed, [16, 3])
    np.testing.assert_allclose(one, two, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(one, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)


def test_the_benchmarks_check_passes_on_the_built_engine(fam, params):
    srv = serving(params)
    got = check.serving_engine(
        srv, fam, TINY, {"prompt_tokens": 40, "decode_steps": 4, "tolerance": TOL,
                         "cache_tolerance": TOL, "clear_margin": 0.001}, SIZES, seed=3)
    assert got["ok"] and got["engine_tokens"]["near_reference_max"] == 1.0, got
    assert got["all_rows"]["max"] < TOL and got["cache"]["plain_pool_is_own"]
    clean(srv)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("loop", LOOPS)
def test_mixed_lengths_give_the_references_tokens(fam, params, loop, mode, monkeypatch):
    """Prompts under the window, past the ring and several rings long in one
    queue, more requests than lanes (an idle lane beside live ones at the
    end), look-ahead and drained steps alike, both kinds' decode read the
    gather or the block walk — the window kind's over a ring wrapped up to
    four times."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    prompts = prompts_of(np.random.default_rng(3), (37, 5, 90, 21, 60, 16))
    srv = serving(params, new_tokens=8, policy=loop_policy(loop))
    for kind in srv.model.cache_kinds:
        assert srv.model.decode_read(kind) == ("kernel" if mode == "interpret" else "gather")
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == reference_tokens(fam, params, prompt, 8), (rid, len(prompt))
    m = srv.metrics
    assert m.prefill_chunks > 0 and (m.decode_steps_async > 0) == (loop == "lookahead")
    clean(srv)


@pytest.mark.parametrize("mode", MODES)
def test_a_lane_reused_after_a_longer_request_gives_the_tokens_of_a_fresh_engine(fam, params, mode, monkeypatch):
    """One lane: the second request reads a ring the first left full, never
    reset — and, in the full layers, blocks past its frontier that the first
    left full: where the block walk reads one of them, it shows here."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    long, short = prompts_of(np.random.default_rng(13), (100, 30))
    srv = serving(params, new_tokens=10, max_batch=1)
    first = srv.submit(long)
    srv.run_to_completion()
    ring_before = np.asarray(srv.cache.window.k).copy()
    second = srv.submit(short)
    out = srv.run_to_completion()
    fresh = serving(params, new_tokens=10, max_batch=1)
    rid = fresh.submit(short)
    assert out[second] == fresh.run_to_completion()[rid] == reference_tokens(fam, params, short, 10)
    assert np.abs(ring_before[:, 1:]).min() > 0 and first != second
    clean(srv)


def test_a_common_start_is_not_shared_and_both_match_the_reference(fam, params):
    """The partial-match trap: a radix hit at token 40 would hand the window
    layers a ring without rows 33..39. Prefix caching is on by default and
    the engine turns it off for a stack with a ring."""
    rng = np.random.default_rng(7)
    common = rng.integers(1, 256, 40).tolist()
    a, b = common + rng.integers(1, 256, 9).tolist(), common + rng.integers(1, 256, 17).tolist()
    srv = serving(params, new_tokens=6)
    assert srv.paged.enable_prefix_caching and not srv._share_prefixes
    ra = srv.submit(a)
    out_a = srv.run_to_completion()[ra]
    rb = srv.submit(b)
    out_b = srv.run_to_completion()[rb]
    assert out_a == reference_tokens(fam, params, a, 6) and out_b == reference_tokens(fam, params, b, 6)
    assert srv.request_info(rb)["cached_tokens"] == 0
    snap = srv.metrics.snapshot()
    assert snap["prefix_hit_rate"] == 0 and srv.allocator.cow_copies == 0
    clean(srv)


def test_preempt_and_resume_reproduce_the_tokens(fam, params):
    rng = np.random.default_rng(21)
    pa, pb = prompts_of(rng, (8, 30))
    srv = PagedServingEngine(
        engine(params), GenerationConfig(max_new_tokens=8),
        PagedConfig(block_size=BS, num_blocks=12, decode_reserve_blocks=1, prefill_chunk_tokens=4))
    preempted, orig = [], srv._preempt
    srv._preempt = lambda req: (preempted.append(req.rid), orig(req))[1]
    ra, rb = srv.submit(pa), srv.submit(pb)
    out = srv.run_to_completion()
    assert preempted, "the pool of 12 blocks holds both requests: nothing was preempted"
    assert out[ra] == reference_tokens(fam, params, pa, 8) and out[rb] == reference_tokens(fam, params, pb, 8)
    assert srv.allocator.active_blocks == 0
    clean(srv)


@pytest.mark.parametrize("knobs,word", [
    ({"spec_draft_tokens": 2}, "spec_draft_tokens > 0"),
    ({"fused_step": True}, "fused_step"),
    ({"spill_enabled": True, "host_tier_bytes": 1 << 20}, "spill_enabled"),
])
def test_what_a_ring_cannot_serve_is_refused_at_construction(params, knobs, word):
    with pytest.raises(ValueError, match=f"{word} is not available for LagunaDecode.*ring of rows a lane"):
        serving(params, **knobs)


def test_an_int8_pool_runs_both_kinds(fam, params):
    srv = serving(params, new_tokens=6, kv_cache_dtype="int8")
    assert srv.cache.full.quantized and srv.cache.window.quantized
    assert srv.cache.window.k_scale.shape[:3] == (3, 1 + LANES * RING_BLOCKS, BS)
    prompts = prompts_of(np.random.default_rng(4), (50, 12))
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    assert all(len(out[r]) == 6 for r in rids)
    # 8 bits of cache move the logits and few of the tokens
    want = [reference_tokens(fam, params, p, 6) for p in prompts]
    agree = np.mean([a == b for r, w in zip(rids, want) for a, b in zip(out[r], w)])
    assert agree >= 0.5
    clean(srv)


def read_by_slices(pool, scale, at, limit, dtype):
    """The read ``_attend`` had before the flat view: ``(bs, NKV, D)`` slices
    of the run of blocks, payload and scales alike."""
    from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import kv_dequantize

    def read(a):
        got = a.reshape((-1,) + a.shape[2:])[at]
        return got.reshape((got.shape[0], -1) + got.shape[3:])[:, :limit]

    return read(pool).astype(dtype) if scale is None else kv_dequantize(read(pool), read(scale), dtype)


@pytest.mark.parametrize("shape", ["ring_wrapped_twice", "limit_cuts_a_block"])
@pytest.mark.parametrize("kv", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("nkv", [4, 8])
def test_the_block_gathers_flat_view_reads_what_the_slices_read(nkv, kv, shape):
    """``_attend`` gathers a payload block as its ``bs * NKV`` rows of ``D``
    (scales through the block view): bit for bit the ``(bs, NKV, D)``-slice
    read it replaced, under and at a tile's 8 kv heads, a bf16 and an int8
    pool — one row a lane through a ring wrapped more than twice with an idle
    lane on the null block (a window layer's ``pdecode``), and a block of rows
    whose ``limit`` ends inside a block (a full layer's ``psfx``)."""
    from neuronx_distributed_llama3_2_tpu.models.laguna import masked_attention, visible

    ring = shape == "ring_wrapped_twice"
    layers, nb, bs, d, lanes = 3, 23, 4, 16, 3
    window, width, t, limit = (8, 4, 1, None) if ring else (None, 7, 5, 22)
    model = LagunaDecode(dataclasses.replace(
        TINY, num_kv_heads=nkv, num_heads=2 * nkv, num_heads_per_layer=(2 * nkv,) * TINY.num_layers,
        head_dim=d, dtype=jnp.bfloat16))
    rng = np.random.default_rng(nkv + 10 * ring)
    pool = model.init_paged_cache(nb, bs, kv_cache_dtype=kv).window
    fill = lambda a: jnp.asarray(  # noqa: E731
        rng.integers(-100, 100, a.shape) if a.dtype == jnp.int8 else rng.normal(size=a.shape), a.dtype)
    pool = jax.tree.map(fill, pool)
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[: lanes * width].reshape(lanes, width), jnp.int32)
    # positions: the ring (16 rows) wrapped more than twice, or a block that ends on row 21
    first = jnp.asarray([37, 41, 0] if ring else [17, 3, 9], jnp.int32)
    pos = first[:, None] + jnp.arange(t, dtype=jnp.int32)
    null = jnp.asarray([[False], [False], [True]]) if ring else None
    if ring:
        table = table.at[2].set(0)                  # an idle lane: its table is the null block's
    q, k, v = (jnp.asarray(rng.normal(size=(lanes, t, n, d)), jnp.bfloat16) for n in (2 * nkv, nkv, nkv))
    kc, vc = ((pool.k, pool.k_scale), (pool.v, pool.v_scale)) if kv else (pool.k, pool.v)
    layer = jnp.int32(1)
    att, kc, vc = jax.jit(lambda *a: model._attend(
        *a, layer, pos, None, window=window, context_encode=False, table=table, limit=limit,
        null_rows=null))(q, k, v, kc, vc)
    rows = width * bs if limit is None else limit
    at = layer * nb + table[:, : -(-rows // bs)]
    (kp, ks), (vp, vs) = (kc, vc) if kv else ((kc, None), (vc, None))
    k_back, v_back = (read_by_slices(a, sc, at, rows, q.dtype) for a, sc in ((kp, ks), (vp, vs)))
    assert k_back.shape == (lanes, rows, nkv, d)
    r = jnp.arange(rows, dtype=jnp.int32)
    k_pos = pos[..., None] - (pos[..., None] - r) % (width * bs)
    want = masked_attention(q, k_back, v_back, visible(pos, k_pos, window))
    np.testing.assert_array_equal(np.asarray(att, np.float32), np.asarray(want, np.float32))
    assert np.isfinite(np.asarray(att, np.float32)).all()


def test_tp2_on_two_virtual_devices_matches_tp1(fam, params):
    """tp = 1 gives the reference's tokens (the tests above): so must tp = 2."""
    prompts = prompts_of(np.random.default_rng(9), (45, 9, 70))
    initialize_model_parallel(tensor_model_parallel_size=2, devices=jax.devices()[:2])
    two = serving(params, new_tokens=6)
    assert two.metrics.tp_size == 2
    shard = two.cache.window.k.addressable_shards[0].data.shape
    assert shard == (3, 1 + LANES * RING_BLOCKS, BS, 1, 16)          # a kv head a rank
    rids2 = [two.submit(p) for p in prompts]
    got = two.run_to_completion()
    assert [got[r] for r in rids2] == [reference_tokens(fam, params, p, 6) for p in prompts]
    clean(two)


def test_the_window_pool_is_lanes_times_ring_and_the_accounts_say_so(params):
    srv = serving(params)
    assert srv._lane_blocks == RING_BLOCKS and srv.table_width == -(-128 // BS) + CHUNK // BS
    window_blocks = 1 + LANES * RING_BLOCKS
    assert srv.cache.window.k.shape == (3, window_blocks, BS, 2, 16)
    window_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(srv.cache.window))
    assert window_bytes == window_blocks * BS * ROW * 3
    full_bytes = 140 * BS * ROW * 2
    assert srv.metrics.pool_bytes_total == srv.metrics.pool_bytes_per_rank == full_bytes + window_bytes
    snap = srv.metrics.snapshot()
    assert snap["window_pool_blocks"] == window_blocks and snap["num_blocks"] == 140
    # lane l's ring is blocks 1 + l * ring ..: laid out once, block 0 the null block
    np.testing.assert_array_equal(srv._lane_tables[2], 1 + 2 * RING_BLOCKS + np.arange(RING_BLOCKS))
    dims = EngineDims.from_engine(srv)
    assert dims.block_bytes == BS * ROW * 2 and dims.kv_row_bytes() == ROW * 2
    assert dims.ring_bytes == RING * ROW * 3 and dims.pool_bytes_local() == full_bytes
    # a request's cache: its context's rows in whole blocks in the full layers, one ring in the others
    assert dims.request_cache_bytes(30) == 32 * ROW * 2 + RING * ROW * 3
    assert dims.request_cache_bytes(3) == 4 * ROW * 2 + RING * ROW * 3
    profile = analytic_profile(("pdecode", LANES, 128), dims)
    assert profile.argument_bytes == dims.param_bytes + full_bytes + LANES * dims.ring_bytes
    moved = profile.bytes_accessed - dims.param_bytes - LANES * TINY.vocab_size * 4
    assert moved == LANES * 128 * ROW * 2 + LANES * RING * ROW * 3


def dispatches(srv):
    return [args for step in srv.tracer.timeline()["steps"] for ph, name, _, _, args in step["events"]
            if ph == "X" and name == "dispatch"]


@pytest.mark.parametrize("mode", MODES)
def test_a_decode_record_says_the_window_rows_the_program_passed(params, mode, monkeypatch):
    """``window_rows_passed`` beside ``window_rows``: every lane's whole ring
    where the program gathers; where it walks, the blocks from the window's
    first row to the lane's own — at most two blocks a live lane over what it
    needs — and one block a lane that is not live."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    srv = serving(params, new_tokens=5, trace_enabled=True)
    for p in prompts_of(np.random.default_rng(2), (20, 3, 50)):
        srv.submit(p)
    srv.run_to_completion()
    records = dispatches(srv)
    assert records and all("window_rows_passed" in a for a in records)
    for a in records:
        assert a["window_rows"] <= a["window_rows_passed"] <= LANES * RING
        if mode == "reference":
            assert a["window_rows_passed"] == LANES * RING
        else:
            idle = (LANES - a["lanes"]) * BS
            assert a["window_rows_passed"] - idle <= a["window_rows"] + a["lanes"] * 2 * BS
            assert (a["window_rows_passed"] - idle) % BS == 0 and a["window_rows_passed"] < LANES * RING
    clean(srv)


def test_a_traced_engine_records_the_kinds_and_the_window_rows(params, monkeypatch):
    srv = serving(params, new_tokens=5, trace_enabled=True, prewarm=True)
    prompts = prompts_of(np.random.default_rng(2), (20, 3, 50))
    for p in prompts:
        srv.submit(p)
    srv.run_to_completion()
    tl = srv.tracer.timeline()
    setup = tl["setup"]
    assert setup["cache_kinds"] == {
        "full": {"layers": 2, "rows_per_lane": None, "row_bytes": ROW, "decode_read": "gather"},
        "window": {"layers": 3, "rows_per_lane": RING, "row_bytes": ROW, "decode_read": "gather"}}
    assert setup["window_ring_rows"] == RING and setup["cache_row_bytes"] == ROW
    records = dispatches(srv)
    assert records and all("rows" in a and "window_rows" in a for a in records)
    for a in records:
        assert a["lanes"] <= a["window_rows"] <= min(a["rows"], a["lanes"] * 8)
    # a lane past the window sees 8 rows; the 3-token prompt's lane never gets there
    assert any(a["window_rows"] == a["lanes"] * 8 < a["rows"] for a in records)
    assert any(a["window_rows"] < a["lanes"] * 8 for a in records)
    assert len(tl["routed"]) > 0 and all(len(row[4]) == 8 for row in tl["routed"])
    assert srv.metrics.snapshot()["window_pool_blocks"] == 1 + LANES * RING_BLOCKS
    # where Pallas kernels run, the record of an engine built there says both kinds are walked
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    reads = {name: kind["decode_read"] for name, kind in srv._kind_facts()["cache_kinds"].items()}
    assert reads == {"full": "kernel", "window": "kernel"}


def test_the_dense_slot_cache_runs_every_layer_at_full_length(fam, params):
    """``InferenceEngine.generate``: the window is a mask alone."""
    prompt = prompts_of(np.random.default_rng(17), (40,))[0]
    eng = engine(params, max_batch=1)
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=6))
    assert list(out.sequences[0]) == reference_tokens(fam, params, prompt, 6)
