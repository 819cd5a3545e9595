"""A model whose cache is a state (power retention) under the paged serving
engine at the tiny size, float32 on the CPU: one block of the pool is one whole
sequence's state. Logits and tokens against the plain reference's full forward
(which has no state at all), and what a state forces on the engine — the live
length of a padded chunk, the zero state of a reused block, no prefix sharing,
the refusals — with weights whose gates sit near 1, so that a state that kept
or lost something it should not have shows many tokens later."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig, InferenceEngine, RetentionDecode, StateCache,
)
from neuronx_distributed_llama3_2_tpu.inference.medusa import MedusaDecoder
from neuronx_distributed_llama3_2_tpu.inference.model import cache_block_bytes, decode_model_for
from neuronx_distributed_llama3_2_tpu.inference.speculative import SpeculativeDecoder
from neuronx_distributed_llama3_2_tpu.models.brumby import BRUMBY_CONFIGS, BrumbyForCausalLM
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine, audit_engine
from tests.drained_policy import LOOPS, loop_policy

TINY = dataclasses.replace(BRUMBY_CONFIGS["tiny-brumby"], max_seq_len=96)
SIZES = {"lanes": 4, "block_size": 96, "max_seq_len": 96, "pool_blocks": 7,
         "prefill_chunk_tokens": 16, "prefill_buckets": [8, 16], "kv_buckets": [96]}
TOL = 1e-4
STATE_BYTES = 2 * (2 * 768 * 33 * 4)        # layers x kv heads x phi x (head + 1) x float32


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("brumby")


@pytest.fixture(scope="module")
def params():
    """Seeded weights with a long memory: one embedding channel is the same in
    every token and every gate reads it, so the gates sit near 0.95 (seeded
    gates sit around 0.5 and forget in a few tokens)."""
    params = jax.jit(BrumbyForCausalLM(TINY).init)(jax.random.key(0))
    table = params["embed"]["embedding"]
    params["embed"] = {"embedding": table.at[:, 0].set(3.0 * float(table.std()))}
    gate = params["layers"]["attn"]["gate"]["kernel"]
    params["layers"]["attn"]["gate"] = {"kernel": gate.at[:, 0, :].set(1.2)}
    return params


def engine(params, **kw):
    return InferenceEngine(TINY, params, max_batch=kw.pop("max_batch", 4), max_seq_len=96,
                           buckets=[8, 16, 32, 96], **kw)


def serving(params, new_tokens=6, policy=None, **paged):
    paged = {"block_size": 96, "num_blocks": 7, "prefill_chunk_tokens": 16,
             "prefill_buckets": (8, 16), "kv_buckets": (96,), **paged}
    return PagedServingEngine(engine(params), GenerationConfig(max_new_tokens=new_tokens),
                              PagedConfig(**paged), policy=policy)


def reference_tokens(fam, params, prompt, new_tokens):
    """Greedy continuation by the reference's full forward, one token at a time."""
    cfg, seq = fam.reference_config(TINY), list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(new_tokens):
            logits = fam.reference.forward_logits(params, cfg, jnp.asarray([seq]))
            seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def prompts_of(rng, lengths):
    return [rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in lengths]


def clean(srv):
    assert srv.allocator.leak_check() == [] and audit_engine(srv) == []


def test_the_family_gets_its_decode_class_and_its_cache():
    model = decode_model_for(TINY)
    assert isinstance(model, RetentionDecode) and not model.cache_is_positional
    pool = model.init_paged_cache(5, 96)
    assert isinstance(pool, StateCache)
    # five states; a block's size in tokens is not a dimension of them
    assert pool.s.shape == (2, 5, 2, 768, 32) and pool.z.shape == (2, 5, 2, 768)
    assert pool.s.dtype == pool.z.dtype == jnp.float32
    assert jax.tree.map(jnp.shape, model.init_paged_cache(5, 16)) == jax.tree.map(jnp.shape, pool)
    assert cache_block_bytes(pool) == STATE_BYTES == 2 * TINY.state_bytes_per_layer()
    assert model.init_paged_cache(5, 96, jnp.bfloat16).s.dtype == jnp.bfloat16     # an explicit dtype is honoured
    assert model.paged_dispatch_path(1) == "gather"
    with pytest.raises(NotImplementedError, match="tree verification"):
        model.forward({}, pool, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                      tree=(jnp.zeros((2,), jnp.int32), jnp.ones((2, 2), bool)))


@pytest.mark.parametrize("kv", ["int8", "fp8_e4m3"])
def test_a_quantized_state_pool_is_refused(params, kv):
    with pytest.raises(NotImplementedError, match="retention state has no quantized form"):
        serving(params, kv_cache_dtype=kv)


@pytest.mark.parametrize("knobs,word", [
    ({"spec_draft_tokens": 2}, "spec_draft_tokens > 0"),
    ({"fused_step": True}, "fused_step"),
    ({"spill_enabled": True, "host_tier_bytes": 1 << 20}, "spill_enabled"),
])
def test_what_a_state_cannot_undo_is_refused_at_construction(params, knobs, word):
    with pytest.raises(ValueError, match=f"{word} is not available for RetentionDecode.*state per sequence"):
        serving(params, **knobs)


@pytest.mark.parametrize("how", ["verify_program", "speculative_decoder", "medusa"])
def test_the_dense_path_refuses_draft_and_verify_too(params, how):
    """A block of drafts through ``forward`` on a slot's state would leave the
    rejected ones in it: the same trap, closed at the dense engine's door."""
    eng = engine(params, max_batch=1)
    with pytest.raises(ValueError, match="not available for RetentionDecode.*state per sequence.*rejected draft"):
        if how == "verify_program":
            eng._verify_program(1, 3)
        elif how == "speculative_decoder":
            SpeculativeDecoder(eng, eng, gamma=2).generate([1, 2, 3], 4)
        else:
            MedusaDecoder(eng, {}, num_heads=3)


@pytest.mark.parametrize("chunk", [0, 16], ids=["whole-prompt", "chunked"])
def test_prefill_then_decode_logits_match_the_reference(fam, params, chunk):
    """``pctx`` over the whole prompt, or ``pctx`` + ``psfx`` chunks, then
    ``pdecode`` steps over the state: every logits row against the reference's
    full forward. The check passes no length, so every piece is a whole rung."""
    srv = serving(params)
    rng = np.random.default_rng(5)
    prompt, fed = rng.integers(1, 256, 48).tolist(), rng.integers(1, 256, 6).tolist()
    sizes = {**SIZES, "prefill_chunk_tokens": chunk, "prefill_buckets": [16, 48]}
    got = check.paged_logits(srv, srv.engine.params, srv.model.init_paged_cache(2, 96), prompt, fed, sizes)
    with jax.default_matmul_precision("highest"):
        want = fam.reference.forward_logits(params, fam.reference_config(TINY), jnp.asarray([prompt + fed]))[0]
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_the_benchmarks_check_passes_and_a_bf16_state_fails_it(fam, params):
    spec_ = {"prompt_tokens": 48, "decode_steps": 4, "tolerance": TOL, "cache_tolerance": TOL}
    srv = serving(params)
    got = check.serving_engine(srv, fam, TINY, spec_, SIZES, seed=3)
    assert got["ok"] and got["engine_tokens"]["near_reference_max"] == 1.0, got
    assert got["all_rows"]["max"] < TOL and got["cache"]["plain_pool_is_own"]
    clean(srv)
    low = serving(params, cache_dtype=jnp.bfloat16)
    assert low.cache.s.dtype == jnp.bfloat16
    got = check.serving_engine(low, fam, TINY, spec_, SIZES, seed=3)
    assert not got["ok"] and not got["cache"]["plain_pool_is_own"] and got["cache"]["p50"] > TOL, got


def test_a_bucket_padded_last_chunk_equals_an_unpadded_one(params):
    """21 rows in a bucket of 32 with the live length, against 21 rows in a
    bucket of 21: the same logits and the same state bit for bit — and without
    the length the padding rows change the state."""
    model = decode_model_for(TINY)
    ids = np.random.default_rng(9).integers(1, 256, 32)
    table = jnp.asarray([[1, 0]], jnp.int32)
    start = jnp.zeros((1,), jnp.int32)

    def run(n, live):
        return model.forward(params, model.init_paged_cache(2, 96), jnp.asarray(ids[None, :n]), start,
                             context_encode=True, block_tables=table, row_live=live)

    exact, cache_exact = run(21, None)
    padded, cache_padded = run(32, jnp.asarray([21], jnp.int32))
    np.testing.assert_allclose(padded[:, :21], exact, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(cache_padded.s, cache_exact.s, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(cache_padded.z, cache_exact.z, rtol=1e-6, atol=1e-7)
    _, blind = run(32, None)
    assert float(jnp.abs(blind.s - cache_exact.s).max()) > 1e-3
    assert not np.any(np.asarray(cache_padded.s[:, 0]))                   # the null state was not touched


@pytest.mark.parametrize("loop", LOOPS)
def test_chunked_padded_prompts_give_the_references_tokens(fam, params, loop):
    """Prompts whose last chunk fills none of the buckets (37 = 16 + 16 + 5 in
    a bucket of 8; 21; 5; 50), continuous batching, look-ahead and drained
    steps alike."""
    prompts = prompts_of(np.random.default_rng(3), (37, 21, 5, 50, 16, 33))
    srv = serving(params, new_tokens=8, policy=loop_policy(loop))
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == reference_tokens(fam, params, prompt, 8), (rid, len(prompt))
    m = srv.metrics
    assert m.prefill_chunks > 0 and m.state_resets == len(prompts)
    assert (m.decode_steps_async > 0) == (loop == "lookahead")
    clean(srv)


def test_a_reused_block_gives_the_tokens_of_a_fresh_engine(fam, params):
    """Two lanes' worth of pool: the third and fourth requests take blocks
    that still hold the first two's past."""
    first, second = prompts_of(np.random.default_rng(13), (30, 44)), prompts_of(np.random.default_rng(14), (40, 9))
    srv = serving(params, new_tokens=8, num_blocks=4, decode_reserve_blocks=1)
    for p in first:
        srv.submit(p)
    srv.run_to_completion()
    assert float(jnp.abs(srv.cache.s[:, 1:]).max()) > 0                   # the freed blocks are not zero
    later = [srv.submit(p) for p in second]
    out = srv.run_to_completion()
    for rid, prompt in zip(later, second):
        assert out[rid] == reference_tokens(fam, params, prompt, 8)
    clean(srv)


def test_a_decode_step_touches_only_the_states_its_tables_name(params):
    """Lane 0 decodes on block 2; lane 1 is mid-prefill and lane 2 idle, both
    on the null table: blocks 1 and 3 (a lane's mid-prefill state, a parked
    one) come back bit for bit, block 2 moves."""
    model = decode_model_for(TINY)
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(a.ndim), a.shape, a.dtype) * 0.1,
        model.init_paged_cache(4, 96))
    tables = jnp.asarray([[2, 0], [0, 0], [0, 0]], jnp.int32)
    tokens, positions = jnp.asarray([5, 0, 0], jnp.int32), jnp.asarray([17, 0, 0], jnp.int32)
    logits, new_positions, after = model.decode_step(params, pool, tokens, positions, tables, kv_limit=96)
    assert logits.shape == (3, 256) and bool(jnp.isfinite(logits).all())
    assert new_positions.tolist() == [18, 1, 1]
    for leaf, leaf_after in zip(pool, after):
        for block in (1, 3):
            assert bool((leaf_after[:, block] == leaf[:, block]).all())
        assert float(jnp.abs(leaf_after[:, 2] - leaf[:, 2]).max()) > 0


def test_preempt_and_resume_reproduce_the_tokens(fam, params):
    """A request taken off its lane in the middle of decoding (its one block
    freed) re-prefills prompt + output from the zero state; one taken in the
    middle of a chunked prefill starts over."""
    long, short = prompts_of(np.random.default_rng(21), (45, 12))
    srv = serving(params, new_tokens=10)
    r_long, r_short = srv.submit(long), srv.submit(short)
    for _ in range(2):
        srv.step()
    victim = srv._requests[r_long]
    assert victim.prefilling
    srv._drain_pending() if srv._pending is not None else None
    srv._preempt(victim)
    while len(srv._requests[r_short].out) < 4:
        srv.step()
    if srv._pending is not None:
        srv._drain_pending()
    srv._preempt(srv._requests[r_short])
    out = srv.run_to_completion()
    assert srv.metrics.preemptions == 2 and srv.metrics.state_resets == 4
    assert out[r_long] == reference_tokens(fam, params, long, 10)
    assert out[r_short] == reference_tokens(fam, params, short, 10)
    clean(srv)


def test_a_common_start_shares_nothing(fam, params):
    """The partial-match trap: two prompts with the same first 40 tokens. A KV
    engine would match them token by token and copy the partly shared block; a
    state after N tokens says nothing about its first 40."""
    rng = np.random.default_rng(31)
    start = rng.integers(1, 256, 40).tolist()
    p1, p2 = start + rng.integers(1, 256, 9).tolist(), start + rng.integers(1, 256, 23).tolist()
    srv = serving(params, new_tokens=8)
    assert srv.paged.enable_prefix_caching          # the default stays on; the engine asks the model
    r1 = srv.submit(p1)
    srv.run_to_completion()
    r2 = srv.submit(p2)
    r3 = srv.submit(p1)                              # a whole repeat shares nothing either
    out = srv.run_to_completion()
    for rid, prompt in ((r2, p2), (r3, p1)):
        assert out[rid] == reference_tokens(fam, params, prompt, 8)
        assert srv.request_info(rid)["cached_tokens"] == 0
    snap = srv.metrics.snapshot()
    assert snap["cached_tokens"] == 0 and srv.allocator.cow_copies == 0 and srv.index.hit_tokens == 0
    assert srv.index.match(p1) == (0, [])
    clean(srv)


def test_the_dense_engine_builds_its_cache_for_generate_only(fam, params):
    eng = engine(params, max_batch=2)
    assert eng._cache is None
    srv = PagedServingEngine(eng, GenerationConfig(max_new_tokens=2), PagedConfig(
        block_size=96, num_blocks=4, prefill_buckets=(16,), kv_buckets=(96,)))
    srv.submit([3, 4, 5])
    srv.run_to_completion()
    assert eng._cache is None                       # the paged engine never reads it
    prompt = prompts_of(np.random.default_rng(41), (21,))[0]     # 21 rows in a bucket of 32: the length reaches the model
    got = eng.generate([prompt], GenerationConfig(max_new_tokens=6))
    assert isinstance(eng._cache, StateCache) and eng.cache.s.shape[1] == 2
    assert got.sequences[0] == reference_tokens(fam, params, prompt, 6)


def test_a_traced_engine_records_the_state_and_the_live_lanes(params):
    srv = serving(params, trace_enabled=True, prewarm=True)
    for p in prompts_of(np.random.default_rng(2), (20, 33)):
        srv.submit(p)
    srv.run_to_completion()
    tl = srv.tracer.timeline()
    assert tl["setup"]["state_bytes_per_lane"] == STATE_BYTES and "cache_row_bytes" not in tl["setup"]
    assert tl["setup"]["program_temp_bytes_max"] > 0
    records = [args for step in tl["steps"] for ph, name, _, _, args in step["events"]
               if ph == "X" and name == "dispatch"]
    assert records and all(a["rows"] == a["lanes"] for a in records)      # the states a step has to move
    assert srv.metrics.snapshot()["state_resets"] == 2
    assert srv.metrics.pool_bytes_total == srv.metrics.pool_bytes_per_rank == 7 * STATE_BYTES
