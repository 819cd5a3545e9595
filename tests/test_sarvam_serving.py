"""The latent (MLA) pool under the paged serving engine at the tiny size,
float32 on the CPU: logits of whole-prompt prefill, chunked prefill and decode
against the plain reference's full forward, and the serving behaviours on the
latent pool — prefix hit, copy-on-write of a shared partial block,
preempt-requeue in the middle of a chunked prefill — against the dense slot
engine over the same model, with the allocator and the invariants clean."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    ContinuousBatchingEngine, GenerationConfig, InferenceEngine, LatentCache, SarvamDecode,
)
from neuronx_distributed_llama3_2_tpu.inference.model import cache_row_bytes, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.models.sarvam import SARVAM_CONFIGS, SarvamForCausalLM
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine, audit_engine

# 2 of the 8 experts held, not from 0: the share runs through every program
TINY = dataclasses.replace(
    SARVAM_CONFIGS["tiny-sarvam"], max_seq_len=64, experts_held=2, first_held_expert=4)
SIZES = {"lanes": 4, "block_size": 16, "max_seq_len": 64, "pool_blocks": 32,
         "prefill_chunk_tokens": 16, "prefill_buckets": [16], "kv_buckets": [64]}
TOL = 1e-4
# the kernel mode decides a decode step's read of the latent pool: the gather and
# ``latent_attention`` ("reference", this tier's), or the block walk interpreted
MODES = ("reference", "interpret")


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("sarvam")


@pytest.fixture(scope="module")
def params():
    return jax.jit(SarvamForCausalLM(TINY).init)(jax.random.key(0))


def engine(params, **kw):
    return InferenceEngine(TINY, params, max_batch=kw.pop("max_batch", 4), max_seq_len=64,
                           buckets=[8, 16, 32, 64], **kw)


def serving(params, new_tokens=6, **paged):
    paged = {"block_size": 16, "num_blocks": 32, "prefill_chunk_tokens": 16,
             "prefill_buckets": (16,), "kv_buckets": (64,), **paged}
    return PagedServingEngine(engine(params), GenerationConfig(max_new_tokens=new_tokens), PagedConfig(**paged))


def dense_outputs(params, prompts, new_tokens):
    dense = ContinuousBatchingEngine(engine(params), GenerationConfig(max_new_tokens=new_tokens))
    for p in prompts:
        dense.submit(p)
    return dense.run_to_completion()


def prompts_of(rng, lengths):
    return [rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in lengths]


def clean(srv):
    assert srv.allocator.leak_check() == [] and audit_engine(srv) == []


def test_the_family_gets_its_decode_class_and_its_cache():
    model = decode_model_for(TINY)
    assert isinstance(model, SarvamDecode)
    pool = model.init_paged_cache(8, 16)
    assert isinstance(pool, LatentCache) and pool.kv.shape == (3, 8, 16, 128)   # 40 values in one lane
    assert model.cache_row_dims() == (1, 1, 128)
    assert cache_row_bytes(pool) == 128 * 4
    assert model.paged_dispatch_path(1) == "gather"
    assert model.forbidden_gather_shapes(4, 64) == {(4, 64, 128)}
    big = decode_model_for(SARVAM_CONFIGS["sarvam-105b"])
    assert big.config.cache_row_width == 576 and big.pool_row_width == 640


@pytest.mark.parametrize("kv", ["int8", "fp8_e4m3"])
def test_a_quantized_latent_pool_is_refused(params, kv):
    with pytest.raises(NotImplementedError, match="latent .* pool has no quantized form"):
        serving(params, kv_cache_dtype=kv)


@pytest.mark.parametrize("chunk", [0, 16], ids=["whole-prompt", "chunked"])
def test_prefill_then_decode_logits_match_the_reference(fam, params, chunk):
    """``pctx`` over the whole prompt, or ``pctx`` + ``psfx`` chunks, then
    ``pdecode`` steps through the latent pool: every logits row against the
    reference's full forward."""
    srv = serving(params)
    rng = np.random.default_rng(5)
    prompt, fed = rng.integers(1, 256, 40).tolist(), rng.integers(1, 256, 5).tolist()
    sizes = {**SIZES, "prefill_chunk_tokens": chunk, "prefill_buckets": [16, 64]}
    got = check.paged_logits(srv, srv.engine.params, srv.model.init_paged_cache(8, 16), prompt, fed, sizes)
    with jax.default_matmul_precision("highest"):
        want = fam.reference.forward_logits(params, fam.reference_config(TINY), jnp.asarray([prompt + fed]))[0]
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_the_benchmarks_check_passes_on_the_built_engine(fam, params):
    srv = serving(params)
    got = check.serving_engine(
        srv, fam, TINY, {"prompt_tokens": 40, "decode_steps": 4, "tolerance": TOL,
                         "cache_tolerance": TOL, "clear_margin": 0.001}, SIZES, seed=3)
    assert got["ok"] and got["engine_tokens"]["near_reference_max"] == 1.0, got
    assert got["all_rows"]["max"] < TOL and got["cache"]["plain_pool_is_own"]
    clean(srv)


def test_a_prefix_hit_gives_what_no_hit_gives(params):
    rng = np.random.default_rng(7)
    document = rng.integers(1, 256, 32).tolist()
    q1, q2 = document + [3, 4, 5, 6, 7], document + [9, 8, 7]
    warm = serving(params)
    warm.submit(q1)
    warm.run_to_completion()
    hit = warm.submit(q2)
    out_hit = warm.run_to_completion()[hit]
    assert warm.request_info(hit)["cached_tokens"] == 32
    cold = serving(params)
    miss = cold.submit(q2)
    assert cold.run_to_completion()[miss] == out_hit
    assert cold.request_info(miss)["cached_tokens"] == 0
    assert out_hit == dense_outputs(params, [q2], 6)[0]
    clean(warm), clean(cold)


def test_cow_of_a_shared_partial_block(params):
    rng = np.random.default_rng(11)
    base = rng.integers(1, 256, 27).tolist()
    p1, p2 = base + [1], base + [2, 3]           # diverge inside block 1 of 16 rows
    srv = serving(params, new_tokens=4, prefill_chunk_tokens=None, prefill_buckets=(16, 32))
    srv.submit(p1)
    out1 = srv.run_to_completion()
    srv.submit(p2)
    out2 = srv.run_to_completion()
    assert srv.allocator.cow_copies >= 1 and srv.request_info(1)["cached_tokens"] == 27
    assert {0: out1[0], 1: out2[1]} == dense_outputs(params, [p1, p2], 4)
    clean(srv)


def test_preempt_requeue_in_the_middle_of_a_chunked_prefill(params):
    rng = np.random.default_rng(21)
    pa, pb = prompts_of(rng, (8, 30))
    srv = PagedServingEngine(
        engine(params), GenerationConfig(max_new_tokens=8),
        PagedConfig(block_size=4, num_blocks=12, decode_reserve_blocks=1, prefill_chunk_tokens=4))
    preempted, orig = [], srv._preempt
    srv._preempt = lambda req: (preempted.append((req.rid, req.prefilling)), orig(req))[1]
    srv.submit(pa)
    rb = srv.submit(pb)
    out = srv.run_to_completion()
    assert (rb, True) in preempted, preempted
    assert out == dense_outputs(params, [pa, pb], 8)
    assert srv.allocator.active_blocks == 0
    clean(srv)


@pytest.mark.parametrize("mode", MODES)
def test_mixed_lengths_give_the_dense_slot_engines_tokens_in_either_mode(params, mode, monkeypatch):
    """More requests than lanes, prompts of 3 to 30 tokens out of step, the
    decode read the gather or the block walk: token for token the dense slot
    engine's (whose cache no table reads: the gather's form in either mode)."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    prompts = prompts_of(np.random.default_rng(3), (5, 30, 20, 9, 26, 3))
    srv = serving(params, new_tokens=8)
    (kind,) = srv.model.cache_kinds
    assert srv.model.decode_read(kind) == ("kernel" if mode == "interpret" else "gather")
    for p in prompts:
        srv.submit(p)
    assert srv.run_to_completion() == dense_outputs(params, prompts, 8)
    clean(srv)


@pytest.mark.parametrize("mode", MODES)
def test_a_lane_reused_after_a_longer_request_gives_the_dense_slot_engines_tokens(params, mode, monkeypatch):
    """One lane: the second request's table points at blocks the first left
    full past its frontier — where the walk reads one of them, it shows here."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    long, short = prompts_of(np.random.default_rng(13), (40, 11))
    srv = PagedServingEngine(
        engine(params, max_batch=1), GenerationConfig(max_new_tokens=8),
        PagedConfig(block_size=4, num_blocks=16, prefill_chunk_tokens=16, prefill_buckets=(16,),
                    kv_buckets=(64,), enable_prefix_caching=False))
    srv.submit(long)
    srv.run_to_completion()
    held = np.abs(np.asarray(srv.cache.kv)[0, 1:]).sum(-1) > 0
    assert held.sum() >= 40                 # the long request's rows are still there
    second = srv.submit(short)
    out = srv.run_to_completion()
    assert out[second] == dense_outputs(params, [short], 8)[0]
    clean(srv)


def test_mixed_traffic_matches_the_dense_slot_engine(params):
    prompts = prompts_of(np.random.default_rng(3), (5, 30, 20, 9, 26, 3))
    srv = serving(params, new_tokens=8)
    for p in prompts:
        srv.submit(p)
    assert srv.run_to_completion() == dense_outputs(params, prompts, 8)
    assert srv.metrics.prefill_chunks > 0
    clean(srv)


def test_a_traced_engine_records_the_row_and_the_held_pairs(params, monkeypatch):
    srv = serving(params, trace_enabled=True, prewarm=True)
    for p in prompts_of(np.random.default_rng(2), (20, 33)):
        srv.submit(p)
    srv.run_to_completion()
    tl = srv.tracer.timeline()
    assert tl["setup"]["cache_row_bytes"] == 128 * 4
    assert len(tl["routed_local"]) == len(tl["routed"]) > 0
    for (_, _, paths, pairs, per_expert), local in zip(tl["routed"], tl["routed_local"]):
        assert paths == ("all",) and len(per_expert) == 8 and pairs % 2 == 0
        assert local == sum(per_expert[4:6])          # experts 4 and 5 are held
    records = [args for step in tl["steps"] for ph, name, _, _, args in step["events"] if ph == "X"]
    assert any("rows" in a for a in records) and any(a.get("kv_bucket") == 64 for a in records)
    assert any(a.get("kv_bucket") == 0 for a in records if "bucket" in a)
    kinds = tl["setup"]["cache_kinds"]
    assert kinds == {"rows": {"layers": 3, "rows_per_lane": None, "row_bytes": 128 * 4, "decode_read": "gather"}}
    # where Pallas kernels run, the record of an engine built there says the pool is walked
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    assert srv._kind_facts()["cache_kinds"]["rows"]["decode_read"] == "kernel"
