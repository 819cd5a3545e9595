"""Xing4.0 under the paged serving engine at the tiny size, float32 on the
CPU: logits of whole-prompt prefill, chunked prefill and decode through the
latent pool against the plain reference's full forward (YaRN's original range
is 32 positions here, the prompts are longer), the serving behaviours — a
prefix hit on the first request's document, preempt-requeue in the middle of a
chunked prefill — against the dense slot engine over the same model, padding
rows that must not touch a live row's streams, and what is refused."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    ContinuousBatchingEngine, GenerationConfig, InferenceEngine, LatentCache, SarvamDecode,
    XingDecode,
)
from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.models.xing import XING_CONFIGS, XingForCausalLM
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine, audit_engine

TINY = dataclasses.replace(XING_CONFIGS["tiny-xing"], max_seq_len=64)
SIZES = {"lanes": 4, "block_size": 16, "max_seq_len": 64, "pool_blocks": 32,
         "prefill_chunk_tokens": 16, "prefill_buckets": [16], "kv_buckets": [64]}
TOL = 1e-4
# the kernel mode decides a decode step's read of the latent pool: the gather and
# ``latent_attention`` ("reference", this tier's), or the block walk interpreted
MODES = ("reference", "interpret")


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("xing")


@pytest.fixture(scope="module")
def params():
    return jax.jit(XingForCausalLM(TINY).init)(jax.random.key(0))


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


def clean(srv):
    assert srv.allocator.leak_check() == [] and audit_engine(srv) == []


def test_the_family_gets_its_decode_class_and_sarvams_cache():
    model = decode_model_for(TINY)
    assert isinstance(model, XingDecode) and isinstance(model, SarvamDecode)
    pool = model.init_paged_cache(8, 16)
    assert isinstance(pool, LatentCache) and pool.kv.shape == (3, 8, 16, 128)
    assert model.paged_dispatch_path(1) == "gather"
    big = decode_model_for(XING_CONFIGS["xing4.0-29b-a4b"])
    assert big.config.cache_row_width == 576 and big.pool_row_width == 640
    # sarvam's config still gets sarvam's class
    from neuronx_distributed_llama3_2_tpu.models.sarvam import SARVAM_CONFIGS
    assert type(decode_model_for(SARVAM_CONFIGS["tiny-sarvam"])) is SarvamDecode


@pytest.mark.parametrize("chunk", [0, 16], ids=["whole-prompt", "chunked"])
def test_prefill_then_decode_logits_match_the_reference(fam, params, chunk):
    """``pctx`` over the whole prompt, or ``pctx`` + ``psfx`` chunks (the last
    one 8 live rows in a bucket of 16), then ``pdecode`` steps through the
    latent pool: every logits row against the reference's full forward, on
    both sides of YaRN's original 32 positions."""
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


def test_a_second_request_hits_the_first_ones_document(params):
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


def test_preempt_requeue_in_the_middle_of_a_chunked_prefill(params):
    rng = np.random.default_rng(21)
    pa, pb = (rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in (8, 30))
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
    prompts = [np.random.default_rng(3).integers(1, TINY.vocab_size, size=(n,)).tolist() for n in (5, 30, 20, 9, 26, 3)]
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
    long, short = [np.random.default_rng(13).integers(1, TINY.vocab_size, size=(n,)).tolist() for n in (40, 11)]
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
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in (5, 30, 20, 9, 26, 3)]
    srv = serving(params, new_tokens=8)
    for p in prompts:
        srv.submit(p)
    assert srv.run_to_completion() == dense_outputs(params, prompts, 8)
    assert srv.metrics.prefill_chunks > 0
    clean(srv)


def test_a_buckets_padding_rows_leave_the_live_rows_streams_untouched(params):
    """A last chunk of 5 live rows in a bucket of 16: whatever tokens fill the
    11 padding rows, the live rows' logits are the same numbers — the
    residual's coefficients are a token's own."""
    model = decode_model_for(TINY)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    rng = np.random.default_rng(1)
    head = rng.integers(1, 256, 16).tolist()
    live = rng.integers(1, 256, 5).tolist()

    @jax.jit
    def run(first, second):
        pool = model.init_paged_cache(8, 16)
        _, pool = model.forward(params, pool, first, jnp.zeros((1,), jnp.int32), None,
                                context_encode=True, block_tables=table)
        logits, _ = model.forward(params, pool, second, jnp.full((1,), 16, jnp.int32), None,
                                  block_tables=table, kv_limit=64)
        return logits[0, :5]

    first = jnp.asarray([head], jnp.int32)
    zeros = run(first, jnp.asarray([live + [0] * 11], jnp.int32))
    noise = run(first, jnp.asarray([live + rng.integers(1, 256, 11).tolist()], jnp.int32))
    np.testing.assert_array_equal(np.asarray(zeros), np.asarray(noise))
    assert float(jnp.abs(zeros).max()) > 0


def test_a_tree_block_is_refused_with_a_message(params):
    model = decode_model_for(TINY)
    tree = (jnp.arange(4, dtype=jnp.int32), jnp.tril(jnp.ones((4, 4), bool)))
    with pytest.raises(NotImplementedError, match="tree verification over a latent cache"):
        model.forward(params, model.init_cache(1, 64), jnp.ones((1, 4), jnp.int32),
                      jnp.zeros((1,), jnp.int32), tree=tree, kv_limit=64)


def test_tensor_parallelism_is_refused_at_construction():
    parallel_state.initialize_model_parallel(tensor_model_parallel_size=2)
    with pytest.raises(NotImplementedError, match="XingDecode under tp > 1"):
        decode_model_for(TINY)
    parallel_state.destroy_model_parallel()
    assert isinstance(decode_model_for(TINY), XingDecode)


def test_a_traced_engine_records_the_residual_row_and_the_decode_rows(params, monkeypatch):
    srv = serving(params, trace_enabled=True, prewarm=True)
    rng = np.random.default_rng(2)
    for n in (20, 33):
        srv.submit(rng.integers(1, TINY.vocab_size, size=(n,)).tolist())
    srv.run_to_completion()
    tl = srv.tracer.timeline()
    assert tl["setup"]["residual_row_bytes"] == 4 * 64 * 4 == TINY.residual_row_bytes
    assert tl["setup"]["cache_row_bytes"] == 128 * 4
    records = [args for step in tl["steps"] for ph, name, _, _, args in step["events"] if ph == "X"]
    assert any("rows" in a for a in records) and any(a.get("kv_bucket") == 64 for a in records)
    kinds = tl["setup"]["cache_kinds"]
    assert kinds == {"rows": {"layers": 3, "rows_per_lane": None, "row_bytes": 128 * 4, "decode_read": "gather"}}
    # where Pallas kernels run, the record of an engine built there says the pool is walked
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    assert srv._kind_facts()["cache_kinds"]["rows"]["decode_read"] == "kernel"


def test_the_programs_carry_the_residuals_scopes_outside_attn_and_moe(params):
    """``mhc/coeff``, ``mhc/sinkhorn`` and ``mhc/mix`` in every paged program,
    never under ``attn`` or ``moe`` (docqa's readers take ``attn``'s seconds
    as they were), and ``attn/q_latent`` for the query's down-projection."""
    import re

    srv = serving(params)
    rng = np.random.default_rng(4)
    srv.submit(rng.integers(1, 256, 40).tolist())
    srv.run_to_completion()
    by_kind = {}
    for rec in srv.program_registry().values():
        by_kind.setdefault(rec.kind, rec)
    assert {"pctx", "psfx", "pdecode"} <= set(by_kind)
    for kind in ("pctx", "psfx", "pdecode"):
        names = set(re.findall(r'op_name="([^"]+)"', by_kind[kind].lower().compile().as_text()))
        parts = [name.split("/") for name in names]
        for scope in ("coeff", "sinkhorn", "mix"):
            under = [p for p in parts if "mhc" in p and scope in p[p.index("mhc"):]]
            assert under, (kind, scope)
            assert not any("attn" in p or "moe" in p or "mlp" in p for p in under), (kind, scope)
        assert any("attn" in p and "q_latent" in p for p in parts), kind
