"""Paged serving engine e2e: greedy equivalence vs the dense slot scheduler,
prefix-cache admission, copy-on-write, and graceful pool exhaustion.

The equivalence property is the whole gate: the paged block-table path must
produce token-identical greedy outputs to the dense seq_ids-scatter path on
fp32 CPU (the same exactness the incremental-vs-recompute and bucket-ladder
tests already establish for the dense programs)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference import (
    ContinuousBatchingEngine,
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import (
    all_shapes,
    audit_programs,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
    audit_engine,
    make_serving_engine,
)

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _engine(params, max_batch=4, max_seq_len=64, buckets=(8, 16, 32)):
    return InferenceEngine(
        TINY, params,
        max_batch=max_batch, max_seq_len=max_seq_len, buckets=list(buckets),
    )


def _dense_outputs(params, prompts, gen, **kw):
    dense = ContinuousBatchingEngine(_engine(params, **kw), gen)
    for p in prompts:
        dense.submit(p)
    return dense.run_to_completion()


def _prompts(rng, lengths):
    return [
        rng.integers(0, TINY.vocab_size, size=(n,)).tolist() for n in lengths
    ]


def test_paged_matches_dense_on_mixed_length_batch(params):
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(3), (5, 12, 20, 9, 17, 3))
    paged = PagedServingEngine(
        _engine(params), gen, PagedConfig(block_size=8, num_blocks=64)
    )
    for p in prompts:
        paged.submit(p)
    out = paged.run_to_completion()
    assert out == _dense_outputs(params, prompts, gen)
    m = paged.metrics
    assert m.finished == len(prompts)
    assert paged.allocator.active_blocks == 0  # everything released
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []


def test_prefix_reuse_reports_cached_tokens_and_stays_equivalent(params):
    gen = GenerationConfig(max_new_tokens=6)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, TINY.vocab_size, size=(24,)).tolist()
    prompts = [
        shared + rng.integers(0, TINY.vocab_size, size=(4,)).tolist()
        for _ in range(6)
    ]
    paged = PagedServingEngine(
        _engine(params, max_batch=2), gen,
        PagedConfig(block_size=8, num_blocks=64),
    )
    for p in prompts:
        paged.submit(p)
    out = paged.run_to_completion()
    assert out == _dense_outputs(params, prompts, gen, max_batch=2)
    # first request prefills everything; later ones admit the shared 24
    # tokens (3 full blocks) by reference
    infos = [paged.request_info(r) for r in range(len(prompts))]
    assert infos[0]["cached_tokens"] == 0
    assert all(i["cached_tokens"] == 24 for i in infos[1:])
    m = paged.metrics
    assert m.cached_tokens == 24 * 5
    assert m.prefix_skip_fraction() > 0.5
    assert paged.index.hit_rate() > 0.5


def test_pool_exhaustion_preempts_requeues_and_completes(params):
    # 9 usable blocks, 4 requests that each grow to 6 blocks: decode MUST
    # exhaust the pool; the engine preempts the youngest and requeues —
    # run_to_completion finishes everyone with no exception and the final
    # tokens are identical to the uncontended dense run (greedy recompute
    # determinism)
    gen = GenerationConfig(max_new_tokens=36)
    prompts = _prompts(np.random.default_rng(5), (12, 12, 12, 12))
    for caching in (False, True):
        paged = PagedServingEngine(
            _engine(params), gen,
            PagedConfig(
                block_size=8, num_blocks=10, decode_reserve_blocks=1,
                enable_prefix_caching=caching,
            ),
        )
        for p in prompts:
            paged.submit(p)
        out = paged.run_to_completion()
        assert out == _dense_outputs(params, prompts, gen)
        assert paged.metrics.preemptions > 0
        assert paged.metrics.finished == 4
        if caching:
            assert paged.allocator.evictions > 0


def test_copy_on_write_on_partial_block_share(params):
    # phase 1 finishes a request whose final partial block gets registered;
    # phase 2's prompt diverges INSIDE that block -> token-granular match +
    # copy-on-write before the suffix write
    gen = GenerationConfig(max_new_tokens=4)
    rng = np.random.default_rng(11)
    base = rng.integers(0, TINY.vocab_size, size=(27,)).tolist()
    p1 = base + [1]
    p2 = base + [2, 3]  # diverges at token 27, mid-block for block_size=8
    paged = PagedServingEngine(
        _engine(params), gen, PagedConfig(block_size=8, num_blocks=64)
    )
    paged.submit(p1)
    out1 = paged.run_to_completion()
    paged.submit(p2)
    out2 = paged.run_to_completion()
    assert paged.allocator.cow_copies >= 1
    assert paged.request_info(1)["cached_tokens"] == 27
    dense = _dense_outputs(params, [p1, p2], gen)
    assert {0: out1[0], 1: out2[1]} == dense


@pytest.mark.slow  # tier-1 time budget; prefix reuse covered by the cached-tokens test
def test_acceptance_prefix_workload():
    # the ISSUE acceptance bar, via the bench entry point: 16 requests
    # sharing a 256-token prefix -> >=50% of prefill tokens skipped AND
    # token-identical to the dense engine
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))
    import kv_block_bench

    record = kv_block_bench.run_bench(kv_block_bench.build_args([]))
    assert record.get("gate_failure") is None
    assert record["dense_equivalent"] is True
    assert record["prefix_skip_fraction"] >= 0.5
    assert record["cached_tokens"] >= 15 * 256


def test_bench_smoke_mode():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))
    import kv_block_bench

    record = kv_block_bench.run_bench(kv_block_bench.build_args(["--smoke"]))
    assert record.get("gate_failure") is None
    assert record["smoke"] is True
    assert record["dense_equivalent"] is True


def test_submit_validation(params):
    gen = GenerationConfig(max_new_tokens=8)
    paged = PagedServingEngine(
        _engine(params), gen,
        PagedConfig(block_size=8, num_blocks=6),
    )
    with pytest.raises(ValueError, match="cache capacity"):
        paged.submit(list(range(60)))  # 60 + 8 > max_seq_len 64
    with pytest.raises(ValueError, match="blocks"):
        paged.submit(list(range(30)))  # needs 5+reserve > 5 usable
    with pytest.raises(ValueError, match="decode_reserve_blocks"):
        PagedServingEngine(
            _engine(params), gen,
            PagedConfig(block_size=8, decode_reserve_blocks=0),
        )


def test_make_serving_engine_flag(params):
    gen = GenerationConfig(max_new_tokens=4)
    assert isinstance(
        make_serving_engine(_engine(params), gen, paged=None),
        ContinuousBatchingEngine,
    )
    assert isinstance(
        make_serving_engine(
            _engine(params), gen,
            paged=PagedConfig(block_size=8, num_blocks=32),
        ),
        PagedServingEngine,
    )


def test_metrics_snapshot_shape(params):
    gen = GenerationConfig(max_new_tokens=4)
    paged = PagedServingEngine(
        _engine(params), gen, PagedConfig(block_size=8, num_blocks=32)
    )
    paged.submit(_prompts(np.random.default_rng(0), (10,))[0])
    paged.run_to_completion()
    snap = paged.metrics.snapshot(paged.allocator, paged.index)
    for key in (
        "submitted", "finished", "preemptions", "prefill_tokens",
        "cached_tokens", "prefix_skip_fraction", "block_utilization",
        "free_blocks", "prefix_hit_rate", "radix_nodes",
        "decode_steps_async", "lame_duck_tokens", "lookahead_declined_pool",
        "lane_syncs", "table_deltas", "h2d_uploads",
        "host_schedule_ms_per_step", "device_wait_ms_per_step",
    ):
        assert key in snap


# ---------------------------------------------------------------------------
# gather-free decode kernel (use_paged_kernel) + chunked prefill
# ---------------------------------------------------------------------------

TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


def _kernel_engine(params, max_batch=4, max_seq_len=64, buckets=(8, 16, 32)):
    return InferenceEngine(
        TINY_KERNEL, params,
        max_batch=max_batch, max_seq_len=max_seq_len, buckets=list(buckets),
    )


def test_paged_kernel_engine_matches_dense(params):
    """Acceptance: with use_paged_kernel, run_to_completion greedy outputs
    are token-identical to the dense engine on the mixed-length fixture."""
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(3), (5, 12, 20, 9, 17, 3))
    paged = PagedServingEngine(
        _kernel_engine(params), gen, PagedConfig(block_size=8, num_blocks=64)
    )
    for p in prompts:
        paged.submit(p)
    assert paged.run_to_completion() == _dense_outputs(params, prompts, gen)


def test_paged_kernel_cow_partial_prefix_matches_dense(params):
    """Kernel path over a partially-shared prefix: the second prompt
    diverges mid-block, so its table carries a COW copy — outputs must
    still match dense exactly."""
    gen = GenerationConfig(max_new_tokens=4)
    rng = np.random.default_rng(11)
    base = rng.integers(0, TINY.vocab_size, size=(27,)).tolist()
    p1 = base + [1]
    p2 = base + [2, 3]  # diverges at token 27, mid-block for block_size=8
    paged = PagedServingEngine(
        _kernel_engine(params), gen, PagedConfig(block_size=8, num_blocks=64)
    )
    paged.submit(p1)
    out1 = paged.run_to_completion()
    paged.submit(p2)
    out2 = paged.run_to_completion()
    assert paged.allocator.cow_copies >= 1
    assert paged.request_info(1)["cached_tokens"] == 27
    dense = _dense_outputs(params, [p1, p2], gen)
    assert {0: out1[0], 1: out2[1]} == dense


def test_paged_kernel_chunked_prefill_matches_dense(params):
    """Kernel + chunked prefill together (the full tentpole config)."""
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(3), (5, 30, 20, 9, 26, 3))
    paged = PagedServingEngine(
        _kernel_engine(params), gen,
        PagedConfig(block_size=8, num_blocks=64, prefill_chunk_tokens=8),
    )
    for p in prompts:
        paged.submit(p)
    out = paged.run_to_completion()
    assert out == _dense_outputs(params, prompts, gen)
    assert paged.metrics.prefill_chunks > 0


def test_paged_kernel_decode_never_materializes_gather(params):
    """Acceptance: the decode jaxpr must not contain a (b, kv_limit, NKV, D)
    gathered K/V array anywhere (including nested scan/jit sub-jaxprs) when
    the kernel is on — and must contain it when it is off (sanity check
    that the assertion actually detects the gather)."""
    import jax.numpy as jnp

    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    b, kv_limit, nb, bs, w = 4, 32, 16, 8, 8
    forbidden = (b, kv_limit, TINY.num_kv_heads, TINY.head_dim)
    for flag, expect_gather in ((False, True), (True, False)):
        cfg = dataclasses.replace(TINY, use_paged_kernel=flag)
        model = LlamaDecode(cfg)
        cache = model.init_paged_cache(nb, bs)
        closed = jax.make_jaxpr(
            lambda p, c, t, ps, tb: model.forward(  # noqa: B023
                p, c, t, ps, None, block_tables=tb, kv_limit=kv_limit
            )
        )(
            params, cache, jnp.zeros((b, 1), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b, w), jnp.int32),
        )
        shapes = all_shapes(closed)
        assert (forbidden in shapes) is expect_gather, (
            f"use_paged_kernel={flag}: gather aval {forbidden} "
            f"{'missing' if expect_gather else 'present'} in decode jaxpr"
        )


@pytest.fixture(scope="module")
def moe_params():
    from neuronx_distributed_llama3_2_tpu.models.mixtral import MIXTRAL_CONFIGS, MixtralForCausalLM

    return jax.jit(MixtralForCausalLM(MIXTRAL_CONFIGS["tiny-moe"]).init)(jax.random.key(0))


def _moe_serving(moe_params, mode, monkeypatch, lanes):
    """A tiny Mixtral engine built (and its programs traced) under kernel
    mode ``mode``: ``"interpret"`` walks a lane's live blocks at one row a
    lane, ``"reference"`` (this tier's) gathers the rung."""
    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
    from neuronx_distributed_llama3_2_tpu.models.mixtral import MIXTRAL_CONFIGS

    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    eng = InferenceEngine(
        MIXTRAL_CONFIGS["tiny-moe"], moe_params, max_batch=lanes, max_seq_len=64, buckets=[8, 16, 32])
    return PagedServingEngine(
        eng, GenerationConfig(max_new_tokens=10),
        PagedConfig(block_size=4, num_blocks=96, prefill_chunk_tokens=8, prefill_buckets=(8,), kv_buckets=(64,)))


def test_the_block_walk_serves_a_tiny_mixtral_token_for_token_with_the_gather(moe_params, monkeypatch):
    """``LlamaDecode._attend_paged`` at one row a lane, the walk
    (``"interpret"``) beside the gather (``"reference"``), under what the
    engine does to a table: a prefix two lanes share, a long prompt prefilled
    in chunks beside live decodes (its lane on the null block meanwhile), more
    requests than lanes (a finished lane's slot and blocks reused)."""
    rng = np.random.default_rng(21)
    vocab = 256
    shared = rng.integers(1, vocab, size=(12,)).tolist()
    own = [rng.integers(1, vocab, size=(n,)).tolist() for n in (3, 5, 6, 41, 9, 30, 2)]
    prompts = [shared + own[0], own[1], shared + own[2]] + own[3:]
    outs, reads = {}, {}
    for mode in ("reference", "interpret"):
        srv = _moe_serving(moe_params, mode, monkeypatch, lanes=4)
        reads[mode] = srv._kind_facts()["cache_kinds"]["rows"]["decode_read"]
        rids = [srv.submit(p) for p in prompts[:2]]
        for _ in range(3):
            srv.step()                     # two lanes decoding, the prefix indexed
        rids += [srv.submit(p) for p in prompts[2:]]
        # the 41-token prompt's chunks run beside the others' decode steps
        beside = 0
        while srv.step():
            live = list(srv._active.values())
            beside += any(r.prefilling for r in live) and any(not r.prefilling for r in live)
        out = srv.run_to_completion()
        outs[mode] = [out[r] for r in rids]
        m = srv.metrics
        assert beside >= 3 and m.prefill_chunks >= 6 and m.cached_tokens >= 12, (beside, m.prefill_chunks)
        assert m.finished == len(prompts) > 3
        assert srv.allocator.leak_check() == [] and audit_engine(srv) == []
    assert reads == {"reference": "gather", "interpret": "kernel"}
    assert outs["interpret"] == outs["reference"] and all(len(o) == 10 for o in outs["interpret"])


def test_chunked_prefill_interleaves_decode(params):
    """Acceptance: with prefill_chunk_tokens set, a long-prompt admission
    interleaves — the already-active lane gains a decode token on the same
    steps that advance the new request's prefill chunks."""
    gen = GenerationConfig(max_new_tokens=16)
    rng = np.random.default_rng(9)
    pa = rng.integers(0, TINY.vocab_size, size=(4,)).tolist()
    pb = rng.integers(0, TINY.vocab_size, size=(32,)).tolist()
    paged = PagedServingEngine(
        _engine(params), gen,
        PagedConfig(block_size=8, num_blocks=64, prefill_chunk_tokens=8),
    )
    ra = paged.submit(pa)
    paged.step()  # A admitted (short prompt: unchunked) and decoding
    rb = paged.submit(pb)
    trace = []  # (A generated, B prefill progress, B still prefilling)
    for _ in range(4):
        paged.step()
        a, b = paged._requests[ra], paged._requests[rb]
        trace.append((len(a.out), b.prefill_pos, b.prefilling))
    # B took all 4 steps of chunked prefill (32 tokens / 8 per chunk) ...
    assert [t[1] for t in trace] == [8, 16, 24, 32]
    assert [t[2] for t in trace] == [True, True, True, False]
    # ... and A decoded one token on every one of those steps
    assert [t[0] for t in trace] == [3, 4, 5, 6]
    assert paged.metrics.prefill_chunks == 4
    assert paged.request_info(rb)["prefilling"] is False
    out = paged.run_to_completion()
    assert out == _dense_outputs(params, [pa, pb], gen)


def test_preempt_resume_mid_chunked_prefill(params):
    """An older lane's decode growth exhausts the pool while a younger
    request is mid-chunked-prefill: the victim is caught prefilling, is
    requeued, re-admits, and the final outputs still match dense."""
    gen = GenerationConfig(max_new_tokens=8)
    rng = np.random.default_rng(21)
    pa = rng.integers(0, TINY.vocab_size, size=(8,)).tolist()
    pb = rng.integers(0, TINY.vocab_size, size=(30,)).tolist()
    paged = PagedServingEngine(
        _engine(params), gen,
        PagedConfig(
            block_size=4, num_blocks=12, decode_reserve_blocks=1,
            prefill_chunk_tokens=4,
        ),
    )
    preempted = []  # (rid, was_prefilling) at preemption time
    orig = paged._preempt

    def spy(req):
        preempted.append((req.rid, req.prefilling))
        orig(req)

    paged._preempt = spy
    ra = paged.submit(pa)
    rb = paged.submit(pb)
    out = paged.run_to_completion()
    assert (rb, True) in preempted, preempted
    assert paged.request_info(rb)["preemptions"] >= 1
    assert out == _dense_outputs(params, [pa, pb], gen)
    assert paged.allocator.active_blocks == 0
    del ra


def test_request_info_map_covers_all_lifecycle_states(params):
    gen = GenerationConfig(max_new_tokens=4)
    paged = PagedServingEngine(
        _engine(params, max_batch=1), gen,
        PagedConfig(block_size=8, num_blocks=64),
    )
    r0 = paged.submit(_prompts(np.random.default_rng(0), (10,))[0])
    r1 = paged.submit(_prompts(np.random.default_rng(1), (10,))[0])
    paged.step()  # r0 active (sole lane), r1 still queued
    assert paged.request_info(r0)["generated_tokens"] >= 1
    assert paged.request_info(r1)["generated_tokens"] == 0
    paged.run_to_completion()
    assert paged.request_info(r0)["done"] is True
    assert paged.request_info(r1)["done"] is True
    with pytest.raises(KeyError, match="unknown request id"):
        paged.request_info(99)


def test_admit_blocked_counter(params):
    """Admission deferrals on the block budget are counted (and flow into
    the metrics log line via snapshot())."""
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(5), (12, 12, 12, 12))
    paged = PagedServingEngine(
        _engine(params), gen,
        PagedConfig(block_size=8, num_blocks=10, decode_reserve_blocks=1),
    )
    for p in prompts:
        paged.submit(p)
    paged.run_to_completion()
    assert paged.metrics.admit_blocked > 0
    snap = paged.metrics.snapshot(paged.allocator, paged.index)
    assert "admit_blocked" in snap and "prefill_chunks" in snap


# ---------------------------------------------------------------------------
# the layer loop carries the whole pool and indexes it by layer
# ---------------------------------------------------------------------------


def _loop_families():
    from neuronx_distributed_llama3_2_tpu.models import (
        MIXTRAL_CONFIGS,
        MixtralForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.models.gptneox import (
        GPTNEOX_CONFIGS,
        GPTNeoXForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.models.olmoe import (
        OLMOE_CONFIGS,
        OlmoeForCausalLM,
    )

    return {
        "llama": (dataclasses.replace(TINY, num_layers=3), LlamaForCausalLM),
        "mixtral": (MIXTRAL_CONFIGS["tiny-moe"], MixtralForCausalLM),
        "olmoe": (OLMOE_CONFIGS["tiny-olmoe"], OlmoeForCausalLM),
        "gptneox": (dataclasses.replace(GPTNEOX_CONFIGS["tiny-neox"], num_layers=3),
                    GPTNeoXForCausalLM),
    }


def _noisy_pool(model, kv):
    """A pool of 12 blocks of 4 rows with something in every row, so that a
    row the program should not have written shows when it has."""
    pool = model.init_paged_cache(12, 4, kv_cache_dtype=kv)
    keys = iter(jax.random.split(jax.random.key(7), 4))

    def noise(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jax.random.randint(next(keys), a.shape, -127, 128, jnp.int32).astype(a.dtype)
        return (0.1 + jax.random.uniform(next(keys), a.shape)).astype(a.dtype)

    return jax.tree.map(noise, pool)


def _per_layer_loop(model, params, pool, tokens, positions, tables, **kw):
    """``forward`` as a plain loop over ``pool[l]``: each layer is handed a
    pool of its one layer and index 0, and the layers' pools are stacked
    again — a ``lax.scan`` with the pool as its ``xs`` and ``ys`` where the
    model scans its layers (the form ``forward`` had), a Python loop where
    it unrolls them."""
    from neuronx_distributed_llama3_2_tpu.models.llama import make_norm

    c = model.config
    pos_block = positions[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    sin, cos = model._rope_tables(tables.shape[1] * pool.block_size)

    def layer(x, layer_in):
        lp, one = layer_in
        one = jax.tree.map(lambda a: a[None], one)
        kc, vc = (
            ((one.k, one.k_scale), (one.v, one.v_scale)) if pool.quantized
            else (one.k, one.v)
        )
        x, kc, vc = model._decode_layer(
            lp, x, kc, vc, 0, sin, cos, pos_block, positions, None,
            block_tables=tables, **kw,
        )
        one = (
            type(pool)(k=kc[0], v=vc[0], k_scale=kc[1], v_scale=vc[1])
            if pool.quantized else type(pool)(k=kc, v=vc)
        )
        return x, jax.tree.map(lambda a: a[0], one)

    x = model._model()._embed()(params["embed"], tokens)
    if c.scan_layers:
        x, new_pool = jax.lax.scan(layer, x, (params["layers"], pool))
    else:
        layers = []
        for l in range(c.num_layers):
            x, one = layer(x, jax.tree.map(lambda a: a[l], (params["layers"], pool)))
            layers.append(one)
        new_pool = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    x = make_norm(c)(params["final_norm"], x)
    return model._model()._logits(params, x), new_pool


@pytest.mark.parametrize("kv", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("program", ["pctx", "psfx", "pdecode"])
@pytest.mark.parametrize("family", ["llama", "mixtral", "olmoe", "gptneox"])
def test_layer_loop_over_the_whole_pool_is_a_loop_over_its_layers(family, program, scan, kv):
    """Logits and the *whole* returned pool — the rows of other layers and of
    unwritten blocks with them — are bit-equal to the per-layer loop."""
    from neuronx_distributed_llama3_2_tpu.inference import decode_model_for

    cfg, train = _loop_families()[family]
    cfg = dataclasses.replace(cfg, scan_layers=scan)
    model = decode_model_for(cfg)
    params = train(cfg).init(jax.random.key(0))
    pool = _noisy_pool(model, kv)
    # two lanes of four blocks; lane 1's last entry is the null block
    tables = jnp.asarray([[3, 9, 1, 5], [7, 2, 11, 0]], jnp.int32)
    rng = np.random.default_rng(3)
    if program == "pctx":
        tokens, positions = rng.integers(0, cfg.vocab_size, (2, 8)), [0, 0]
        kw = dict(context_encode=True)
    elif program == "psfx":
        tokens, positions = rng.integers(0, cfg.vocab_size, (2, 4)), [5, 8]
        kw = dict(context_encode=False, kv_limit=16)
    else:
        tokens, positions = rng.integers(0, cfg.vocab_size, (2, 1)), [6, 11]
        kw = dict(context_encode=False, kv_limit=16)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)

    want_logits, want_pool = jax.jit(
        lambda p, ch: _per_layer_loop(model, p, ch, tokens, positions, tables, **kw)
    )(params, pool)
    got_logits, got_pool = jax.jit(
        lambda p, ch: model.forward(p, ch, tokens, positions, None, block_tables=tables, **kw),
        donate_argnums=(1,),
    )(params, jax.tree.map(jnp.copy, pool))

    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    written = 0
    for got, want, before in zip(
        jax.tree.leaves(got_pool), jax.tree.leaves(want_pool), jax.tree.leaves(pool)
    ):
        assert got.shape == before.shape and got.dtype == before.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        written += int(np.sum(np.any(
            np.asarray(got) != np.asarray(before), axis=tuple(range(3, got.ndim))
        )))
    # the rows the program wrote, and no other, differ from what was there:
    # T rows a lane in every layer of every leaf (lane 1's rows 12.. of psfx
    # and pdecode do not exist: positions stay inside the table)
    leaves = len(jax.tree.leaves(pool))
    assert written == leaves * cfg.num_layers * tokens.size
