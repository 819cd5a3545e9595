"""Quantized paged KV pool (``PagedConfig.kv_cache_dtype``): parity matrix,
COW-with-scales, fp-path regression, capacity accounting, spec drift canary.

The exactness property under test is stronger than "quantized is close to
fp": because the per-(row, kv-head) scales are append-local (quantize on
write, dequantize identically on every read path), EVERY quantized engine
configuration — gather or kernel, sync or async, chunked or whole prefill,
tp=1 or tp=2 — must produce token-IDENTICAL greedy outputs. Only the
quantized-vs-fp comparison gets a tolerance band (the int8 round-trip error
itself). The fp path must be structurally untouched: scales default to
``None`` and the cache flattens to the same ``(k, v)`` pair as before.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import audit_programs
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.parallel.state import (
    initialize_model_parallel,
    kv_head_shard_size,
)
from neuronx_distributed_llama3_2_tpu.quantization import (
    KV_CACHE_DTYPES,
    KV_SCALE_DTYPE,
    kv_dequantize,
    kv_quantize,
    kv_scale_itemsize,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
    audit_engine,
)
from neuronx_distributed_llama3_2_tpu.serving.block_allocator import (
    kv_pool_bytes_per_rank,
)

from tests.drained_policy import LOOPS
from tests.test_async_serving import _paged, _run
from tests.test_paged_serving import _prompts

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _qcfg(**kw):
    kw.setdefault("kv_cache_dtype", "int8")
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return PagedConfig(**kw)


@pytest.fixture(scope="module")
def int8_baseline(params):
    """Reference cell of the parity matrix: int8, gather, drained, whole."""
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(7), (5, 12, 20, 9))
    out = _run(_paged(params, gen, _qcfg(), loop="drained"), prompts)
    return gen, prompts, out


# -- scale-math units ------------------------------------------------------


def test_kv_quantize_roundtrip_int8():
    x = jax.random.normal(jax.random.key(1), (4, 8, 3, 16), jnp.float32) * 5.0
    q, s = kv_quantize(x, jnp.int8)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == KV_SCALE_DTYPE and s.shape == x.shape[:-1]
    y = kv_dequantize(q, s, jnp.float32)
    # symmetric absmax: per-element error bounded by half a quantization
    # step, i.e. scale/2 per (row, head)
    err = jnp.max(jnp.abs(y - x) / jnp.maximum(s.astype(jnp.float32)[..., None], 1e-6))
    assert float(err) <= 0.5 + 1e-3
    # write/read stability: re-quantizing the dequantized values must be a
    # fixed point (the engine round-trips fresh K/V through the pool)
    q2, s2 = kv_quantize(y, jnp.int8)
    assert jnp.array_equal(q, q2) and jnp.array_equal(s, s2)


def test_kv_quantize_zero_rows_and_fp8():
    z = jnp.zeros((2, 4, 2, 8), jnp.float32)
    q, s = kv_quantize(z, jnp.int8)
    assert jnp.array_equal(kv_dequantize(q, s, jnp.float32), z)
    for name in ("fp8_e4m3", "fp8_e5m2"):
        dt = KV_CACHE_DTYPES[name]
        x = jax.random.normal(jax.random.key(2), (2, 4, 2, 8), jnp.float32)
        q, s = kv_quantize(x, dt)
        y = kv_dequantize(q, s, jnp.float32)
        assert q.dtype == dt and bool(jnp.all(jnp.isfinite(y)))
        assert float(jnp.max(jnp.abs(y - x))) < 0.2 * float(jnp.max(jnp.abs(x)))


def test_kv_cache_dtype_validation(params):
    assert set(KV_CACHE_DTYPES) == {"bf16", "int8", "fp8_e4m3", "fp8_e5m2"}
    assert kv_scale_itemsize("bf16") == 0
    assert kv_scale_itemsize("int8") == kv_scale_itemsize("fp8_e4m3") == 2
    gen = GenerationConfig(max_new_tokens=4)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _paged(params, gen, _qcfg(kv_cache_dtype="int4"))
    with pytest.raises(ValueError, match="cache_dtype"):
        _paged(params, gen, _qcfg(cache_dtype=jnp.bfloat16))


# -- fp-path regression ----------------------------------------------------


def test_fp_default_cache_has_no_scale_arrays(params):
    """Structural bitwise guarantee: the default (bf16) pool is the exact
    pre-quantization pytree — two payload leaves, no scale fields — so fp
    traces, donation, and sharding specs are untouched."""
    m = LlamaDecode(TINY)
    cache = m.init_paged_cache(16, 8)
    assert cache.k_scale is None and cache.v_scale is None
    assert not cache.quantized
    assert len(jax.tree.leaves(cache)) == 2
    qc = m.init_paged_cache(16, 8, kv_cache_dtype="int8")
    assert qc.quantized and qc.k.dtype == jnp.int8
    assert qc.k_scale.dtype == KV_SCALE_DTYPE
    assert qc.k_scale.shape == qc.k.shape[:-1]
    assert len(jax.tree.leaves(qc)) == 4
    with pytest.raises(ValueError):
        m.init_paged_cache(16, 8, dtype=jnp.bfloat16, kv_cache_dtype="int8")


def test_fp_engine_metrics_and_pool_bytes_unchanged(params):
    gen = GenerationConfig(max_new_tokens=4)
    paged = _paged(params, gen, PagedConfig(block_size=8, num_blocks=16))
    snap = paged.metrics.snapshot(paged.allocator)
    assert snap["kv_dtype"] == "bf16"
    assert snap["pool_bytes_per_rank"] == kv_pool_bytes_per_rank(
        num_layers=TINY.num_layers, num_blocks=16, block_size=8,
        num_kv_heads=TINY.num_kv_heads, head_dim=TINY.head_dim,
        dtype_bytes=4,  # tiny runs fp32 on CPU
    )


def test_dense_path_rejects_quantized_cache(params):
    m = LlamaDecode(TINY)
    qc = m.init_paged_cache(16, 8, kv_cache_dtype="int8")
    ids = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="quantized"):
        m.forward(params, qc, ids, jnp.zeros((1,), jnp.int32))


# -- engine parity matrix --------------------------------------------------


@pytest.mark.parametrize("model_cfg", [TINY, TINY_KERNEL], ids=["gather", "kernel"])
@pytest.mark.parametrize("loop", LOOPS)
@pytest.mark.parametrize("chunk", [None, 6], ids=["whole", "chunked"])
def test_quantized_parity_matrix(params, int8_baseline, model_cfg, loop, chunk):
    """Every int8 cell is token-identical to the reference cell: the
    append-local scales make quantized values independent of prefill
    chunking, loop mode, and kernel-vs-gather eligibility."""
    gen, prompts, want = int8_baseline
    paged = _paged(
        params, gen,
        _qcfg(prefill_chunk_tokens=chunk), model_cfg=model_cfg, loop=loop,
    )
    assert _run(paged, prompts) == want
    assert paged.metrics.kv_dtype == "int8"
    assert paged.metrics.snapshot()["kv_dtype"] == "int8"


@pytest.mark.parametrize(
    "kv_dtype",
    # tier-1 time budget: one fp8 flavour in the default tier, the other slow
    ["fp8_e4m3", pytest.param("fp8_e5m2", marks=pytest.mark.slow)],
)
def test_fp8_gather_matches_kernel(params, kv_dtype):
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(np.random.default_rng(11), (5, 12, 9))
    got_g = _run(_paged(params, gen, _qcfg(kv_cache_dtype=kv_dtype)), prompts)
    got_k = _run(
        _paged(params, gen, _qcfg(kv_cache_dtype=kv_dtype), model_cfg=TINY_KERNEL),
        prompts,
    )
    assert got_g == got_k


def test_int8_logits_within_tolerance_of_fp(params):
    """The only non-exact comparison: quantized vs fp logits after a paged
    prefill + one decode step sit inside the int8 round-trip band
    (measured ~0.25% relative on tiny; asserted at 5%)."""
    m = LlamaDecode(TINY)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_size, size=(2, 16)), jnp.int32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pos0 = jnp.zeros((2,), jnp.int32)

    def one(kv_dtype):
        cache = m.init_paged_cache(16, 8, kv_cache_dtype=kv_dtype)
        lg, cache = m.forward(
            params, cache, ids, pos0,
            block_tables=tables, context_encode=kv_dtype is None,
        )
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        lg2, _, _ = m.decode_step(
            params, cache, tok, jnp.full((2,), 16, jnp.int32), tables,
            kv_limit=32,
        )
        return lg2

    fp, q = one(None), one("int8")
    rel = jnp.max(jnp.abs(fp - q)) / jnp.max(jnp.abs(fp))
    assert float(rel) < 0.05


# -- low-precision MXU decode dot (PagedConfig.quant_mxu) -------------------


@pytest.mark.parametrize("loop", LOOPS)
def test_quant_mxu_parity_cells(params, int8_baseline, loop):
    """quant_mxu rows of the parity matrix: the int8-accumulate q·k dot
    (scales applied post-dot) stays token-identical to the reference int8
    cell on tiny — measured zero greedy drift; the formal gate is the 5%
    logits band of test_quant_mxu_logits_within_band_of_fp."""
    gen, prompts, want = int8_baseline
    paged = _paged(
        params, gen,
        _qcfg(quant_mxu=True), model_cfg=TINY_KERNEL, loop=loop,
    )
    assert _run(paged, prompts) == want
    assert paged.model.config.quant_mxu


def test_quant_mxu_logits_within_band_of_fp(params):
    """The acceptance band from the quant parity matrix: decode logits
    through the MXU-native int8 dot sit inside 5% of the FP cache path
    (the widened int8 path already sits inside the same band above)."""
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_size, size=(2, 16)), jnp.int32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pos0 = jnp.zeros((2,), jnp.int32)

    def one(kv_dtype, quant_mxu=False):
        m = LlamaDecode(
            dataclasses.replace(TINY_KERNEL, quant_mxu=quant_mxu)
        )
        cache = m.init_paged_cache(16, 8, kv_cache_dtype=kv_dtype)
        lg, cache = m.forward(
            params, cache, ids, pos0,
            block_tables=tables, context_encode=kv_dtype is None,
        )
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        lg2, _, _ = m.decode_step(
            params, cache, tok, jnp.full((2,), 16, jnp.int32), tables,
            kv_limit=32,
        )
        return lg2

    fp, mxu = one(None), one("int8", quant_mxu=True)
    rel = jnp.max(jnp.abs(fp - mxu)) / jnp.max(jnp.abs(fp))
    assert float(rel) < 0.05


# -- COW with scales -------------------------------------------------------


def test_copy_block_fn_copies_scale_rows(params):
    gen = GenerationConfig(max_new_tokens=4)
    paged = _paged(params, gen, _qcfg(num_blocks=8))
    c = paged.cache
    c = type(c)(
        k=c.k.at[:, 2].set(7), v=c.v.at[:, 2].set(-7),
        k_scale=c.k_scale.at[:, 2].set(3.0),
        v_scale=c.v_scale.at[:, 2].set(5.0),
    )
    out = paged._copy_block_fn(
        c, jnp.asarray(2, jnp.int32), jnp.asarray(5, jnp.int32)
    )
    assert bool(jnp.all(out.k[:, 5] == 7)) and bool(jnp.all(out.v[:, 5] == -7))
    assert bool(jnp.all(out.k_scale[:, 5] == 3.0))
    assert bool(jnp.all(out.v_scale[:, 5] == 5.0))


def test_cow_prefix_share_stays_exact(params):
    """Prefix-cached int8 engine == uncached int8 engine: COW copies the
    scale tile with the payload tile, so a shared partial block diverges
    safely after the copy."""
    gen = GenerationConfig(max_new_tokens=6)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, TINY.vocab_size, size=(20,)).tolist()
    prompts = [
        shared + rng.integers(0, TINY.vocab_size, size=(4,)).tolist()
        for _ in range(4)
    ]
    cached = _paged(params, gen, _qcfg(), model_cfg=TINY_KERNEL)
    out = _run(cached, prompts)
    assert cached.metrics.cached_tokens > 0
    assert cached.allocator.cow_copies >= 1
    uncached = _paged(
        params, gen, _qcfg(enable_prefix_caching=False), model_cfg=TINY_KERNEL
    )
    assert _run(uncached, prompts) == out


# -- speculative decoding drift canary -------------------------------------


@pytest.mark.slow  # tier-1 time budget; statistical canary, not a parity gate
def test_spec_accept_rate_drift_canary(params):
    """Soak canary: the n-gram drafter's accept rate under int8 must track
    the fp rate — quantization error that flipped verify argmaxes would
    show up here as drift."""
    gen = GenerationConfig(max_new_tokens=16)
    rng = np.random.default_rng(9)
    pattern = rng.integers(0, TINY.vocab_size, size=(6,)).tolist()
    prompts = [pattern * 5, pattern * 4 + pattern[:3]]

    def accept(kv_dtype):
        paged = _paged(
            params, gen,
            _qcfg(kv_cache_dtype=kv_dtype, spec_draft_tokens=3),
            model_cfg=TINY_KERNEL,
        )
        out = _run(paged, prompts)
        assert paged.metrics.draft_tokens > 0
        return paged.metrics.accept_rate(), out

    fp_rate, _ = accept("bf16")
    q_rate, q_out = accept("int8")
    assert abs(fp_rate - q_rate) <= 0.15
    # and speculation does not change the int8 tokens themselves
    plain = _run(_paged(params, gen, _qcfg(), model_cfg=TINY_KERNEL), prompts)
    assert q_out == plain


# -- residency (zero-upload steady state) ----------------------------------


def test_quantized_steady_state_is_fully_resident(params):
    """The PR-4 acceptance check holds under int8: steady-state async steps
    do zero host→device uploads — quantize-on-write lives inside the same
    donated decode program, so no extra transfers appear."""
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=32, num_blocks=8,
            kv_cache_dtype="int8",
        ),
    )
    paged.submit(_prompts(np.random.default_rng(0), (4,))[0])
    paged.step()
    paged.step()
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas)
        assert paged.step()
        assert (m.h2d_uploads, m.lane_syncs, m.table_deltas) == before
        assert paged._last_readback_lag == 1
    paged.run_to_completion()
    # quantized teardown: pool drained, scale arrays still matching dtype
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []


# -- tensor parallel -------------------------------------------------------


def test_quantized_tp2_matches_tp1_and_pool_bytes(params, int8_baseline):
    """tp=2 int8 kernel engine is token-identical to tp=1, the scale
    arrays shard the same kv-head split, and per-rank pool bytes (payload
    + scales) are exactly half the logical pool."""
    gen, prompts, want = int8_baseline
    initialize_model_parallel(
        tensor_model_parallel_size=2, devices=jax.devices()[:2]
    )
    paged = _paged(params, gen, _qcfg(), model_cfg=TINY_KERNEL)
    assert _run(paged, prompts) == want
    m = paged.metrics
    assert m.tp_size == 2 and m.kv_dtype == "int8"
    assert m.pool_bytes_total == 2 * m.pool_bytes_per_rank
    heads_rank = kv_head_shard_size(TINY.num_kv_heads)
    assert heads_rank == TINY.num_kv_heads // 2
    assert m.pool_bytes_per_rank == kv_pool_bytes_per_rank(
        num_layers=TINY.num_layers, num_blocks=64, block_size=8,
        num_kv_heads=TINY.num_kv_heads, head_dim=TINY.head_dim,
        dtype_bytes=1, tp_size=2, scale_bytes=kv_scale_itemsize("int8"),
    )


# -- capacity accounting ---------------------------------------------------


def test_int8_capacity_ratio_at_llama_geometry():
    """Acceptance number: at llama-class head_dim=64 and fixed per-chip
    pool bytes, int8 (+fp16 scales) fits ≥1.9× the bf16 resident lanes."""
    geom = dict(
        num_layers=32, num_blocks=1024, block_size=16,
        num_kv_heads=8, head_dim=64,
    )
    bf16 = kv_pool_bytes_per_rank(dtype_bytes=2, **geom)
    int8 = kv_pool_bytes_per_rank(
        dtype_bytes=1, scale_bytes=kv_scale_itemsize("int8"), **geom
    )
    ratio = bf16 / int8
    assert ratio >= 1.9
    # equivalently: at a fixed byte budget, the block count (→ resident
    # lanes or kv_limit) scales by the same factor
    budget = bf16
    blocks_bf16 = budget // (bf16 // geom["num_blocks"])
    blocks_int8 = budget // (int8 // geom["num_blocks"])
    assert blocks_int8 >= 1.9 * blocks_bf16
