"""Fused on-device sampling (``PagedConfig.on_device_sampling``) and the
low-precision MXU decode dot (``PagedConfig.quant_mxu``).

The contracts under test:

- **greedy identity**: a greedy GenerationConfig under the fused engine
  (sentinel params, ``temperature <= 0`` -> exact argmax) is
  token-identical to the plain greedy engine in every loop mode;
- **zero-upload steady state**: sampled traffic keeps ``h2d_uploads`` at
  zero across decode-only steps — the GC003 twin for sampled traffic
  (the host path pays a PRNG-key upload per step);
- **preempt-resume determinism**: the per-lane base key is derived from
  ``(gen.seed, rid)`` and every draw is keyed by its landing sequence
  index (``fold_in``), so a preempted-and-resumed request replays the
  identical token stream, sync and async;
- **quant_mxu**: int8 q·k dots accumulate in int32 on the MXU inside the
  5% logits band of the fp engine, GC005 permits exactly that shape iff
  the knob is on, and the engine refuses the knob without a quantized
  pool;
- **sampling units**: top_k=0 / top_p=1.0 are true no-ops, top_k > vocab
  clamps, the top-p boundary token is included, fp16 logits sample in
  fp32 math.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import (
    audit_programs,
    check_fp32_widening,
)
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.inference.sampling import (
    GREEDY_TEMPERATURE,
    SamplingConfig,
    lane_keys,
    sample,
    sample_lanes,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
    audit_engine,
)

from tests.drained_policy import LOOPS
from tests.test_async_serving import _paged, _run
from tests.test_paged_serving import _prompts

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)

SAMPLED = SamplingConfig(greedy=False, temperature=0.8, top_k=40, top_p=0.9)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _cfg(**kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("on_device_sampling", True)
    return PagedConfig(**kw)


# -- sampling units (host path) --------------------------------------------


def test_sample_no_filters_is_plain_categorical():
    """top_k=0 and top_p=1.0 must be true no-ops: the draw equals a plain
    categorical over the temperature-scaled logits."""
    logits = jax.random.normal(jax.random.key(3), (4, 32), jnp.float32) * 2
    cfg = SamplingConfig(greedy=False, temperature=0.7)
    for i in range(5):
        key = jax.random.key(i)
        want = jax.random.categorical(key, logits / 0.7, axis=-1)
        got = sample(logits, key, cfg)
        assert jnp.array_equal(got, want.astype(jnp.int32))


def test_sample_top_k_clamps_to_vocab():
    """top_k beyond the vocab clamps: identical draws to no filter."""
    logits = jax.random.normal(jax.random.key(4), (3, 16), jnp.float32)
    big = SamplingConfig(greedy=False, temperature=1.0, top_k=1000)
    off = SamplingConfig(greedy=False, temperature=1.0)
    for i in range(5):
        key = jax.random.key(i)
        assert jnp.array_equal(sample(logits, key, big), sample(logits, key, off))


def test_sample_top_p_keeps_minimal_prefix_with_boundary():
    """probs ~(0.5, 0.3, 0.2): top_p=0.5 keeps exactly the head token
    (mass before it is 0 < 0.5, before the next is 0.5, not < 0.5);
    top_p=0.51 must also keep the BOUNDARY token that crosses the mass
    threshold — the minimal-prefix rule with boundary inclusion."""
    probs = np.array([0.5, 0.3, 0.2])
    logits = jnp.asarray(np.log(probs))[None, :]

    def picks(top_p, n=60):
        cfg = SamplingConfig(greedy=False, temperature=1.0, top_p=top_p)
        return {int(sample(logits, jax.random.key(i), cfg)[0]) for i in range(n)}

    assert picks(0.5) == {0}
    assert picks(0.51) <= {0, 1} and 1 in picks(0.51)
    assert picks(0.81) == {0, 1, 2} - ({2} - picks(0.81))  # 2 now eligible
    assert picks(1e-6) == {0}  # degenerate top_p still keeps the argmax


def test_sample_fp16_logits_use_fp32_math():
    logits16 = (
        jax.random.normal(jax.random.key(5), (2, 64), jnp.float32) * 3
    ).astype(jnp.float16)
    cfg = SamplingConfig(greedy=False, temperature=0.9, top_k=8, top_p=0.95)
    for i in range(4):
        key = jax.random.key(i)
        got = sample(logits16, key, cfg)
        want = sample(logits16.astype(jnp.float32), key, cfg)
        assert jnp.array_equal(got, want)
        assert got.dtype == jnp.int32


# -- sampling units (fused lanes path) --------------------------------------


def _lane_arrays(rows):
    temps = jnp.asarray([r[0] for r in rows], jnp.float32)
    topks = jnp.asarray([r[1] for r in rows], jnp.int32)
    topps = jnp.asarray([r[2] for r in rows], jnp.float32)
    return temps, topks, topps


def test_sample_lanes_matches_host_per_row():
    """Every (temperature, top_k, top_p) mode must draw the exact token
    the host ``sample`` path draws with the identically folded key —
    decode-shaped (B, V) and verify-shaped (B, T, V)."""
    rows = [
        (GREEDY_TEMPERATURE, 0, 1.0),
        (0.7, 0, 1.0),
        (1.3, 8, 1.0),
        (0.9, 0, 0.8),
        (1.1, 16, 0.9),
        (1.0, 1000, 1.0),   # top_k > vocab clamps
    ]
    b, v = len(rows), 128
    rng_data = jax.random.key_data(
        jax.random.split(jax.random.key(9), b)
    ).astype(jnp.uint32)
    temps, topks, topps = _lane_arrays(rows)
    positions = jnp.asarray([3, 100, 7, 255, 64, 1], jnp.int32)
    for t in (1, 4):
        shape = (b, v) if t == 1 else (b, t, v)
        logits = jax.random.normal(jax.random.key(10 + t), shape) * 3.0
        index = positions if t == 1 else positions[:, None] + jnp.arange(t)
        got = np.asarray(jax.jit(sample_lanes)(
            logits, rng_data, index, temps, topks, topps
        ))
        lrows = np.asarray(logits).reshape(b, max(t, 1) if t > 1 else 1, v)
        idx = np.asarray(jnp.broadcast_to(index, got.shape)).reshape(b, -1)
        for i, (temp, tk, tp) in enumerate(rows):
            base = jax.random.wrap_key_data(rng_data[i])
            for j in range(lrows.shape[1]):
                key = jax.random.fold_in(base, int(idx[i, j]))
                if temp <= 0:
                    want = int(np.argmax(lrows[i, j]))
                else:
                    want = int(sample(
                        jnp.asarray(lrows[i, j]), key,
                        SamplingConfig(
                            greedy=False, temperature=temp, top_k=tk, top_p=tp
                        ),
                    ))
                assert got.reshape(b, -1)[i, j] == want, (i, j, rows[i])


def test_sample_lanes_greedy_sentinel_is_exact_argmax():
    logits = jax.random.normal(jax.random.key(12), (3, 64)) * 4
    rng_data = jnp.zeros((3, 2), jnp.uint32)
    temps = jnp.full((3,), GREEDY_TEMPERATURE, jnp.float32)
    got = sample_lanes(
        logits, rng_data, jnp.zeros((3,), jnp.int32),
        temps, jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.float32),
    )
    assert jnp.array_equal(got, jnp.argmax(logits, -1).astype(jnp.int32))


def test_lane_keys_fold_by_index():
    rng_data = jax.random.key_data(
        jax.random.split(jax.random.key(2), 2)
    ).astype(jnp.uint32)
    idx = jnp.asarray([5, 9], jnp.int32)
    keys = lane_keys(rng_data, idx)
    for i in range(2):
        want = jax.random.fold_in(
            jax.random.wrap_key_data(rng_data[i]), int(idx[i])
        )
        assert jnp.array_equal(
            jax.random.key_data(keys[i]), jax.random.key_data(want)
        )


# -- engine: greedy identity + metrics --------------------------------------


@pytest.fixture(scope="module")
def greedy_baseline(params):
    """Plain greedy host-path reference (sync ≡ async per
    tests/test_async_serving.py, so one baseline serves both cells)."""
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(3), (5, 12, 20, 9))
    want = _run(
        _paged(params, gen, PagedConfig(block_size=8, num_blocks=64)),
        prompts,
    )
    return gen, prompts, want


@pytest.mark.parametrize("loop", LOOPS)
def test_fused_greedy_identity(params, greedy_baseline, loop):
    """Greedy traffic through the fused program (sentinel params) is
    token-identical to the plain greedy engine."""
    gen, prompts, want = greedy_baseline
    paged = _paged(params, gen, _cfg(), loop=loop)
    assert _run(paged, prompts) == want
    m = paged.metrics
    assert m.sampled_steps == 0          # greedy dispatches aren't "sampled"
    assert m.host_sample_fallbacks == 0
    assert m.rng_reseeds == len(prompts)


def test_sampled_run_metrics_and_determinism(params):
    gen = GenerationConfig(max_new_tokens=8, sampling=SAMPLED)
    prompts = _prompts(np.random.default_rng(4), (5, 12, 20, 9))
    paged = _paged(params, gen, _cfg())
    out = _run(paged, prompts)
    assert all(len(o) == 8 for o in out.values())
    assert paged.metrics.sampled_steps > 0
    assert paged.metrics.host_sample_fallbacks == 0
    # same seed, fresh engine -> identical streams
    assert _run(_paged(params, gen, _cfg()), prompts) == out


def test_audit_flags_corrupted_sampling_mirrors(params):
    """Invariant 8 (serving/invariants.py): audit_engine cross-checks the
    sampling mirrors against the lane roster. A free lane knocked off the
    greedy park sentinel, an active lane whose params drift from the
    GenerationConfig install, and a perturbed rng base key must each be
    flagged; the untouched engine is clean."""
    gen = GenerationConfig(max_new_tokens=16, sampling=SAMPLED)
    paged = _paged(params, gen, _cfg())
    for p in _prompts(np.random.default_rng(6), (5, 9)):
        paged.submit(p)
    for _ in range(4):
        paged.step()
    assert audit_engine(paged) == []
    lane = next(iter(paged._active))
    free = next(iter(paged._free_lanes))
    paged._temps[free] = np.float32(0.7)  # knock the park sentinel
    paged._topks[lane] = 7                # drift an active install
    paged._rng[lane, 0] ^= np.uint32(1)   # perturb the replay key
    v = audit_engine(paged)
    assert any("not parked" in s for s in v)
    assert any("do not match" in s for s in v)
    assert any("SeedSequence base key" in s for s in v)


def test_host_sampling_counts_fallbacks(params):
    gen = GenerationConfig(max_new_tokens=6, sampling=SAMPLED)
    prompts = _prompts(np.random.default_rng(5), (5, 9))
    paged = _paged(params, gen, PagedConfig(block_size=8, num_blocks=64))
    _run(paged, prompts)
    assert paged.metrics.host_sample_fallbacks > 0
    assert paged.metrics.sampled_steps == 0


@pytest.mark.parametrize("loop", LOOPS)
def test_sampled_steady_state_zero_uploads(params, loop):
    """The GC003 twin for sampled traffic: an event-free fused sampled
    decode step uploads NOTHING — no per-step PRNG key, no sampling
    params (the host path pays a key upload every step). Same shape as
    test_steady_state_step_is_fully_resident in
    tests/test_async_serving.py, with sampling on."""
    gen = GenerationConfig(max_new_tokens=20, sampling=SAMPLED)
    paged = _paged(
        params, gen,
        _cfg(block_size=32, num_blocks=8), loop=loop,
    )
    paged.submit(_prompts(np.random.default_rng(0), (4,))[0])
    paged.step()  # admission + prefill
    paged.step()  # first decode dispatch: flushes the dirty lane
    m = paged.metrics
    for _ in range(12):
        before = m.h2d_uploads
        assert paged.step()
        assert m.h2d_uploads == before
    paged.run_to_completion()
    assert m.sampled_steps > 0 and m.host_sample_fallbacks == 0


def test_fused_sampling_tracer_labels(params):
    gen = GenerationConfig(max_new_tokens=4, sampling=SAMPLED)
    prompts = _prompts(np.random.default_rng(8), (5, 9))
    paged = _paged(
        params, gen, _cfg(trace_enabled=True, trace_buffer_steps=64)
    )
    _run(paged, prompts)
    evs = paged.tracer.chrome_events()
    dispatches = [e for e in evs if e["name"] == "dispatch"]
    assert dispatches
    assert all(e["args"]["sampling"] == "fused" for e in dispatches)


# -- engine: preempt-resume determinism --------------------------------------


@pytest.mark.parametrize("loop", LOOPS)
def test_sampled_preempt_resume_replays_stream(params, loop):
    """Pool contention preempts and resumes sampled requests; the
    fold_in-by-landing-index key discipline must replay the identical
    token streams the uncontended run produces."""
    gen = GenerationConfig(max_new_tokens=24, sampling=SAMPLED)
    prompts = _prompts(np.random.default_rng(5), (12, 12, 12, 12))
    want = _run(_paged(params, gen, _cfg(), loop=loop), prompts)
    paged = _paged(
        params, gen, _cfg(num_blocks=10, decode_reserve_blocks=1),
        loop=loop,
    )
    out = _run(paged, prompts)
    assert paged.metrics.preemptions > 0
    assert out == want


@pytest.mark.slow  # tier-1 time budget; sync/async cells run in-tier above
def test_sampled_preempt_resume_with_chunked_prefill(params):
    gen = GenerationConfig(max_new_tokens=20, sampling=SAMPLED)
    prompts = _prompts(np.random.default_rng(13), (14, 12, 11, 13))
    want = _run(_paged(params, gen, _cfg()), prompts)
    paged = _paged(
        params, gen,
        _cfg(
            num_blocks=10, decode_reserve_blocks=1, prefill_chunk_tokens=6,
        ),
    )
    out = _run(paged, prompts)
    assert paged.metrics.preemptions > 0
    assert out == want


# -- engine: sampled speculative verify --------------------------------------


def test_spec_requires_fused_for_sampled_traffic(params):
    gen = GenerationConfig(max_new_tokens=6, sampling=SAMPLED)
    with pytest.raises(ValueError, match="on_device_sampling"):
        _paged(
            params, gen,
            PagedConfig(block_size=8, num_blocks=64, spec_draft_tokens=4),
        )


def test_sampled_spec_matches_non_spec_stream(params):
    """The accept rule against SAMPLED targets preserves the target
    distribution stream exactly: spec on/off produce identical tokens
    because both draw target token i with fold_in(lane_key, i)."""
    rng = np.random.default_rng(3)
    prompts = [
        (rng.integers(0, TINY.vocab_size, size=(4,)).tolist() * 5)[:n]
        for n in (12, 18, 9, 14)
    ]
    gen = GenerationConfig(max_new_tokens=10, sampling=SAMPLED)
    want = _run(_paged(params, gen, _cfg()), prompts)
    # drafting re-tried every step: ten tokens are too few to sit out a
    # dry-spell pause on the look-ahead
    paged = _paged(params, gen, _cfg(spec_draft_tokens=4, spec_retry_steps=0))
    out = _run(paged, prompts)
    assert paged.metrics.verify_steps > 0
    assert out == want


# -- quant_mxu ---------------------------------------------------------------


def test_quant_mxu_requires_quantized_pool(params):
    gen = GenerationConfig(max_new_tokens=4)
    with pytest.raises(ValueError, match="quantized kv_cache_dtype"):
        _paged(
            params, gen,
            PagedConfig(block_size=8, num_blocks=64, quant_mxu=True),
        )


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quant_mxu_kernel_logits_within_band(params, kv_dtype):
    """decode logits with the MXU-native low-precision dot stay inside
    the 5% band of the quantized fp32-widened kernel (which itself sits
    inside the band of the fp engine — test_quantized_serving)."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_size, (2, 16)), jnp.int32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    def one(quant_mxu):
        m = LlamaDecode(
            dataclasses.replace(
                TINY_KERNEL, quant_mxu=quant_mxu
            )
        )
        cache = m.init_paged_cache(16, 8, kv_cache_dtype=kv_dtype)
        lg, cache = m.forward(
            params, cache, ids, jnp.zeros((2,), jnp.int32),
            block_tables=tables,
        )
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        lg2, _, _ = m.decode_step(
            params, cache, tok, jnp.full((2,), 16, jnp.int32), tables,
            kv_limit=32,
        )
        return lg2

    widened, mxu = one(False), one(True)
    rel = jnp.max(jnp.abs(widened - mxu)) / jnp.max(jnp.abs(widened))
    assert float(rel) < 0.05


def test_quant_mxu_engine_audit_clean_and_knob_aware(params):
    """The quant_mxu engine passes the full program audit (GC005 permits
    the int8->int32 dot under the knob) — and the SAME decode jaxpr fails
    GC005 with the knob off, proving the permitted shape is in the trace."""
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(np.random.default_rng(9), (5, 12, 9))
    paged = _paged(
        params, gen,
        _cfg(kv_cache_dtype="int8", quant_mxu=True),
        model_cfg=TINY_KERNEL,
    )
    _run(paged, prompts)  # audit_programs(paged) == [] inside _run
    # (token parity vs the widened int8 engine: test_quant_mxu_parity_cells
    # in tests/test_quantized_serving.py)
    rec = next(r for k, r in paged._programs.items() if k[0] == "pdecode")
    closed = jax.make_jaxpr(rec.fn)(*rec.example_args)
    assert check_fp32_widening(closed, "pdecode", quant_mxu=True) == []
    neg = check_fp32_widening(closed, "pdecode")
    assert any(f.rule == "GC005" and "dot_general" in f.detail for f in neg)


@pytest.mark.slow  # tier-1 time budget; statistical canary, not a parity gate
def test_quant_mxu_spec_accept_drift_canary(params):
    """Accept-rate canary: speculative greedy serving over the MXU-native
    int8 dot must not drift the accept rate more than 0.15 from the
    widened int8 kernel engine (tiny CPU measures zero drift; the band
    is the formal acceptance gate from the quant parity matrix)."""
    rng = np.random.default_rng(3)
    prompts = [
        (rng.integers(0, TINY.vocab_size, size=(4,)).tolist() * 5)[:n]
        for n in (12, 18, 9, 14)
    ]
    gen = GenerationConfig(max_new_tokens=10)

    def accept_rate(quant_mxu):
        paged = _paged(
            params, gen,
            _cfg(
                kv_cache_dtype="int8", quant_mxu=quant_mxu,
                spec_draft_tokens=4,
            ),
            model_cfg=TINY_KERNEL,
        )
        _run(paged, prompts)
        assert paged.metrics.verify_steps > 0
        return paged.metrics.accept_rate()

    assert abs(accept_rate(True) - accept_rate(False)) <= 0.15


# -- catalog / accounting ----------------------------------------------------


def test_fused_catalog_uses_lane_sentinel(params):
    gen = GenerationConfig(max_new_tokens=4)
    paged = _paged(params, gen, _cfg())
    keys = paged.catalog.keys()
    assert any(k[0] == "pdecode" and k[1] == "lane" for k in keys)
    assert "cfg=lane" in paged.catalog.describe()


def test_accounting_dims_and_analytic_costs(params):
    """from_engine captures the two new flags, and the analytic profiles
    price them: +5 lane_set elements per lane under fused sampling, the
    q·k half of the attention term discounted under quant_mxu, prefill
    untouched."""
    from neuronx_distributed_llama3_2_tpu.serving.accounting import (
        EngineDims,
        analytic_cost,
    )

    gen = GenerationConfig(max_new_tokens=4)
    mxu = EngineDims.from_engine(_paged(
        params, gen, _cfg(kv_cache_dtype="int8", quant_mxu=True),
        model_cfg=TINY_KERNEL,
    ))
    assert mxu.quant_mxu and mxu.fused_sampling
    plain = dataclasses.replace(mxu, quant_mxu=False, fused_sampling=False)
    # lane_set scatters 5 extra residents per lane when fused
    f_fused = analytic_cost(("lane_set",), mxu)[0]
    f_plain = analytic_cost(("lane_set",), plain)[0]
    assert f_fused == f_plain + mxu.max_batch * 5
    # decode discount is exactly the q·k half at int8 throughput
    key = ("pdecode", "lane", 32, False, False)
    want = plain.max_batch * plain.num_layers * plain.hidden_size * 32
    assert analytic_cost(key, plain)[0] - analytic_cost(key, mxu)[0] == want
    # prefill keys carry no discount (the fp32 prefill path is untouched)
    pkey = ("pctx", 8, "lane", False)
    assert analytic_cost(pkey, mxu)[0] == analytic_cost(pkey, plain)[0]
