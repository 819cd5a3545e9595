"""One-dispatch steady state: the fused mixed-mode step program.

The contract under test (docs/serving.md "Fused mixed-mode step"):
``PagedConfig.fused_step`` packs decode lanes, speculative-verify rows,
and active prefill-chunk suffixes into ONE ``pmixed`` query-row grid over
the shared paged KV pool — one model dispatch per engine step — and the
emitted token streams stay **byte-identical** to the unfused engine (and
therefore to the dense oracle) across the whole serving matrix:
{gather, kernel} × {drained, lookahead} × {spec, no-spec} × {chunked, whole}.

The tier-1 quartet is a pairwise-covering slice of that cube (the PR 9
matrix split); the remaining legs ride the opt-in slow tier. Alongside
parity: preemption/resume mid-fused-step, the dispatches-per-step
reduction on mixed traffic (the perf claim the knob exists for), the
graftscope row-role trace tags, and the host-sampling eligibility guard.
"""

import dataclasses

import numpy as np
import pytest

import jax

from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    NGramDrafter,
    PagedConfig,
    PagedServingEngine,
)

from tests.test_paged_serving import _dense_outputs, _prompts
from tests.test_speculative_serving import _paged, _rep_prompts, _run

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)
GEN = GenerationConfig(max_new_tokens=8)

# mixed lengths straddling chunk=6: whole-prefill shorts, chunk-walk
# longs, and a 5th prompt that queues behind max_batch=4
_PLAIN_LENS = (5, 26, 9, 7, 12)
_REP_LENS = (9, 26, 12, 7, 15)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


_DENSE = {}


def _dense(params, prompts):
    key = tuple(tuple(p) for p in prompts)
    if key not in _DENSE:
        _DENSE[key] = _dense_outputs(params, prompts, GEN)
    return _DENSE[key]


def _leg_cfg(spec, chunk, **kw):
    return PagedConfig(
        block_size=8, num_blocks=64,
        spec_draft_tokens=(3 if spec == "spec" else 0),
        prefill_chunk_tokens=(6 if chunk == "chunk" else None),
        fused_step=True, **kw,
    )


def _leg_prompts(spec):
    if spec == "spec":
        return _rep_prompts(np.random.default_rng(31), _REP_LENS)
    return _prompts(np.random.default_rng(29), _PLAIN_LENS)


_S = pytest.mark.slow
# (model, loop, spec, chunk) — the tier-1 quartet covers every value of
# every dimension and all model×{loop,spec,chunk} + loop×chunk +
# spec×chunk pairs; the full cube runs under -m slow
CUBE = [
    ("kernel", "drained", "spec", "chunk"),
    ("gather", "lookahead", "nospec", "chunk"),
    ("kernel", "lookahead", "nospec", "whole"),
    ("gather", "drained", "spec", "whole"),
    pytest.param("kernel", "drained", "nospec", "chunk", marks=_S),
    pytest.param("kernel", "drained", "spec", "whole", marks=_S),
    pytest.param("kernel", "drained", "nospec", "whole", marks=_S),
    pytest.param("kernel", "lookahead", "spec", "chunk", marks=_S),
    pytest.param("kernel", "lookahead", "spec", "whole", marks=_S),
    pytest.param("kernel", "lookahead", "nospec", "chunk", marks=_S),
    pytest.param("gather", "drained", "spec", "chunk", marks=_S),
    pytest.param("gather", "drained", "nospec", "chunk", marks=_S),
    pytest.param("gather", "drained", "nospec", "whole", marks=_S),
    pytest.param("gather", "lookahead", "spec", "chunk", marks=_S),
    pytest.param("gather", "lookahead", "spec", "whole", marks=_S),
    pytest.param("gather", "lookahead", "nospec", "whole", marks=_S),
]


@pytest.mark.parametrize(
    "model,loop,spec,chunk",
    CUBE,
    ids=[
        "-".join(c.values if hasattr(c, "values") else c) for c in CUBE
    ],
)
def test_fused_token_parity(params, model, loop, spec, chunk):
    """Every leg: the fused engine's outputs equal the dense oracle (the
    unfused paged engines are pinned to the same oracle by their own
    suites, so this is transitively fused == unfused). Teardown inside
    ``_run`` keeps the invariant auditor, the block-pool leak check, and
    the program audit (GC001-GC008, including the pmixed no-gather and
    zero-upload checks) on every leg."""
    model_cfg = TINY_KERNEL if model == "kernel" else TINY
    drafter = NGramDrafter() if spec == "spec" else None
    prompts = _leg_prompts(spec)
    paged = _paged(
        params, GEN, _leg_cfg(spec, chunk), model_cfg, drafter=drafter,
        loop=loop,
    )
    out = _run(paged, prompts)
    assert out == _dense(params, prompts)
    if chunk == "chunk":
        # chunk walks rode the one-dispatch grid, never a psfx program
        assert paged.metrics.mixed_dispatches > 0
        assert not any(
            k[0] == "psfx" for k in paged.program_registry()
        )
    if spec == "spec":
        assert paged.metrics.draft_tokens > 0


def test_fused_preempt_resume_mid_step(params):
    """An older lane's decode growth exhausts the tight pool while a
    younger request is mid-chunk-walk INSIDE the mixed grid: the victim
    is requeued, re-admits through the fused path, and final outputs
    still match dense."""
    gen = GenerationConfig(max_new_tokens=8)
    rng = np.random.default_rng(21)
    pa = rng.integers(0, TINY.vocab_size, size=(8,)).tolist()
    pb = rng.integers(0, TINY.vocab_size, size=(30,)).tolist()
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=4, num_blocks=12, decode_reserve_blocks=1,
            prefill_chunk_tokens=4, fused_step=True,
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
    out = _run(paged, [])
    assert (rb, True) in preempted, preempted
    assert paged.request_info(rb)["preemptions"] >= 1
    assert paged.metrics.mixed_dispatches > 0
    assert out == _dense_outputs(params, [pa, pb], gen)
    del ra


def _staggered(params, fused):
    """Mixed-traffic soak: long prompts arriving while earlier lanes are
    decoding, so unfused steps pay a psfx dispatch AND a decode dispatch
    while fused steps pay one pmixed."""
    paged = _paged(
        params, GEN,
        PagedConfig(
            block_size=8, num_blocks=64, prefill_chunk_tokens=6,
            fused_step=fused, trace_enabled=fused, trace_buffer_steps=128,
        ),
        TINY_KERNEL,
    )
    prompts = _prompts(np.random.default_rng(9), (21, 25, 18, 23))
    paged.submit(prompts[0])
    for p in prompts[1:]:
        paged.step()
        paged.step()
        paged.submit(p)
    out = _run(paged, [])
    return paged, out


def test_fused_reduces_dispatches_per_step_on_mixed_traffic(params):
    """The perf claim: on overlapped prefill+decode traffic the fused
    engine's model-dispatch-per-step ratio drops strictly below the
    unfused engine's (whose prefill chunks and decode are separate
    dispatches), while tokens stay identical. Also pins the
    ``dispatches_per_step`` snapshot gauge and the graftscope row-role
    tags (decode/verify/prefill row counts per fused dispatch)."""
    fused, out_f = _staggered(params, fused=True)
    unfused, out_u = _staggered(params, fused=False)
    assert out_f == out_u
    snap_f = fused.metrics.snapshot(fused.allocator, fused.index)
    snap_u = unfused.metrics.snapshot(unfused.allocator, unfused.index)
    assert snap_f["dispatches_per_step"] == pytest.approx(
        fused.metrics.compute_dispatches
        / max(fused.metrics.engine_steps, 1),
        abs=1e-4,
    )
    assert snap_f["dispatches_per_step"] < snap_u["dispatches_per_step"]
    assert fused.metrics.mixed_dispatches > 0
    assert unfused.metrics.mixed_dispatches == 0
    # every fused dispatch slice names how many rows each role packed
    mixed = [
        e for e in fused.tracer.chrome_events()
        if e["name"] == "dispatch" and e["args"].get("mode") == "mixed"
    ]
    assert mixed
    for e in mixed:
        a = e["args"]
        assert a["prefill_rows"] > 0  # abstention never dispatches pmixed
        assert a["decode_rows"] >= 0 and a["verify_rows"] >= 0
        assert (
            a["lanes"]
            == a["prefill_rows"] + a["decode_rows"] + a["verify_rows"]
        )
        assert a["prefill_tokens"] > 0
    # at least one fused step packed prefill rows WITH live decode lanes
    assert any(
        e["args"]["decode_rows"] + e["args"]["verify_rows"] > 0
        for e in mixed
    )


def test_fused_rejects_host_sampling(params):
    """Eligibility guard: fused_step needs per-lane device sampling for
    non-greedy configs (host sampling would re-upload every step); the
    constructor must refuse loudly rather than silently degrade."""
    gen = GenerationConfig(
        max_new_tokens=4,
        sampling=dataclasses.replace(GEN.sampling, greedy=False,
                                     temperature=0.7),
    )
    eng = InferenceEngine(
        TINY, params, max_batch=2, max_seq_len=32, buckets=[8]
    )
    with pytest.raises(ValueError, match="fused_step"):
        PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=8, num_blocks=16, prefill_chunk_tokens=4,
                fused_step=True,
            ),
        )
    # same config with on-device sampling is legal
    PagedServingEngine(
        eng, gen,
        PagedConfig(
            block_size=8, num_blocks=16, prefill_chunk_tokens=4,
            fused_step=True, on_device_sampling=True,
        ),
    )
