"""The look-ahead step loop: parity, steady-state residency, soak, and the
rules that drain it.

The contract under test (docs/serving.md "How the engine steps"): wherever
the scheduler has nothing to do, the engine dispatches decode step N+1 from
device-resident state before reading step N's tokens back, and must be

- token-identical for greedy sampling to the *drained* sequence run on every
  step (``tests/drained_policy.py``, the plain reference, passed through
  ``policy=``) and to the dense engine, across the whole matrix (gather path,
  Pallas kernel path, chunked prefill on/off, preempt-resume, speculative),
- genuinely resident: a steady-state step performs zero host→device uploads
  of tokens/positions/tables and its readback lags dispatch by exactly one
  step (the ``h2d_uploads`` / ``_last_readback_lag`` choke-point counters),
- drained by scheduler events only: a lane to admit into, a lane mid-prefill,
  a finish the host can count, a pool that needs a preemption, drafting, the
  ladder's rung 2 — each booked under its ``lookahead_declined_*`` counter; a
  waiting queue with no free lane is none of them.
"""

import dataclasses

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import audit_programs
from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
    check_action_trace,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
    audit_engine,
)

from tests.drained_policy import LOOPS, loop_policy
from tests.test_paged_serving import _dense_outputs, _prompts

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _paged(params, gen, paged_cfg, model_cfg=TINY, loop="lookahead", **engine_kw):
    """``loop`` is one of ``LOOPS``: the engine's own step loop, or the
    drained reference passed through ``policy=``."""
    engine_kw.setdefault("max_batch", 4)
    engine_kw.setdefault("max_seq_len", 64)
    engine_kw.setdefault("buckets", [8, 16, 32])
    eng = InferenceEngine(model_cfg, params, **engine_kw)
    return PagedServingEngine(eng, gen, paged_cfg, policy=loop_policy(loop))


def _run(paged, prompts):
    for p in prompts:
        paged.submit(p)
    out = paged.run_to_completion()
    # drained pipeline + clean pool, whatever the path taken
    assert paged._pending is None
    assert paged.allocator.active_blocks == 0
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []
    # GC010: the recorded step-action trace must replay through the
    # schedule legality automaton (analysis/graftsched.py)
    assert check_action_trace(paged) == []
    return out


@pytest.mark.parametrize("loop", LOOPS)
@pytest.mark.parametrize("model_cfg", [TINY, TINY_KERNEL], ids=["gather", "kernel"])
@pytest.mark.parametrize("chunk", [None, 6], ids=["whole", "chunked"])
def test_loop_parity_matrix(params, model_cfg, chunk, loop):
    """Greedy outputs identical to the dense engine on both loops, with and
    without the Pallas decode kernel and chunked prefill."""
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(3), (5, 28, 20, 9, 17, 3))
    cfg = PagedConfig(block_size=8, num_blocks=64, prefill_chunk_tokens=chunk)
    paged = _paged(params, gen, cfg, model_cfg, loop=loop)
    assert _run(paged, prompts) == _dense_outputs(params, prompts, gen)
    m = paged.metrics
    # every output ends by count, which the host foresees: no lane is held
    # a step past its last token
    assert m.lame_duck_tokens == 0
    if loop == "lookahead":
        assert m.decode_steps_async > 0
        assert m.lookahead_declined_finish > 0
    else:
        assert m.decode_steps_async == 0


@pytest.mark.parametrize("loop", LOOPS)
def test_loop_parity_under_preemption(params, loop):
    """Pool exhaustion mid-decode: the look-ahead must drop to the drained
    sequence for the preempting step (``lookahead_declined_pool`` counts
    it) and still match the uncontended dense run (greedy recompute
    determinism)."""
    gen = GenerationConfig(max_new_tokens=36)
    prompts = _prompts(np.random.default_rng(11), (12, 10, 14, 9))
    cfg = PagedConfig(block_size=8, num_blocks=10, decode_reserve_blocks=1)
    paged = _paged(params, gen, cfg, loop=loop)
    assert _run(paged, prompts) == _dense_outputs(params, prompts, gen)
    assert paged.metrics.preemptions > 0
    if loop == "lookahead":
        assert paged.metrics.lookahead_declined_pool > 0


@pytest.mark.parametrize("loop", LOOPS)
def test_steady_state_step_is_fully_resident(params, loop):
    """Acceptance check: once in steady state (no admissions, no block
    growth — block_size 32 means a short decode never crosses a boundary),
    a step does ZERO host→device uploads and ZERO resident-state programs
    on either loop — the decode program feeds tokens/positions back on
    device — and the look-ahead's token readback lags dispatch by exactly
    one step where the drained sequence reads in the same step."""
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen, PagedConfig(block_size=32, num_blocks=8), loop=loop
    )
    paged.submit(_prompts(np.random.default_rng(0), (4,))[0])
    paged.step()  # admission + prefill (uploads, dirty-lane flush queued)
    paged.step()  # first dispatch: flushes the dirty lane
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas)
        assert paged.step()
        assert (m.h2d_uploads, m.lane_syncs, m.table_deltas) == before
        assert paged._last_readback_lag == (1 if loop == "lookahead" else 0)
        assert m.device_wait_ms >= 0.0
    paged.run_to_completion()


def _drive(paged, prompts, arrivals, limit=3000):
    """Submit ``prompts[i]`` once the engine has taken ``arrivals[i]`` steps
    and step until everything drained; returns per step the rids that were
    admitted and the rids that finished in it."""
    admitted, finished, seen_a, seen_f = [], [], set(), set()
    steps, nxt, alive = 0, 0, True
    while alive or nxt < len(prompts):
        while nxt < len(prompts) and arrivals[nxt] <= steps:
            paged.submit(prompts[nxt])
            nxt += 1
        alive = paged.step()
        steps += 1
        now_a = {r.rid for r in paged._active.values()} | set(paged._finished)
        admitted.append(sorted(now_a - seen_a))
        finished.append(sorted(set(paged._finished) - seen_f))
        seen_a |= now_a
        seen_f |= set(paged._finished)
        assert steps < limit, "did not converge"
    assert paged._pending is None
    assert paged.allocator.active_blocks == 0
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []
    assert check_action_trace(paged) == []
    return admitted, finished


# tier-1 budget: schedule-invariance now has an in-tier model checker —
# tests/test_graftsched.py runs seeded schedule permutations with
# per-action invariant audits and stream-identity; this longer soak
# rides the slow tier
@pytest.mark.slow
def test_soak_randomized_schedule_token_identical(params):
    """Seeded soak: a randomized arrival schedule (mixed prompt lengths,
    chunked prefill, a pool tight enough to preempt) driven step-by-step
    into the drained reference and the look-ahead engine independently for
    200+ steps. Outputs must be token-identical and the block pool must
    drain to zero."""
    rng = np.random.default_rng(1234)
    gen = GenerationConfig(max_new_tokens=14)
    n_requests = 26
    prompts = _prompts(rng, rng.integers(3, 40, size=n_requests))
    arrivals = np.sort(rng.integers(0, 190, size=n_requests)).tolist()

    def drive(loop):
        # the look-ahead leg runs prewarmed: the whole catalog compiles
        # before traffic and the soak must then compile NOTHING (GC008)
        paged = _paged(
            params, gen,
            PagedConfig(
                block_size=4, num_blocks=24, decode_reserve_blocks=1,
                prefill_chunk_tokens=8, prewarm=loop == "lookahead",
            ),
            loop=loop, max_seq_len=64, buckets=[8, 16, 32],
        )
        admitted, _ = _drive(paged, prompts, arrivals)
        assert paged.metrics.finished == n_requests
        out = {r: req.out for r, req in paged._finished.items()}
        return out, len(admitted), paged.metrics

    out_ref, steps_ref, _ = drive("drained")
    out, steps, m = drive("lookahead")
    assert out == out_ref
    assert steps_ref >= 200 and steps >= 200
    assert m.decode_steps_async > 0
    assert m.preemptions > 0  # the schedule actually exercised preemption
    assert m.prefill_chunks > 0  # ... and chunked prefill
    # prewarmed leg: 200+ heterogeneous steps hit only prewarmed programs
    assert m.prewarm_compiles > 0
    assert m.steadystate_compiles == 0


@pytest.mark.parametrize(
    "model_cfg",
    # tier-1 time budget: the spec soak runs the kernel path by default;
    # the gather-fallback soak rides the slow tier (the parity matrix above
    # still exercises gather in-tier)
    [pytest.param(TINY, marks=pytest.mark.slow), TINY_KERNEL],
    ids=["gather", "kernel"],
)
@pytest.mark.parametrize(
    "chunk",
    # tier-1 budget: chunked is the stricter prefill path; the whole-
    # prefill spec soak rides the slow tier
    [pytest.param(None, marks=pytest.mark.slow), 8],
    ids=["whole", "chunked"],
)
def test_soak_spec_randomized_schedule(params, model_cfg, chunk):
    """Speculative variant of the soak: the same randomized arrival driving
    with the n-gram drafter on (tight pool), across gather/kernel ×
    whole/chunked prefill. Greedy recompute determinism makes the
    uncontended dense run the reference — whatever interleaving of verify
    steps, dry-spell plain steps, and preempt-resumes the schedule causes,
    the outputs must be token-identical and the pool must drain."""
    rng = np.random.default_rng(99)
    gen = GenerationConfig(max_new_tokens=14)
    cfg = dict(
        block_size=4, num_blocks=24, decode_reserve_blocks=1,
        prefill_chunk_tokens=chunk, spec_draft_tokens=4,
    )
    n_requests = 14
    lengths = rng.integers(3, 32, size=n_requests)
    # repetitive/free-text mix: even lanes draft well, odd lanes abstain
    free = iter(_prompts(rng, lengths))
    prompts = []
    for i, n in enumerate(lengths):
        plain = next(free)
        if i % 2 == 0:
            pat = rng.integers(1, 9, size=3).tolist()
            prompts.append((pat * (int(n) // 3 + 1))[: int(n)])
        else:
            prompts.append(plain)
    arrivals = np.sort(rng.integers(0, 80, size=n_requests)).tolist()

    paged = _paged(
        params, gen, PagedConfig(**cfg), model_cfg,
        max_seq_len=64, buckets=[8, 16, 32],
    )
    _drive(paged, prompts, arrivals)
    assert paged.metrics.finished == n_requests
    out = {r: paged._finished[r].out for r in sorted(paged._finished)}
    assert out == _dense_outputs(params, prompts, gen)
    m = paged.metrics
    assert m.verify_steps > 0
    assert m.accepted_tokens > 0
    assert m.preemptions > 0  # the schedule actually exercised preemption
    assert m.lookahead_declined_spec > 0


DECLINED = ("spec", "ladder", "admit", "prefill", "finish", "pool")


def test_lookahead_metrics_in_snapshot(params):
    gen = GenerationConfig(max_new_tokens=6)
    paged = _paged(params, gen, PagedConfig(block_size=8, num_blocks=32))
    _run(paged, _prompts(np.random.default_rng(2), (5, 9)))
    snap = paged.metrics.snapshot(paged.allocator, paged.index)
    for key in (
        "decode_steps_async", "lame_duck_tokens",
        *("lookahead_declined_" + r for r in DECLINED),
        "lane_syncs", "table_deltas", "h2d_uploads",
        "host_schedule_ms", "device_wait_ms",
        "host_schedule_ms_per_step", "device_wait_ms_per_step",
    ):
        assert key in snap, key
    assert snap["decode_steps_async"] > 0
    assert snap["host_schedule_ms"] >= 0.0


# -- what drains the look-ahead, and what does not ---------------------------


def test_waiting_queue_with_no_free_lane_keeps_the_lookahead(params):
    """More requests than lanes: the queue is never empty while the first
    wave decodes, and the look-ahead runs all the same — a queue the
    admission wave cannot shorten is no scheduler event. A lane freed by a
    finish the host can count is handed to the queue's head in the very
    step the drained reference does it: admissions fall on the same steps,
    step for step."""
    gen = GenerationConfig(max_new_tokens=9)
    rng = np.random.default_rng(21)
    prompts = _prompts(rng, (6, 11, 4, 9, 13, 5, 8))
    arrivals = [0, 0, 0, 2, 2, 5, 11]
    cfg = PagedConfig(block_size=8, num_blocks=64)

    ref = _paged(params, gen, cfg, loop="drained", max_batch=2)
    want = _drive(ref, prompts, arrivals)
    assert ref.metrics.decode_steps_async == 0

    paged = _paged(params, gen, cfg, max_batch=2)
    ahead_while_queued = 0
    step = paged.step

    def counting_step():
        nonlocal ahead_while_queued
        queued = bool(paged._queue) and not paged._free_lanes
        before = paged.metrics.decode_steps_async
        alive = step()
        ahead_while_queued += queued and (
            paged.metrics.decode_steps_async > before
        )
        return alive

    paged.step = counting_step
    got = _drive(paged, prompts, arrivals)
    # admissions: the same step. Finishes: the reference reads a lane's last
    # token in the step that dispatches it, the look-ahead at the head of
    # the next — the step that re-admits into the lane on both loops
    assert [a for a in got[0] if a] == [a for a in want[0] if a]
    assert got[0][: len(want[0])] == want[0]
    when = lambda fin: {r: i for i, rids in enumerate(fin) for r in rids}
    ref_at, at = when(want[1]), when(got[1])
    assert all(at[r] - ref_at[r] in (0, 1) for r in ref_at)
    assert len(got[0]) - len(want[0]) in (0, 1)
    assert {r: q.out for r, q in paged._finished.items()} == {
        r: q.out for r, q in ref._finished.items()
    }
    assert ahead_while_queued >= 10
    m = paged.metrics
    assert m.lame_duck_tokens == 0
    assert m.lookahead_declined_finish > 0 and m.lookahead_declined_admit > 0


def test_eos_costs_one_lame_duck_token_and_a_count_none(params):
    """Only EOS is learnt a step late: the program dispatched past it is the
    lane's lame-duck step, its token discarded. A finish by count is
    foreseen and costs none."""
    # the tiny model mostly repeats one token; this prompt's greedy stream
    # changes token mid-decode, which gives an EOS to end on
    prompt = _prompts(np.random.default_rng(4), (7,))[0]
    cfg = PagedConfig(block_size=8, num_blocks=32)
    by_count = _paged(params, GenerationConfig(max_new_tokens=12), cfg)
    out = _run(by_count, [prompt])[0]
    assert by_count.metrics.lame_duck_tokens == 0
    # a token whose first occurrence is well inside the decode phase
    at = next(i for i in range(3, 11) if out[i] not in out[:i])
    gen = GenerationConfig(max_new_tokens=12, eos_token_id=out[at])
    ref = _run(_paged(params, gen, cfg, loop="drained"), [prompt])[0]
    assert ref == out[: at + 1]
    paged = _paged(params, gen, cfg)
    assert _run(paged, [prompt])[0] == ref
    assert paged.metrics.lame_duck_tokens == 1


def test_a_chunked_prompt_costs_one_token_readback(params):
    """A non-final chunk's sampled token is never read back: four chunks,
    one blocking readback — the final chunk's, the request's first token."""
    gen = GenerationConfig(max_new_tokens=4)
    prompt = _prompts(np.random.default_rng(9), (30,))[0]
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=32, prefill_chunk_tokens=8),
    )
    reads_before_decode = 0
    read = paged._read_tokens

    def counting_read(toks):
        nonlocal reads_before_decode
        reads_before_decode += paged.metrics.decode_steps == 0
        return read(toks)

    paged._read_tokens = counting_read
    assert _run(paged, [prompt]) == _dense_outputs(params, [prompt], gen)
    assert paged.metrics.prefill_chunks == 4
    assert reads_before_decode == 1


def _declined_scenario(params, reason):
    """An engine driven through a schedule built to make ``reason`` hold a
    decode step back."""
    rng = np.random.default_rng(31)
    gen = GenerationConfig(max_new_tokens=10)
    cfg = dict(block_size=8, num_blocks=64)
    arrivals = [0, 3]
    prompts = _prompts(rng, (6, 9))
    if reason == "prefill":
        cfg["prefill_chunk_tokens"] = 8
        prompts = _prompts(rng, (6, 30))
    elif reason == "pool":
        gen = GenerationConfig(max_new_tokens=36)
        cfg.update(num_blocks=10, decode_reserve_blocks=1)
        prompts, arrivals = _prompts(rng, (12, 10, 14, 9)), [0, 0, 0, 0]
    elif reason == "spec":
        cfg["spec_draft_tokens"] = 4
        prompts = [[3, 5, 7] * 4, [2, 4, 6] * 3]
    paged = _paged(params, gen, PagedConfig(**cfg))
    if reason == "ladder":
        paged._degrade_level = 2  # the ladder itself is off: the rung stays
    _drive(paged, prompts, arrivals)
    return paged.metrics


@pytest.mark.parametrize("reason", DECLINED)
def test_each_declined_reason_fires(params, reason):
    """Why a decode step was not dispatched ahead is booked under the rule
    that drained it, so the next writer can see which rule to relax."""
    m = _declined_scenario(params, reason)
    assert getattr(m, "lookahead_declined_" + reason) > 0
    if reason == "ladder":
        # rung 2 sheds the look-ahead altogether, whatever else holds
        assert m.decode_steps_async == 0
        assert m.lookahead_declined_ladder == m.decode_steps
    else:
        assert m.lookahead_declined_ladder == 0
        declined = sum(getattr(m, "lookahead_declined_" + r) for r in DECLINED)
        # every decode step went ahead or names the rule that held it
        assert m.decode_steps_async + declined >= m.decode_steps
