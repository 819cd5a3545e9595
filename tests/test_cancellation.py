"""Cancellation parity: a client cancel never perturbs survivors.

The contract (serving/engine.py ``cancel`` docstring): cancellation
routes through the existing failure domain — drain the in-flight
lookahead, then ``_fail_request`` (blocks released, lane freed, FINISH
emitted, terminal timing stamped). Survivors' resident state is
untouched, so their token streams must be **identical** to an
uncancelled run of the same workload (greedy recompute determinism, the
same exactness the preemption and fault-tolerance suites pin).

Matrix: the victim is cancelled while queued, mid-chunked-prefill,
mid-decode, and mid-verify (speculative engine, between verify steps).
Every leg tears down with the invariant auditor, the block-pool leak
check, and the GC010 action-trace automaton clean.
"""

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import audit_programs
from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
    check_action_trace,
)
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
    audit_engine,
)

from tests.test_paged_serving import _prompts
from tests.test_speculative_serving import _rep_prompts

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _paged(params, gen, paged_cfg, drafter=None, max_batch=4):
    eng = InferenceEngine(
        TINY, params, max_batch=max_batch, max_seq_len=64,
        buckets=[8, 16, 32],
    )
    return PagedServingEngine(eng, gen, paged_cfg, drafter=drafter)


def _teardown_clean(paged):
    assert paged._pending is None
    assert paged.allocator.active_blocks == 0
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []
    assert check_action_trace(paged) == []


def _run_with_cancel(make_engine, prompts, victim, should_cancel):
    """Submit everything, step until ``should_cancel(info)`` holds for the
    victim, cancel between steps, run to completion. Returns (engine,
    victim tokens at cancel)."""
    paged = make_engine()
    for p in prompts:
        paged.submit(p)
    cancelled_at = None
    alive = True
    while alive:
        alive = paged.step()
        info = paged.request_info(victim)
        if cancelled_at is None and not info["done"] and should_cancel(info):
            assert paged.cancel(victim) is True
            cancelled_at = list(paged.request_tokens(victim))
    assert cancelled_at is not None, "cancel predicate never fired"
    return paged, cancelled_at


def _check_parity(paged, baseline, victim, cancelled_at):
    """Survivors token-identical to the uncancelled run; the victim is a
    terminal failed record whose stream froze at the cancel point."""
    for rid, toks in baseline.items():
        if rid == victim:
            continue
        assert paged.request_tokens(rid) == toks, f"survivor {rid} diverged"
        assert paged.request_info(rid)["status"] == "finished"
    info = paged.request_info(victim)
    assert info["status"] == "failed"
    assert info["error"] == "cancelled by client"
    assert paged.request_tokens(victim) == cancelled_at
    assert paged.metrics.cancelled_requests == 1
    assert paged.metrics.failed_requests == 1
    assert paged.metrics.requests_by_class["batch"]["failed"] == 1
    _teardown_clean(paged)


def test_cancel_while_queued(params):
    """Cancel before admission: the victim never touches a lane or a
    block; the others run exactly as if it were never submitted."""
    gen = GenerationConfig(max_new_tokens=6)
    cfg = dict(block_size=8, num_blocks=64)
    prompts = _prompts(np.random.default_rng(21), (5, 9, 12, 7, 6))
    victim = 4  # max_batch=4: rid 4 waits in the queue behind the wave

    solo = _paged(params, gen, PagedConfig(**cfg))
    for p in prompts:
        solo.submit(p)
    baseline = solo.run_to_completion()

    paged = _paged(params, gen, PagedConfig(**cfg))
    for p in prompts:
        paged.submit(p)
    assert paged.cancel(victim) is True  # still queued, pre-step
    assert paged.metrics.queued_requests == len(prompts) - 1
    paged.run_to_completion()
    _check_parity(paged, baseline, victim, cancelled_at=[])
    assert paged.request_info(victim)["generated_tokens"] == 0


# shared by the mid-prefill and mid-decode legs (and their single
# uncancelled baseline run): the victim gets the longest prompt so its
# chunk walk spans steps
_CHUNK_GEN = GenerationConfig(max_new_tokens=8)
_CHUNK_CFG = dict(
    block_size=8, num_blocks=64, prefill_chunk_tokens=6,
)
_CHUNK_PROMPTS = _prompts(np.random.default_rng(23), (5, 26, 9, 7))


@pytest.fixture(scope="module")
def chunk_baseline(params):
    solo = _paged(params, _CHUNK_GEN, PagedConfig(**_CHUNK_CFG))
    for p in _CHUNK_PROMPTS:
        solo.submit(p)
    return solo.run_to_completion()


@pytest.mark.parametrize(
    "when",
    ["mid_prefill", "mid_decode"],
)
def test_cancel_mid_prefill_and_mid_decode(params, chunk_baseline, when):
    """Cancel during the victim's chunk walk (prefilling, no tokens yet)
    and mid-decode (some tokens committed): survivors byte-identical,
    victim's stream frozen at the cancel point."""
    gen, cfg, prompts = _CHUNK_GEN, _CHUNK_CFG, _CHUNK_PROMPTS
    victim = 1
    baseline = chunk_baseline

    if when == "mid_prefill":
        pred = lambda info: info["prefilling"]  # noqa: E731
    else:
        pred = lambda info: info["generated_tokens"] >= 2  # noqa: E731
    paged, cancelled_at = _run_with_cancel(
        lambda: _paged(params, gen, PagedConfig(**cfg)),
        prompts, victim, pred,
    )
    if when == "mid_prefill":
        assert cancelled_at == []  # no token ever committed
    else:
        assert 2 <= len(cancelled_at) < len(baseline[victim])
        assert cancelled_at == baseline[victim][: len(cancelled_at)]
    _check_parity(paged, baseline, victim, cancelled_at)


@pytest.mark.parametrize(
    "when",
    ["mid_prefill", "mid_decode"],
)
def test_cancel_mid_fused_step(params, chunk_baseline, when):
    """fused_step legs of the chunk matrix: the victim's chunk walk rides
    the one-dispatch pmixed grid, the cancel lands between fused
    dispatches, and the survivors must stay byte-identical to the
    UNFUSED uncancelled baseline — cancellation parity and fused-step
    token parity pinned by the same assertion."""
    gen, prompts = _CHUNK_GEN, _CHUNK_PROMPTS
    cfg = dict(_CHUNK_CFG, fused_step=True)
    victim = 1
    baseline = chunk_baseline

    if when == "mid_prefill":
        pred = lambda info: info["prefilling"]  # noqa: E731
    else:
        pred = lambda info: info["generated_tokens"] >= 2  # noqa: E731
    paged, cancelled_at = _run_with_cancel(
        lambda: _paged(params, gen, PagedConfig(**cfg)),
        prompts, victim, pred,
    )
    assert paged.metrics.mixed_dispatches > 0
    if when == "mid_prefill":
        assert cancelled_at == []  # no token ever committed
    else:
        assert 2 <= len(cancelled_at) < len(baseline[victim])
        assert cancelled_at == baseline[victim][: len(cancelled_at)]
    _check_parity(paged, baseline, victim, cancelled_at)


def test_cancel_mid_verify_speculative(params):
    """Speculative engine: cancel between verify steps while the victim
    has accepted drafted tokens. The drain-then-fail path must unwind the
    in-flight lookahead without touching the survivors' accept streams."""
    gen = GenerationConfig(max_new_tokens=12)
    cfg = dict(
        block_size=8, num_blocks=64,
        spec_draft_tokens=3,
    )
    drafter = NGramDrafter()
    prompts = _rep_prompts(np.random.default_rng(17), (12, 15, 9))
    victim = 1

    solo = _paged(params, gen, PagedConfig(**cfg), drafter=NGramDrafter())
    for p in prompts:
        solo.submit(p)
    baseline = solo.run_to_completion()

    def pred(info):
        # at least one verify step has run and the victim holds tokens —
        # the cancel lands between verify dispatches
        return (
            paged_ref[0].metrics.verify_steps >= 2
            and info["generated_tokens"] >= 1
        )

    paged_ref = []

    def make():
        eng = _paged(params, gen, PagedConfig(**cfg), drafter=drafter)
        paged_ref.append(eng)
        return eng

    paged, cancelled_at = _run_with_cancel(make, prompts, victim, pred)
    assert paged.metrics.verify_steps >= 2
    assert cancelled_at == baseline[victim][: len(cancelled_at)]
    assert len(cancelled_at) < len(baseline[victim])
    _check_parity(paged, baseline, victim, cancelled_at)
