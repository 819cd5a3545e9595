"""Speculative decoding on the paged serving engine.

The contract under test (docs/serving.md "Speculative decoding"):

- the accept/reject rule is a pure shared function
  (:func:`..inference.speculative.accept_rule`) whose greedy output is
  provably identical to plain greedy decoding, whatever the drafts;
- the engine's verify step is token-identical to the non-speculative loop
  across the whole matrix (gather/kernel × chunked/whole prefill ×
  sync/async), including under preemption, and drains the block pool;
- the verify-step program reads the KV pool gather-free when the kernel
  is enabled (jaxpr walk), and the PR 4 steady-state residency property
  survives speculation — a verify step's only extra host→device traffic
  is the draft upload itself.
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
from neuronx_distributed_llama3_2_tpu.inference.speculative import accept_rule
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

from tests.drained_policy import LOOPS, loop_policy
from tests.test_paged_serving import _dense_outputs, _prompts

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _rep_prompts(rng, lengths, period=3):
    """Repetitive prompts (short repeated n-gram pattern) so the
    prompt-lookup drafter actually proposes."""
    out = []
    for n in lengths:
        pat = rng.integers(1, 9, size=period).tolist()
        out.append((pat * (n // period + 1))[:n])
    return out


def _paged(params, gen, paged_cfg, model_cfg=TINY, drafter=None, loop="lookahead"):
    """``loop`` is one of ``tests.drained_policy.LOOPS``."""
    eng = InferenceEngine(
        model_cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16, 32]
    )
    return PagedServingEngine(
        eng, gen, paged_cfg, drafter=drafter, policy=loop_policy(loop)
    )


def _run(paged, prompts):
    for p in prompts:
        paged.submit(p)
    out = paged.run_to_completion()
    assert paged._pending is None
    assert paged.allocator.active_blocks == 0
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []
    return out


# ---------------------------------------------------------------------------
# accept_rule: the shared pure accept/reject function
# ---------------------------------------------------------------------------


def _accept_ref(drafts, greedy, draft_len):
    """The rule as the obvious per-row python loop (the form previously
    inlined in SpeculativeDecoder.generate)."""
    a = 0
    while a < draft_len and drafts[a] == greedy[a]:
        a += 1
    return a, list(drafts[:a]) + [greedy[a]]


def test_accept_rule_greedy_parity():
    """Direct unit test: for random drafts/targets the batched rule equals
    the sequential greedy accept loop row by row — emitted[:accept+1] is
    the accepted prefix plus the target's correction/bonus token."""
    rng = np.random.default_rng(0)
    k = 4
    drafts = rng.integers(0, 5, size=(64, k)).astype(np.int32)
    greedy = rng.integers(0, 5, size=(64, k + 1)).astype(np.int32)
    dlen = rng.integers(0, k + 1, size=(64,)).astype(np.int32)
    accept, emitted = accept_rule(drafts, greedy, draft_len=dlen)
    accept, emitted = np.asarray(accept), np.asarray(emitted)
    for i in range(64):
        a_ref, em_ref = _accept_ref(
            drafts[i].tolist(), greedy[i].tolist(), int(dlen[i])
        )
        assert accept[i] == a_ref
        assert emitted[i, : a_ref + 1].tolist() == em_ref
    # no cap: full-k acceptance reachable
    accept2, emitted2 = accept_rule(drafts, drafts_to_greedy := np.concatenate(
        [drafts, greedy[:, -1:]], axis=1
    ))
    assert (np.asarray(accept2) == k).all()
    assert (np.asarray(emitted2) == drafts_to_greedy).all()


def test_accept_rule_is_traceable():
    """The engine traces the rule inside the jitted verify program — it
    must stay functional under jit with no host round trips."""
    fn = jax.jit(lambda d, g, n: accept_rule(d, g, draft_len=n))
    a, e = fn(
        jnp.asarray([[7, 8, 9]], jnp.int32),
        jnp.asarray([[7, 8, 1, 2]], jnp.int32),
        jnp.asarray([2], jnp.int32),
    )
    assert int(a[0]) == 2  # third match blocked by draft_len
    assert np.asarray(e)[0, :3].tolist() == [7, 8, 1]


# ---------------------------------------------------------------------------
# NGramDrafter: prompt-lookup proposals
# ---------------------------------------------------------------------------


def test_ngram_drafter_proposes_continuation():
    d = NGramDrafter(max_n=3, min_n=1)
    # last 3-gram (4,5,6) occurred earlier, followed by 7, 8
    assert d.propose([1, 4, 5, 6, 7, 8, 2, 4, 5, 6], 2) == [7, 8]
    # longest n wins: the 1-gram match (…,3,9) would propose 9, but the
    # 2-gram (2,3)->4 is the stronger signal
    assert d.propose([2, 3, 4, 1, 3, 9, 2, 3], 1) == [4]


def test_ngram_drafter_abstains():
    d = NGramDrafter(max_n=3, min_n=2)
    assert d.propose([1, 2, 3, 4, 5], 4) == []  # no repeated 2/3-gram
    assert d.propose([1, 2], 4) == []           # history too short
    assert d.propose([1, 2, 1, 2], 0) == []     # no budget


def test_ngram_drafter_latest_occurrence_wins():
    d = NGramDrafter(max_n=2, min_n=2)
    # (1,2) occurs twice; the LATER one (followed by 9) is the prediction
    assert d.propose([1, 2, 5, 1, 2, 9, 3, 1, 2], 1) == [9]


# ---------------------------------------------------------------------------
# engine: greedy parity across the matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model_cfg", [TINY, TINY_KERNEL], ids=["gather", "kernel"])
@pytest.mark.parametrize("chunk", [None, 6], ids=["whole", "chunked"])
def test_spec_parity_matrix(params, model_cfg, chunk):
    """Speculative greedy serving == dense engine, with/without the paged
    kernel and chunked prefill — and speculation must actually fire."""
    gen = GenerationConfig(max_new_tokens=10)
    prompts = _rep_prompts(np.random.default_rng(3), (12, 22, 9, 17))
    cfg = dict(block_size=8, num_blocks=64, prefill_chunk_tokens=chunk)
    want = _dense_outputs(params, prompts, gen)
    paged = _paged(
        params, gen, PagedConfig(**cfg, spec_draft_tokens=4), model_cfg
    )
    out = _run(paged, prompts)
    assert out == want
    m = paged.metrics
    assert m.verify_steps > 0
    assert m.accepted_tokens > 0
    assert 0.0 < m.accept_rate() <= 1.0


@pytest.mark.parametrize("loop", LOOPS)
def test_spec_parity_on_both_loops(params, loop):
    """A drafting step is a drained one; dry stretches hand the loop back
    to the look-ahead (or, on the drained reference, retry every step) —
    output must stay identical to the plain drained sequence."""
    gen = GenerationConfig(max_new_tokens=12)
    rng = np.random.default_rng(5)
    # mixed traffic: two repetitive prompts (draft well), two random ones
    prompts = _rep_prompts(rng, (12, 18)) + _prompts(rng, (9, 14))
    cfg = dict(block_size=8, num_blocks=64)
    want = _run(_paged(params, gen, PagedConfig(**cfg), loop="drained"), prompts)
    paged = _paged(
        params, gen, PagedConfig(**cfg, spec_draft_tokens=4), loop=loop,
    )
    out = _run(paged, prompts)
    assert out == want
    assert paged.metrics.verify_steps > 0


def test_spec_parity_under_preemption(params):
    """Pool exhaustion while speculating: spec-row backing never preempts
    (drafts trim instead), base-row backing still does — outputs must
    match the uncontended dense run exactly."""
    gen = GenerationConfig(max_new_tokens=36)
    prompts = _rep_prompts(np.random.default_rng(11), (12, 10, 14, 9))
    cfg = dict(block_size=8, num_blocks=10, decode_reserve_blocks=1)
    want = _dense_outputs(params, prompts, gen)
    paged = _paged(params, gen, PagedConfig(**cfg, spec_draft_tokens=4))
    out = _run(paged, prompts)
    assert out == want
    assert paged.metrics.preemptions > 0
    assert paged.metrics.verify_steps > 0


class _WrongDrafter:
    """Adversarial proposer: always drafts a token the tiny model is very
    unlikely to emit — accept rate ~0, exercising the disable heuristic."""

    def propose(self, history, max_tokens):
        return [int(TINY.vocab_size - 1)] * max_tokens


def test_spec_disable_heuristic_and_parity(params):
    """A hopeless drafter costs verify width for a while, then every lane
    drops to plain decode (spec_disabled_lanes) — and the output is STILL
    token-identical (the accept rule never admits a wrong token)."""
    gen = GenerationConfig(max_new_tokens=24)
    prompts = _prompts(np.random.default_rng(2), (6, 11, 9))
    want = _dense_outputs(params, prompts, gen)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=8, num_blocks=64, spec_draft_tokens=4,
            spec_probation_tokens=8, spec_min_accept_rate=0.2,
        ),
        drafter=_WrongDrafter(),
    )
    out = _run(paged, prompts)
    assert out == want
    m = paged.metrics
    assert m.spec_disabled_lanes == len(prompts)
    assert m.accept_rate() < 0.2
    # after disabling, plain decode finished the requests
    assert m.decode_steps > m.verify_steps


def test_spec_requires_greedy(params):
    from neuronx_distributed_llama3_2_tpu.inference.sampling import (
        SamplingConfig,
    )

    gen = GenerationConfig(
        max_new_tokens=4,
        sampling=SamplingConfig(greedy=False, temperature=1.0),
    )
    with pytest.raises(ValueError, match="greedy"):
        _paged(params, gen, PagedConfig(spec_draft_tokens=4))


# ---------------------------------------------------------------------------
# residency + gather-freedom acceptance checks
# ---------------------------------------------------------------------------


def test_verify_step_program_contains_no_gather(params):
    """Acceptance: the multi-token verify jaxpr must not materialize the
    (b, kv_limit, NKV, D) block-table gather when the kernel is on — and
    must when it is off (the walker actually detects it)."""
    from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import all_shapes

    b, k, kv_limit, nb, bs, w = 4, 4, 32, 16, 8, 8
    forbidden = (b, kv_limit, TINY.num_kv_heads, TINY.head_dim)
    for flag, expect_gather in ((False, True), (True, False)):
        cfg = dataclasses.replace(TINY, use_paged_kernel=flag)
        model = LlamaDecode(cfg)
        cache = model.init_paged_cache(nb, bs)
        closed = jax.make_jaxpr(
            lambda p, c, t, ps, tb, dl: model.verify_step(  # noqa: B023
                p, c, t, ps, tb, dl, kv_limit=kv_limit
            )
        )(
            params, cache, jnp.zeros((b, k + 1), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b, w), jnp.int32),
            jnp.zeros((b,), jnp.int32),
        )
        shapes = all_shapes(closed)
        assert (forbidden in shapes) is expect_gather, (
            f"use_paged_kernel={flag}: gather aval {forbidden} "
            f"{'missing' if expect_gather else 'present'} in verify jaxpr"
        )


def test_spec_steady_state_residency(params):
    """Acceptance: the PR 4 zero-upload property holds with speculation
    enabled — steady-state steps upload nothing except, on verify steps,
    the draft block itself (drafts + draft_len: exactly 2 uploads), and
    never re-push tokens/positions/tables."""
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=32, num_blocks=8, spec_draft_tokens=4
        ),
    )
    paged.submit(_rep_prompts(np.random.default_rng(0), (6,))[0])
    paged.step()  # admission + prefill
    paged.step()  # first decode dispatch (flushes the dirty lane)
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas, m.verify_steps)
        if not paged.step():
            break
        d_uploads = m.h2d_uploads - before[0]
        is_verify = m.verify_steps - before[3]
        assert m.lane_syncs == before[1]
        assert m.table_deltas == before[2]
        assert d_uploads == (2 if is_verify else 0), (d_uploads, is_verify)
    paged.run_to_completion()
    assert m.verify_steps > 0


def test_spec_metrics_in_snapshot(params):
    gen = GenerationConfig(max_new_tokens=8)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=32, spec_draft_tokens=4),
    )
    _run(paged, _rep_prompts(np.random.default_rng(4), (9, 13)))
    snap = paged.metrics.snapshot(paged.allocator, paged.index)
    for key in (
        "draft_tokens", "accepted_tokens", "verify_steps",
        "spec_disabled_lanes", "accept_rate",
    ):
        assert key in snap, key
    assert snap["verify_steps"] > 0
    assert snap["draft_tokens"] >= snap["accepted_tokens"] > 0
    assert snap["accept_rate"] == pytest.approx(
        snap["accepted_tokens"] / snap["draft_tokens"], abs=1e-3
    )


# ---------------------------------------------------------------------------
# tree speculation (docs/serving.md "Tree speculation")
# ---------------------------------------------------------------------------


def _tree_accept_ref(tokens, targets, parents, node_len):
    """The tree rule as the obvious host loop: a node is accepted iff its
    token equals the target's continuation of its (accepted) parent; the
    deepest accepted node wins, ties to the lowest packed index."""
    t = len(tokens)
    depth = [0] * t
    acc = [True] + [False] * (t - 1)
    for j in range(1, t):
        p = parents[j]
        depth[j] = depth[p] + 1
        acc[j] = j < node_len and acc[p] and tokens[j] == targets[p]
    best = max(range(t), key=lambda j: (depth[j] if acc[j] else -1, -j))
    path = []
    node = best
    while node != 0:
        path.append(tokens[node])
        node = parents[node]
    return depth[best], list(reversed(path)) + [targets[best]], best


def test_tree_accept_rule_host_oracle():
    """Random packed trees vs the host loop: accept depth, emitted path,
    and best-node tie-breaking all agree row by row."""
    from neuronx_distributed_llama3_2_tpu.inference.speculative import (
        tree_accept_rule,
    )

    rng = np.random.default_rng(0)
    t, rows = 6, 128
    tokens = rng.integers(0, 4, size=(rows, t)).astype(np.int32)
    targets = rng.integers(0, 4, size=(rows, t)).astype(np.int32)
    parents = np.zeros((rows, t), np.int32)
    for j in range(1, t):
        parents[:, j] = rng.integers(0, j, size=rows)
    node_len = rng.integers(1, t + 1, size=rows).astype(np.int32)
    accept, emitted, best = tree_accept_rule(
        tokens, targets, parents, node_len=node_len
    )
    accept, emitted, best = map(np.asarray, (accept, emitted, best))
    for i in range(rows):
        a_ref, em_ref, b_ref = _tree_accept_ref(
            tokens[i].tolist(), targets[i].tolist(),
            parents[i].tolist(), int(node_len[i]),
        )
        assert accept[i] == a_ref, i
        assert best[i] == b_ref, i
        assert emitted[i, : a_ref + 1].tolist() == em_ref, i


def test_tree_accept_rule_chain_equals_accept_rule():
    """A chain topology reduces the tree rule exactly to accept_rule:
    same accept, same emitted prefix, for random drafts and lengths."""
    from neuronx_distributed_llama3_2_tpu.inference.speculative import (
        tree_accept_rule,
    )

    rng = np.random.default_rng(1)
    k, rows = 4, 64
    drafts = rng.integers(0, 5, size=(rows, k)).astype(np.int32)
    greedy = rng.integers(0, 5, size=(rows, k + 1)).astype(np.int32)
    dlen = rng.integers(0, k + 1, size=rows).astype(np.int32)
    a_lin, e_lin = accept_rule(drafts, greedy, draft_len=dlen)
    # chain packing: node j+1 hangs off node j; block = [resident|drafts]
    block = np.concatenate(
        [np.zeros((rows, 1), np.int32), drafts], axis=1
    )
    parents = np.maximum(np.arange(k + 1, dtype=np.int32) - 1, 0)
    parents = np.broadcast_to(parents, (rows, k + 1))
    a_tree, e_tree, best = tree_accept_rule(
        block, greedy, parents, node_len=dlen + 1
    )
    a_lin, e_lin = np.asarray(a_lin), np.asarray(e_lin)
    a_tree, e_tree = np.asarray(a_tree), np.asarray(e_tree)
    assert (a_tree == a_lin).all()
    assert (np.asarray(best) == a_tree).all()  # chain: best node == depth
    for i in range(rows):
        a = int(a_lin[i])
        assert e_tree[i, : a + 1].tolist() == e_lin[i, : a + 1].tolist()


def test_tree_accept_rule_hand_trees():
    """Hand-built trees: empty accept, full-path accept, and the
    lowest-index tie-break between equal-depth accepted leaves."""
    from neuronx_distributed_llama3_2_tpu.inference.speculative import (
        tree_accept_rule,
    )

    # tree: root -> {1, 2}; 1 -> 3 (primary chain), 2 -> nothing
    parents = np.asarray([[0, 0, 0, 1]], np.int32)
    node_len = np.asarray([4], np.int32)

    # nothing accepted: accept 0, best = root, bonus = targets[0]
    a, e, b = tree_accept_rule(
        np.asarray([[9, 5, 6, 7]], np.int32),
        np.asarray([[1, 2, 3, 4]], np.int32),
        parents, node_len=node_len,
    )
    assert (int(a[0]), int(b[0])) == (0, 0)
    assert int(np.asarray(e)[0, 0]) == 1

    # full primary path accepted: root->1->3, bonus = targets[3]
    a, e, b = tree_accept_rule(
        np.asarray([[9, 1, 6, 2]], np.int32),
        np.asarray([[1, 2, 3, 4]], np.int32),
        parents, node_len=node_len,
    )
    assert (int(a[0]), int(b[0])) == (2, 3)
    assert np.asarray(e)[0, :3].tolist() == [1, 2, 4]

    # tie: BOTH children of the root accepted at depth 1 -> the lower
    # packed index (node 1, the drafter's primary branch) wins
    a, e, b = tree_accept_rule(
        np.asarray([[9, 1, 1, 6]], np.int32),
        np.asarray([[1, 8, 7, 4]], np.int32),
        parents, node_len=node_len,
    )
    assert (int(a[0]), int(b[0])) == (1, 1)
    assert np.asarray(e)[0, :2].tolist() == [1, 8]

    # node_len caps: same tokens, but only the root is live -> accept 0
    a, e, b = tree_accept_rule(
        np.asarray([[9, 1, 6, 2]], np.int32),
        np.asarray([[1, 2, 3, 4]], np.int32),
        parents, node_len=np.asarray([1], np.int32),
    )
    assert (int(a[0]), int(b[0])) == (0, 0)


def test_ngram_propose_tree_trie():
    d = NGramDrafter(max_n=3, min_n=1)
    # repeated-run tail: propose truncates to one token at the run tail,
    # but the trie deepens the chain from the earlier site's longer copy
    run = [3, 1] + [5] * 7
    assert d.propose(run, 4) == [5]
    toks, pars = d.propose_tree(run, 4, branches=2)
    assert toks == [5, 5] and pars == [0, 1]
    # the linear propose chain is always the leftmost path
    h = [1, 4, 5, 6, 7, 8, 2, 4, 5, 6]
    toks, pars = d.propose_tree(h, 4, branches=2)
    chain = d.propose(h, 4)
    # the first len(chain) trie insertions ARE the propose chain
    assert toks[: len(chain)] == chain
    assert pars[: len(chain)] == list(range(len(chain)))
    # branches=1 degrades to exactly the linear chain
    toks1, pars1 = d.propose_tree(h, 4, branches=1)
    assert toks1 == chain and pars1 == list(range(len(chain)))
    # divergent sites branch: two occurrences of (1,2) with different
    # continuations -> a branch under the shared root
    h2 = [1, 2, 5, 7, 1, 2, 9, 3, 1, 2]
    toks2, pars2 = d.propose_tree(h2, 6, branches=2)
    assert toks2[0] == 9  # latest site first == propose chain
    assert 5 in toks2      # earlier site's divergent continuation
    assert pars2[toks2.index(5)] == 0  # branches off the root
    # parents always precede children (topo-packed)
    for i, p in enumerate(pars2):
        assert 0 <= p <= i
    # abstains like propose
    assert d.propose_tree([1, 2, 3], 0, 2) == ([], [])


def test_tree_drafter_adapter():
    from neuronx_distributed_llama3_2_tpu.serving import TreeDrafter

    class _Chain:
        def propose(self, history, max_tokens):
            return [7, 8, 9][:max_tokens]

    td = TreeDrafter(_Chain(), branches=3)
    assert td.propose([1, 2], 2) == [7, 8]
    toks, pars = td.propose_tree([1, 2], 3)
    assert toks == [7, 8, 9] and pars == [0, 1, 2]  # single-chain tree
    # wrapping a tree-capable drafter delegates (trie, not chain)
    inner = NGramDrafter(max_n=3, min_n=1)
    td2 = TreeDrafter(inner, branches=2)
    run = [3, 1] + [5] * 7
    assert td2.propose_tree(run, 4) == inner.propose_tree(run, 4, 2)


def test_medusa_packed_parents():
    from neuronx_distributed_llama3_2_tpu.inference.medusa import (
        generate_medusa_buffers,
    )
    from neuronx_distributed_llama3_2_tpu.inference.speculative import (
        tree_topology,
    )

    bufs = generate_medusa_buffers()
    parents = bufs.packed_parents()
    assert parents[0] == 0
    for i in range(1, bufs.tree_len):
        assert 0 <= parents[i] < i  # parents precede children
    # round trip: tree_topology over the packed parents reproduces the
    # static buffers' depths and ancestor mask exactly
    depths, anc = tree_topology(parents)
    assert np.asarray(depths).tolist() == bufs.depths.tolist()
    assert (np.asarray(anc) == bufs.ancestor_mask).all()


# {gather, kernel} x {drained, lookahead}: the two tier-1 legs cover every
# value of both axes (kernel on the look-ahead, gather on the drained
# reference); the remaining diagonal rides the opt-in slow tier, same split
# as test_fused_step's cube
_TREE_MATRIX = [
    ("kernel", "lookahead"),
    ("gather", "drained"),
    pytest.param("kernel", "drained", marks=pytest.mark.slow),
    pytest.param("gather", "lookahead", marks=pytest.mark.slow),
]


@pytest.mark.parametrize(
    "model,loop",
    _TREE_MATRIX,
    ids=["-".join(c.values if hasattr(c, "values") else c)
         for c in _TREE_MATRIX],
)
def test_tree_spec_parity_matrix(params, model, loop):
    """Packed-tree greedy serving == dense engine across {gather, kernel}
    x {drained, lookahead} — and tree verifies must actually fire (t=5 <= the
    kernel's max_t, so the kernel leg runs the ancestor-masked kernel)."""
    model_cfg = TINY_KERNEL if model == "kernel" else TINY
    gen = GenerationConfig(max_new_tokens=10)
    prompts = _rep_prompts(np.random.default_rng(3), (12, 22, 9, 17))
    want = _dense_outputs(params, prompts, gen)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=8, num_blocks=64, spec_draft_tokens=4,
            spec_tree=True,
        ),
        model_cfg, loop=loop,
    )
    out = _run(paged, prompts)
    assert out == want
    m = paged.metrics
    assert m.tree_verify_steps > 0
    assert m.tree_draft_tokens > 0
    assert m.accepted_tokens > 0
    assert m.tree_accept_by_shape  # per-shape mix populated


def test_tree_spec_parity_under_preemption(params):
    """Pool exhaustion while tree-speculating: the frontier commit and
    rollback keep outputs identical to the uncontended dense run."""
    gen = GenerationConfig(max_new_tokens=36)
    prompts = _rep_prompts(np.random.default_rng(11), (12, 10, 14, 9))
    cfg = dict(block_size=8, num_blocks=10, decode_reserve_blocks=1)
    want = _dense_outputs(params, prompts, gen)
    paged = _paged(
        params, gen, PagedConfig(**cfg, spec_draft_tokens=4, spec_tree=True)
    )
    out = _run(paged, prompts)
    assert out == want
    assert paged.metrics.preemptions > 0
    assert paged.metrics.tree_verify_steps > 0


def test_tree_steady_state_residency(params):
    """The zero-upload property under tree speculation: a tree verify
    step's only host->device traffic is ONE packed upload (drafts +
    parents + node count in a single (B, 2k+1) block — linear verify
    pays two), zero on plain steps."""
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=32, num_blocks=8,
            spec_draft_tokens=4, spec_tree=True,
        ),
    )
    paged.submit(_rep_prompts(np.random.default_rng(0), (6,))[0])
    paged.step()  # admission + prefill
    paged.step()  # first decode dispatch (flushes the dirty lane)
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas, m.verify_steps)
        if not paged.step():
            break
        d_uploads = m.h2d_uploads - before[0]
        is_verify = m.verify_steps - before[3]
        assert m.lane_syncs == before[1]
        assert m.table_deltas == before[2]
        assert d_uploads == (1 if is_verify else 0), (d_uploads, is_verify)
    paged.run_to_completion()
    assert m.tree_verify_steps > 0


def test_tree_beats_linear_tokens_per_step(params):
    """Equal budget, repetitive traffic: the packed tree (which always
    contains the linear chain as its leftmost path) emits at least as
    many tokens per decode step as linear speculation, and strictly more
    over the workload — while staying byte-identical."""
    gen = GenerationConfig(max_new_tokens=24)
    prompts = _rep_prompts(np.random.default_rng(0), (12, 22, 9, 17))
    cfg = dict(block_size=8, num_blocks=64, spec_draft_tokens=4)
    runs = {}
    for tree in (False, True):
        paged = _paged(params, gen, PagedConfig(**cfg, spec_tree=tree))
        out = _run(paged, prompts)
        emitted = sum(len(v) for v in out.values())
        runs[tree] = (out, emitted / max(paged.metrics.decode_steps, 1))
    assert runs[False][0] == runs[True][0]  # byte parity tree vs linear
    assert runs[True][1] > runs[False][1], runs


def test_tree_requires_spec(params):
    gen = GenerationConfig(max_new_tokens=4)
    with pytest.raises(ValueError, match="spec_tree"):
        _paged(params, gen, PagedConfig(spec_tree=True))


def test_tree_metrics_in_snapshot(params):
    gen = GenerationConfig(max_new_tokens=8)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=8, num_blocks=32, spec_draft_tokens=4, spec_tree=True
        ),
    )
    _run(paged, _rep_prompts(np.random.default_rng(4), (9, 13)))
    snap = paged.metrics.snapshot(paged.allocator, paged.index)
    assert snap["tree_verify_steps"] > 0
    assert snap["tree_draft_tokens"] >= snap["tree_verify_steps"]
    assert snap["tree_accept_by_shape"]
    shape, mix = next(iter(snap["tree_accept_by_shape"].items()))
    assert shape == "t5"
    assert mix["lanes"] == sum(mix["by_len"].values())
    prom = paged.metrics.prometheus(paged.allocator, paged.index)
    assert "serving_tree_accept_lanes_shape" in prom
