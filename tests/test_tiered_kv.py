"""Tiered KV storage: the host-RAM spill tier behind the radix index.

Two layers (docs/serving.md "Tiered KV storage"):

- pure-host unit tests over the four-state block lifecycle
  (free/active/cached/spilled): :class:`HostTier` budget/LRU mechanics,
  the allocator's ``spill_hook`` eviction diversion, and the radix
  index's spilled-node bookkeeping (``mark_spilled`` / ``heal`` /
  ``invalidate_spilled`` / the insert-heal path);
- engine acceptance on the tiny CPU model: an eviction-heavy
  multi-tenant churn workload must produce **byte-identical token
  streams** with spill on vs off (restore-over-recompute is an
  optimization, never a numerics change) while actually restoring —
  including through an int8 pool (the scale tiles ride the spilled
  payload) and onto a copy-on-write extension of a restored block; the
  crossover knob declines restores when priced out; a host-tier fault
  falls back to re-prefill inside the victim's failure domain.
"""

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
from neuronx_distributed_llama3_2_tpu.serving import (
    BlockAllocator,
    FaultInjector,
    FaultPlan,
    PagedConfig,
    PagedServingEngine,
    RadixPrefixIndex,
    audit_engine,
)
from neuronx_distributed_llama3_2_tpu.serving.block_allocator import HostTier
from neuronx_distributed_llama3_2_tpu.serving.radix_index import SPILLED_BLOCK

TINY = LLAMA_CONFIGS["tiny"]


# ---------------------------------------------------------------------------
# HostTier
# ---------------------------------------------------------------------------


def test_host_tier_put_get_and_lru_budget_eviction():
    dropped = []
    t = HostTier(budget_bytes=100, on_evict=dropped.append)
    s1, s2, s3 = t.allocate_sid(), t.allocate_sid(), t.allocate_sid()
    assert (s1, s2, s3) == (0, 1, 2)  # sids are monotonic, never reused
    t.put_at(s1, ("a",), 40)
    t.put_at(s2, ("b",), 40)
    assert t.resident_bytes == 80 and t.num_entries == 2
    t.get(s1)  # touch: s2 becomes LRU
    t.put_at(s3, ("c",), 40)  # 120 > 100 -> evict s2
    assert dropped == [s2]
    assert t.evictions == 1
    assert not t.has(s2) and t.has(s1) and t.has(s3)
    assert t.resident_bytes == 80
    assert t.stats()["host_tier_evictions"] == 1
    assert t.pop(s1) == ("a",)
    t.drop(s3)  # silent drop: no on_evict
    assert dropped == [s2]
    assert t.resident_bytes == 0 and t.num_entries == 0


def test_host_tier_oversized_entry_evicts_itself():
    dropped = []
    t = HostTier(budget_bytes=10, on_evict=dropped.append)
    sid = t.allocate_sid()
    t.put_at(sid, ("big",), 50)  # cannot fit: immediately evicted
    assert dropped == [sid]
    assert t.resident_bytes == 0


def test_host_tier_budget_validation():
    with pytest.raises(ValueError):
        HostTier(budget_bytes=0)


# ---------------------------------------------------------------------------
# the four-state lifecycle: spill_hook + radix spilled nodes
# ---------------------------------------------------------------------------


def _pool(n=32, bs=4):
    a = BlockAllocator(num_blocks=n, block_size=bs)
    return a, RadixPrefixIndex(a)


def _spill_all(a, idx, tier):
    """Wire a spill hook that diverts every eviction into ``tier``."""
    def hook(bid):
        sid = tier.allocate_sid()
        if not idx.mark_spilled(bid, sid):
            return False
        tier.put_at(sid, (f"payload-{bid}",), 8)
        return True

    a.spill_hook = hook
    a.host_tier = tier


def test_spill_hook_diverts_eviction_and_match_stops_at_spilled():
    a, idx = _pool(n=4, bs=4)  # 3 usable blocks
    tier = HostTier(budget_bytes=1 << 20)
    _spill_all(a, idx, tier)
    b1, b2, b3 = a.alloc(), a.alloc(), a.alloc()
    toks = list(range(1, 13))
    idx.insert(toks, [b1, b2, b3])
    for b in (b1, b2, b3):
        a.release(b)
    got = a.alloc()  # evicts b1 (LRU) -> spilled, pool id recycled
    assert got == b1
    assert a.evictions == 1
    assert idx.num_spilled == 1 and idx.num_nodes == 2
    assert tier.num_entries == 1
    # four-state conservation: the spilled node holds no pool id
    assert a.leak_check() == []
    # match cannot hand out a spilled block...
    assert idx.match(toks) == (0, [])
    # ...but walk sees the full spilled-prefix chain
    matched, chain = idx.walk(toks)
    assert matched == 12
    assert chain[0].block == SPILLED_BLOCK and chain[0].sid == 0
    assert [n.block for n in chain[1:]] == [b2, b3]


def test_heal_rebinds_spilled_node_to_fresh_block():
    a, idx = _pool(n=4, bs=4)
    tier = HostTier(budget_bytes=1 << 20)
    _spill_all(a, idx, tier)
    b1 = a.alloc()
    idx.insert([1, 2, 3, 4], [b1])
    a.release(b1)
    a.alloc(), a.alloc(), a.alloc()  # force the eviction
    assert idx.num_spilled == 1
    (node,) = idx._spilled.values()
    idx.on_spill_drop = lambda sid: tier.drop(sid)
    nb = 1  # caller freed a lane; restore into a fresh id
    idx.heal(node, nb)
    assert idx.num_spilled == 0
    assert node.block == nb and node.sid == -1
    assert a.is_registered(nb)
    assert tier.num_entries == 0  # heal released the host payload
    assert idx.match([1, 2, 3, 4]) == (4, [nb])


def test_insert_heals_spilled_child_with_prefilled_block():
    a, idx = _pool(n=8, bs=4)
    tier = HostTier(budget_bytes=1 << 20)
    _spill_all(a, idx, tier)
    idx.on_spill_drop = lambda sid: tier.drop(sid)
    b1, b2 = a.alloc(), a.alloc()
    idx.insert([1, 2, 3, 4, 5, 6, 7, 8], [b1, b2])
    a.release(b1)
    a.release(b2)
    while a.free_blocks:
        a.alloc()
    a.alloc()  # evict+spill b1
    a.alloc()  # evict+spill b2
    assert idx.num_spilled == 2
    # a declined restore re-prefills the same prefix: insert must heal
    # the spilled chain in place of duplicating nodes
    nb1, nb2 = 1, 2
    assert idx.insert([1, 2, 3, 4, 5, 6, 7, 8], [nb1, nb2]) == 2
    assert idx.num_spilled == 0
    assert tier.num_entries == 0
    assert idx.match([1, 2, 3, 4, 5, 6, 7, 8]) == (8, [nb1, nb2])


def test_invalidate_spilled_drops_the_whole_downstream_run():
    a, idx = _pool(n=4, bs=4)
    tier = HostTier(budget_bytes=1 << 20)
    _spill_all(a, idx, tier)
    idx.on_spill_drop = lambda sid: tier.drop(sid)
    b1, b2, b3 = a.alloc(), a.alloc(), a.alloc()
    idx.insert(list(range(1, 13)), [b1, b2, b3])
    for b in (b1, b2, b3):
        a.release(b)
    a.alloc(), a.alloc(), a.alloc()  # spill the whole chain
    assert idx.num_spilled == 3
    sid0 = min(idx._spilled)  # shallowest = the failure domain's root
    idx.invalidate_spilled(sid0)
    assert idx.num_spilled == 0
    assert idx.num_nodes == 0
    assert tier.num_entries == 0
    assert a.leak_check() == []


def test_eviction_of_resident_child_under_spilled_parent():
    # parent spilled, child resident: evicting the child must not touch
    # the parent's host payload, and the chain stays walkable up to it
    a, idx = _pool(n=4, bs=4)
    tier = HostTier(budget_bytes=1 << 20)
    b1, b2 = a.alloc(), a.alloc()
    idx.insert([1, 2, 3, 4, 5, 6, 7, 8], [b1, b2])
    spilled_once = []

    def hook(bid):
        if bid == b1 and not spilled_once:
            sid = tier.allocate_sid()
            assert idx.mark_spilled(bid, sid)
            tier.put_at(sid, ("p",), 8)
            spilled_once.append(bid)
            return True
        return False  # child falls through to the plain drop path

    a.spill_hook = hook
    a.host_tier = tier
    a.alloc()  # consume the last free block so evictions engage
    a.release(b1)
    a.release(b2)
    a.alloc()  # evicts b1 -> spilled
    assert idx.num_spilled == 1
    a.alloc()  # evicts b2 -> plain drop (hook declines)
    assert idx.num_spilled == 1  # parent payload untouched
    assert tier.num_entries == 1
    assert a.leak_check() == []


# ---------------------------------------------------------------------------
# engine acceptance: byte-identity, COW-on-restored, int8, crossover, faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _churn_prompts(seed=7, n_fillers=4, prefix_tokens=20):
    """Shared prefix ending mid-block (20 = 2.5 blocks at block_size=8):
    the re-hit request must COW the restored partial leaf block."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, TINY.vocab_size, size=(prefix_tokens,)).tolist()
    fillers = [
        rng.integers(0, TINY.vocab_size, size=(20,)).tolist()
        for _ in range(n_fillers)
    ]
    return shared, fillers


def _run_churn(params, spill, kv_dtype="bf16", crossover=1e9, injector=None):
    """Seed a shared prefix, churn the pool past eviction, re-hit the
    prefix twice with different mid-block tails. Returns (outs, engine)."""
    gen = GenerationConfig(max_new_tokens=4)
    eng = PagedServingEngine(
        InferenceEngine(
            TINY, params, max_batch=2, max_seq_len=64, buckets=[8, 16, 32]
        ),
        gen,
        PagedConfig(
            block_size=8, num_blocks=12, kv_cache_dtype=kv_dtype,
            spill_enabled=spill,
            host_tier_bytes=(1 << 30) if spill else 0,
            restore_crossover=crossover if spill else 1.0,
        ),
        injector=injector,
    )
    shared, fillers = _churn_prompts()
    outs = {}
    eng.submit(shared + [1, 2])
    outs.update(eng.run_to_completion())
    for f in fillers:
        eng.submit(f)
    outs.update(eng.run_to_completion())
    eng.submit(shared + [3, 4])
    eng.submit(shared + [5, 6])
    outs.update(eng.run_to_completion())
    assert audit_engine(eng) == []
    assert eng.allocator.leak_check() == []
    return outs, eng


@pytest.fixture(scope="module")
def bf16_baseline(params):
    return _run_churn(params, spill=False)[0]


def test_spill_restore_byte_identity_and_cow_on_restored_block(
    params, bf16_baseline
):
    outs, eng = _run_churn(params, spill=True)
    assert outs == bf16_baseline  # restore is invisible to the tokens
    m = eng.metrics
    assert m.blocks_spilled > 0
    assert m.restore_hits > 0 and m.blocks_restored > 0
    assert m.restore_bytes > 0 and m.restore_uploads > 0
    # the 20-token prefix ends mid-block: extending past a restored
    # partial leaf must go through copy-on-write, never write in place
    assert eng.allocator.cow_copies > 0
    # conservation held with a populated host tier (the audit above ran
    # with spilled payloads resident); spill bookkeeping is consistent
    assert eng.index.num_spilled == len(eng.index._spilled)
    snap = m.snapshot(eng.allocator, eng.index)
    assert snap["restore_hit_rate"] > 0
    assert snap["host_tier_bytes"] >= 0


def test_quantized_scale_tiles_round_trip_through_spill(params):
    base, _ = _run_churn(params, spill=False, kv_dtype="int8")
    outs, eng = _run_churn(params, spill=True, kv_dtype="int8")
    m = eng.metrics
    assert m.restore_hits > 0
    # byte-identity through an int8 pool proves the k/v scale tiles
    # rode the spilled payload and restored exactly (a lost or reordered
    # scale tile would change the dequantized logits)
    assert outs == base


def test_restore_crossover_declines_and_audit_spots_lost_payload(params):
    # crossover 0 prices every restore out: the engine must fall back to
    # re-prefill (insert() heals the spilled chain) with identical tokens
    outs, eng = _run_churn(params, spill=True, crossover=0.0)
    m = eng.metrics
    assert m.restore_hits == 0 and m.blocks_restored == 0
    assert m.restore_declined > 0
    assert outs == _run_churn(params, spill=False)[0]
    # invariant 9 teeth: losing a host payload behind the index's back
    # (bypassing the drop hooks) is a detectable violation
    if eng.index.num_spilled and eng.host_tier.num_entries:
        sid = next(iter(eng.index._spilled))
        if eng.host_tier.has(sid):
            eng.host_tier._entries.pop(sid)
            assert any("payload" in v for v in audit_engine(eng))


def test_host_tier_fault_falls_back_to_reprefill(params, bf16_baseline):
    inj = FaultInjector(FaultPlan(seed=3, host_tier_rate=1.0))
    outs, eng = _run_churn(params, spill=True, injector=inj)
    m = eng.metrics
    assert inj.counts["host_tier"] >= 1
    assert m.restore_fallbacks >= 1
    assert m.restore_hits == 0  # every attempt was corrupted
    # the fallback re-prefills inside the victim's failure domain:
    # every token stream stays byte-identical to the fault-free baseline
    assert outs == bf16_baseline


def test_spill_config_validation(params):
    with pytest.raises(ValueError, match="host_tier_bytes"):
        PagedServingEngine(
            InferenceEngine(
                TINY, params, max_batch=2, max_seq_len=64, buckets=[8, 16]
            ),
            GenerationConfig(max_new_tokens=2),
            PagedConfig(block_size=8, num_blocks=12, spill_enabled=True),
        )
    with pytest.raises(ValueError, match="prefix"):
        PagedServingEngine(
            InferenceEngine(
                TINY, params, max_batch=2, max_seq_len=64, buckets=[8, 16]
            ),
            GenerationConfig(max_new_tokens=2),
            PagedConfig(
                block_size=8, num_blocks=12, spill_enabled=True,
                host_tier_bytes=1 << 20, enable_prefix_caching=False,
            ),
        )


@pytest.mark.parametrize("chunk,pieces,rung", [(None, 1, 32), (8, 3, 8), (16, 2, 16)])
def test_a_recompute_is_priced_as_the_dispatches_chunking_would_run(params, chunk, pieces, rung):
    """A spilled run of 24 rows against the prefill ladder: one ``pctx[32]``
    where the engine does not chunk, ``ceil(24 / chunk)`` dispatches of the
    chunk's rung where it does — the ladder of a chunked engine holds no rung
    for the whole run, and the estimate must not ask it for one."""
    from neuronx_distributed_llama3_2_tpu.serving.accounting import (
        EngineDims,
        analytic_cost,
    )

    eng = PagedServingEngine(
        InferenceEngine(TINY, params, max_batch=2, max_seq_len=64, buckets=[8, 16, 32]),
        GenerationConfig(max_new_tokens=2),
        PagedConfig(
            block_size=8, num_blocks=12, spill_enabled=True, host_tier_bytes=1 << 20,
            prefill_chunk_tokens=chunk,
        ),
    )
    assert eng._prefill_buckets[-1] == (rung if chunk else 64)
    restore_s, recompute_s = eng._restore_price(4096, 24)
    flops = analytic_cost(("pctx", rung), EngineDims.from_engine(eng))[0]
    peak = eng.metrics.peak_flops_per_chip * max(eng.metrics.tp_size, 1)
    assert restore_s > 0 and recompute_s == pytest.approx(pieces * flops / peak)
