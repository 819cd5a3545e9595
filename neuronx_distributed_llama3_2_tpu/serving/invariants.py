"""Invariant auditor for the paged serving engine's host-side state.

The engine's correctness rests on a web of cross-structure invariants —
block refcounts conserved across request tables, the radix index, and the
allocator's free/cached partition; block-table mirrors agreeing with
request bookkeeping; decode frontiers inside the pool — that no single
module can check alone. :func:`audit_engine` walks all of it in one pass
and returns human-readable violation strings ([] = clean).

Host-only by design: nothing here reads a device array, so an audit never
forces a sync, never perturbs the async lookahead (the depth-1 lag is
*modeled*, not drained), and costs O(active lanes × table width) python —
microseconds against a multi-ms decode step. The engine runs it every
``PagedConfig.audit_interval`` steps (metric-counted, non-fatal) and
strictly at finish/preempt/fail under ``audit_debug``; soak tests call it
at teardown alongside ``BlockAllocator.leak_check``.

Invariants checked:

1. Pool partition — every usable block id in exactly one of {free, active
   refcounts, cached LRU}; no free block still registered; cached blocks
   all registered (``BlockAllocator.leak_check``).
2. Refcount conservation — each block's refcount equals the number of
   active request tables addressing it (prefix sharing is the only
   legitimate source of refcount > 1).
3. Table validity — in-range non-null ids, no duplicate within one table,
   host mirror rows matching: installed tables for decode-ready lanes,
   all-NULL decode-invisible rows for mid-chunked-prefill lanes and free
   lanes.
4. Lane bookkeeping — active lanes and the free-lane list partition the
   batch; ``req.lane`` round-trips.
5. Frontier/position sanity — ``req.position == len(prompt + out) - 1``
   for decode-ready lanes; the dispatch-frontier mirror leads it by
   exactly the in-flight lookahead depth (1 while pending, else 0);
   positions sit inside the table's backing.
6. Radix coherence — every indexed node's block is allocator-registered
   and maps back to its node; parent/child links are consistent.
7. Scale-array presence — the cache carries k/v scale arrays iff
   ``PagedConfig.kv_cache_dtype`` is quantized; and (7b) a kind of cache
   that is laid out a lane (a ring of rows, a state's slot) has a pool of
   the null block and every lane's blocks, each named by exactly one lane.
8. Fused-sampling residents — with ``PagedConfig.on_device_sampling``
   the four sampling residents (temps/topks/topps/rng) are present and
   the host mirrors correctly shaped; free lanes sit parked at the
   greedy sentinel (temp <= 0, topk 0, topp 1, null key), active lanes
   carry the installed GenerationConfig params and their request's
   SeedSequence-derived base key (the preempt-resume replay contract).
   Without the knob, all four residents are None.
9. Spilled residency — with ``PagedConfig.spill_enabled`` every node in
   the radix index's spilled set carries the ``SPILLED_BLOCK`` sentinel
   (never a live pool id), round-trips through its sid key, keeps a
   consistent parent link, and has its payload *somewhere*: resident in
   the host tier or still queued in the engine's D2H drain. The host
   tier's resident bytes respect its budget. Without the knob, the
   spilled set, the pending queue, and the host tier are all empty/None
   (pool conservation across all four residency states — free, active,
   cached, spilled — is checks 1 + 9 together).
"""

from __future__ import annotations

from typing import List

import numpy as np

from neuronx_distributed_llama3_2_tpu.serving.block_allocator import (
    NULL_BLOCK,
)
from neuronx_distributed_llama3_2_tpu.serving.radix_index import (
    SPILLED_BLOCK,
)


class InvariantViolation(AssertionError):
    """Raised by the engine's strict (debug-mode) audits; carries the full
    violation list."""

    def __init__(self, violations: List[str]):
        self.violations = list(violations)
        super().__init__(
            f"{len(self.violations)} serving invariant violation(s): "
            + "; ".join(self.violations)
        )


def summarize_violations(violations: List[str], limit: int = 3) -> str:
    """Compact one-line digest of an audit result for trace instants and
    log lines: the first ``limit`` violations verbatim, plus a count of
    the rest."""
    head = "; ".join(violations[:limit])
    extra = len(violations) - limit
    return head + (f"; (+{extra} more)" if extra > 0 else "")


def audit_engine(engine) -> List[str]:
    """Audit one :class:`.engine.PagedServingEngine`. Returns violation
    strings, [] when every invariant holds. Never raises, never touches
    device arrays."""
    v: List[str] = []
    alloc = engine.allocator
    index = engine.index
    nb = alloc.num_blocks

    # 1. pool partition
    for bid in alloc.leak_check():
        v.append(f"pool partition violated at block {bid}")

    # 2. refcount conservation vs active tables
    expected: dict = {}
    for req in engine._active.values():
        for b in req.table:
            expected[b] = expected.get(b, 0) + 1
    for b, n in expected.items():
        if alloc.refcount(b) != n:
            v.append(
                f"block {b}: refcount {alloc.refcount(b)} != {n} table refs"
            )
    for b, n in alloc._ref.items():
        if b not in expected:
            v.append(f"block {b}: refcount {n} but no active table holds it")

    # 3 + 4 + 5. lanes, tables, frontiers
    pending_lanes = set(engine._pending[1]) if engine._pending else set()
    max_batch = engine.engine.max_batch
    active_lanes = set(engine._active.keys())
    free_lanes = set(engine._free_lanes)
    if active_lanes & free_lanes:
        v.append(f"lanes both active and free: {sorted(active_lanes & free_lanes)}")
    if active_lanes | free_lanes != set(range(max_batch)):
        v.append(
            f"lane partition broken: active {sorted(active_lanes)} + free "
            f"{sorted(free_lanes)} != 0..{max_batch - 1}"
        )
    for lane in free_lanes - engine._dirty_lanes:
        if (engine._tables[lane] != NULL_BLOCK).any():
            v.append(f"free lane {lane}: table mirror row not all-NULL")
    for lane, req in engine._active.items():
        if req.lane != lane:
            v.append(f"lane {lane}: request {req.rid} thinks it is on lane {req.lane}")
        if len(set(req.table)) != len(req.table):
            v.append(f"rid {req.rid}: duplicate block in table {req.table}")
        for b in req.table:
            if not 1 <= b < nb:
                v.append(f"rid {req.rid}: table holds invalid block id {b}")
        row = engine._tables[lane]
        if lane in engine._dirty_lanes:
            pass  # mirror queued for rewrite; skip the row checks
        elif req.prefilling:
            if getattr(engine, "_fused_step", False):
                # fused mode prefills THROUGH the pmixed grid, so the
                # mid-prefill table mirror is live; the resident write
                # position parks at prefill_target (a private or
                # null-backed row — never a shared prefix block) until
                # the final chunk lands
                w = len(req.table)
                if list(row[:w]) != req.table:
                    v.append(
                        f"rid {req.rid}: fused mid-prefill mirror row "
                        f"{list(row[:w])} != table {req.table}"
                    )
                if (row[w:] != NULL_BLOCK).any():
                    v.append(
                        f"rid {req.rid}: mirror row live past table end"
                    )
                if int(engine._positions[lane]) != req.prefill_target:
                    v.append(
                        f"rid {req.rid}: fused mid-prefill resident "
                        f"position {int(engine._positions[lane])} not "
                        f"parked at prefill_target {req.prefill_target}"
                    )
            elif (row != NULL_BLOCK).any():
                v.append(
                    f"rid {req.rid}: decode-visible table row live "
                    "mid-chunked-prefill"
                )
        else:
            w = len(req.table)
            if list(row[:w]) != req.table:
                v.append(
                    f"rid {req.rid}: table mirror row {list(row[:w])} != "
                    f"table {req.table}"
                )
            if (row[w:] != NULL_BLOCK).any():
                v.append(f"rid {req.rid}: mirror row live past table end")
            want = len(req.prompt) + len(req.out) - 1
            if req.position != want:
                v.append(
                    f"rid {req.rid}: position {req.position} != "
                    f"len(prompt + out) - 1 = {want}"
                )
            lag = int(engine._positions[lane]) - req.position
            want_lag = 1 if lane in pending_lanes else 0
            if lag != want_lag:
                v.append(
                    f"rid {req.rid}: dispatch frontier lag {lag} != {want_lag}"
                )
            if int(engine._positions[lane]) > engine._pos_cap:
                v.append(f"rid {req.rid}: frontier past the table's last row")
            if req.position >= engine.engine.max_seq_len:
                v.append(
                    f"rid {req.rid}: position {req.position} past max_seq_len"
                )

    # 6. radix coherence
    for bid, node in index._by_block.items():
        if node.block != bid:
            v.append(f"radix node for block {bid} claims block {node.block}")
        if not alloc.is_registered(bid):
            v.append(f"radix-indexed block {bid} not registered in allocator")
        if node.parent is not None and node.parent.children.get(node.key) is not node:
            v.append(f"radix node for block {bid}: broken parent link")

    # 7. scale arrays match the configured pool dtype
    quant = engine.paged.kv_cache_dtype != "bf16"
    # a pool a kind of cache, where the cache has fields by kind
    pools = [engine._kind_pool(kind) for kind in engine.model.cache_kinds]
    has_k = all(getattr(pool, "k_scale", None) is not None for pool in pools)
    has_v = all(getattr(pool, "v_scale", None) is not None for pool in pools)
    if quant != has_k or quant != has_v:
        v.append(
            f"kv_cache_dtype={engine.paged.kv_cache_dtype!r} but cache "
            f"scale arrays present=(k={has_k}, v={has_v})"
        )

    # 7b. a kind laid out a lane (a ring of rows, a state's slot): its pool
    # is the null block and every lane's blocks, each named by one lane
    lane_kind = getattr(engine, "_lane_kind", None)
    if lane_kind is not None:
        import jax

        tables = engine._lane_tables
        blocks = int(jax.tree.leaves(engine._kind_pool(lane_kind))[0].shape[1])
        if blocks != 1 + tables.size or sorted(tables.ravel().tolist()) != list(range(1, blocks)):
            v.append(
                f"{lane_kind.name} kind: pool of {blocks} blocks, lanes' tables "
                f"{tables.shape} do not name each of 1..{blocks - 1} once"
            )

    # 9. spilled residency (checked before 8: that one early-returns)
    tier = getattr(engine, "host_tier", None)
    spilled = getattr(index, "_spilled", {})
    pending_sids = {e[0] for e in getattr(engine, "_spill_pending", ())}
    if not getattr(engine, "_spill", False):
        if spilled:
            v.append(
                f"{len(spilled)} spilled radix node(s) without spill_enabled"
            )
        if pending_sids:
            v.append("spill drain queue non-empty without spill_enabled")
        if tier is not None:
            v.append("host tier present without spill_enabled")
    else:
        for sid, node in spilled.items():
            if node.block != SPILLED_BLOCK:
                v.append(
                    f"spilled node sid {sid}: block {node.block} != "
                    "SPILLED_BLOCK sentinel"
                )
            if node.sid != sid:
                v.append(f"spilled node sid {sid}: claims sid {node.sid}")
            if (
                node.parent is not None
                and node.parent.children.get(node.key) is not node
            ):
                v.append(f"spilled node sid {sid}: broken parent link")
            if not tier.has(sid) and sid not in pending_sids:
                v.append(
                    f"spilled node sid {sid}: payload neither resident in "
                    "the host tier nor queued for drain"
                )
        if tier.resident_bytes > tier.budget_bytes:
            v.append(
                f"host tier over budget: {tier.resident_bytes} > "
                f"{tier.budget_bytes} bytes"
            )

    # 8. fused-sampling residents match the on_device_sampling knob
    residents = {
        "_d_temps": engine._d_temps, "_d_topks": engine._d_topks,
        "_d_topps": engine._d_topps, "_d_rng": engine._d_rng,
    }
    if not engine._fused:
        for name, arr in residents.items():
            if arr is not None:
                v.append(
                    f"sampling resident {name} present without "
                    "on_device_sampling"
                )
        return v
    for name, arr in residents.items():
        if arr is None:
            v.append(f"on_device_sampling engine missing resident {name}")
    mirror_spec = (
        ("_temps", engine._temps, (max_batch,), np.float32),
        ("_topks", engine._topks, (max_batch,), np.int32),
        ("_topps", engine._topps, (max_batch,), np.float32),
        ("_rng", engine._rng, (max_batch, 2), np.uint32),
    )
    for name, arr, shape, dtype in mirror_spec:
        if arr.shape != shape or arr.dtype != dtype:
            v.append(
                f"sampling mirror {name}: shape {arr.shape}/{arr.dtype} != "
                f"{shape}/{np.dtype(dtype)}"
            )
    for lane in free_lanes:
        # released lanes park at the greedy sentinel with a null key
        # (_clear_lane_sampling writes the mirror at release time, so this
        # holds whether or not the lane_set flush has happened yet)
        if (
            engine._temps[lane] > 0.0
            or engine._topks[lane] != 0
            or engine._topps[lane] != 1.0
            or engine._rng[lane].any()
        ):
            v.append(f"free lane {lane}: sampling mirror not parked")
    s = engine.gen.sampling
    for lane, req in engine._active.items():
        if s.greedy:
            ok = (
                engine._temps[lane] <= 0.0
                and engine._topks[lane] == 0
                and engine._topps[lane] == 1.0
            )
        else:
            ok = (
                engine._temps[lane] == np.float32(s.temperature)
                and engine._topks[lane] == s.top_k
                and engine._topps[lane] == np.float32(s.top_p)
            )
        if not ok:
            v.append(
                f"rid {req.rid}: lane {lane} sampling params do not match "
                "the GenerationConfig install"
            )
        if not s.greedy and not np.array_equal(
            engine._rng[lane], engine._lane_rng(req.rid)
        ):
            v.append(
                f"rid {req.rid}: lane {lane} rng key != the request's "
                "SeedSequence base key (preempt-resume replay would diverge)"
            )
    return v
