"""TP-sharded paged serving: tp=2 CPU-hosted parity, residency, jaxpr.

The contract under test (docs/serving.md "Multi-chip serving"): on a pure
tensor-parallel mesh whose tp divides both head counts, the paged decode
path stays on the Pallas kernel — run per rank inside a shard_map region on
its NKV head slice (``paged_flash_decode_tp``) — and must be

- token-identical to the tp=1 engine (and the dense engine) for greedy
  sampling across the spec × {sync,async} × {chunked,whole} matrix,
- still gather-free: the decode jaxpr under the mesh contains no
  ``(b, kv_limit, NKV, D)`` materialized K/V copy,
- still resident: the async steady state does zero host→device uploads
  with readback lag exactly 1, tables/positions replicated.

The mesh is CPU-hosted: conftest forces 8 virtual devices, and
``initialize_model_parallel(..., devices=jax.devices()[:2])`` makes the
mesh pure-tp (without the explicit slice the spare devices would land on
dp and the eligibility gate would — correctly — fall back to the gather).
"""

import dataclasses

import jax
import jax.numpy as jnp
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
from neuronx_distributed_llama3_2_tpu.parallel.state import (
    initialize_model_parallel,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
)

from tests.test_async_serving import _paged, _run
from tests.test_paged_serving import _dense_outputs, _prompts

TINY = LLAMA_CONFIGS["tiny"]
# tiny: num_heads=8, num_kv_heads=4 — both divide tp=2 (2 kv heads/rank)
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _tp_mesh(tp=2):
    """Pure-tp mesh over the first ``tp`` virtual CPU devices."""
    return initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=jax.devices()[:tp]
    )


# -- eligibility gate ------------------------------------------------------


def test_kernel_gate_tp_divisible_mesh():
    """tp=2 pure mesh with divisible heads: kernel eligible for the whole
    linear-t range AND for packed-tree verify — the per-lane ancestor
    bitmasks ride into the shard_map region replicated like the block
    tables, so trees cost no new collectives; only a >32-node tree
    (ancestor sets no longer pack into int32) falls back to the gather."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    _tp_mesh()
    m = LlamaDecode(TINY_KERNEL)
    assert m._paged_kernel_eligible(1, None)
    assert m._paged_kernel_eligible(TINY.paged_kernel_max_t, None)
    assert not m._paged_kernel_eligible(TINY.paged_kernel_max_t + 1, None)
    assert m._paged_kernel_eligible(TINY.paged_kernel_max_t, object())
    wide = LlamaDecode(
        dataclasses.replace(TINY_KERNEL, paged_kernel_max_t=64)
    )
    assert wide._paged_kernel_eligible(33, None)
    assert not wide._paged_kernel_eligible(33, object())  # int32 bound


def test_kernel_gate_indivisible_heads_fall_back():
    """nkv % tp != 0 means the pool replicated (paged_cache_specs'
    _head_axis fallback) — the gate must keep the sharded gather."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    _tp_mesh()
    odd = dataclasses.replace(TINY_KERNEL, num_heads=6, num_kv_heads=3)
    assert not LlamaDecode(odd)._paged_kernel_eligible(1, None)


def test_kernel_gate_non_tp_mesh_falls_back():
    """A dp-extended mesh (8 devices, tp=2 ⇒ dp=4) is not pure-tp: the
    head-split shard_map region would not cover the mesh, so the gate
    falls back to the sharded einsums."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode
    from neuronx_distributed_llama3_2_tpu.parallel.state import mesh_is_tp_only

    initialize_model_parallel(tensor_model_parallel_size=2)  # all 8 devices
    assert not mesh_is_tp_only()
    assert not LlamaDecode(TINY_KERNEL)._paged_kernel_eligible(1, None)


def test_kernel_gate_size_one_mesh_still_eligible():
    """A tp=1 single-device mesh is the single-chip case — eligible."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    initialize_model_parallel(devices=jax.devices()[:1])
    assert LlamaDecode(TINY_KERNEL)._paged_kernel_eligible(1, None)


def test_mesh_is_tp_only_uninitialized_is_false():
    from neuronx_distributed_llama3_2_tpu.parallel.state import mesh_is_tp_only

    assert not mesh_is_tp_only()


# -- sharded kernel unit parity -------------------------------------------


@pytest.mark.parametrize("t", [None, 1, 4], ids=["3dim", "t1", "t4"])
def test_sharded_kernel_matches_single_chip(t):
    """paged_flash_decode_tp on a tp=2 mesh == paged_flash_decode on one
    chip, bitwise (same kernel body, disjoint head slices, fp32)."""
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode,
        paged_flash_decode_tp,
    )

    b, n, nkv, d, nb, bs, w, limit = 3, 8, 4, 16, 17, 8, 6, 40
    tt = 1 if t is None else t
    rng = np.random.default_rng(5)
    qshape = (b, n, d) if t is None else (b, t, n, d)
    q = jnp.asarray(rng.normal(size=qshape), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, nkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, nkv, d)), jnp.float32)
    nblk = -(-limit // bs)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, w), np.int32)
    for i in range(b):
        tables[i, :nblk] = perm[i * nblk:(i + 1) * nblk]
    tables = jnp.asarray(tables)
    pos = jnp.asarray(rng.integers(0, limit - tt + 1, size=(b,)), jnp.int32)

    ref = jax.jit(
        lambda q, k, v: paged_flash_decode(q, k, v, tables, pos, kv_limit=limit)
    )(q, kp, vp)
    st = _tp_mesh()
    out = jax.jit(
        lambda q, k, v: paged_flash_decode_tp(
            q, k, v, tables, pos, mesh=st.mesh, kv_limit=limit
        )
    )(q, kp, vp)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_kernel_rejects_indivisible_heads():
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode_tp,
    )

    st = _tp_mesh()
    q = jnp.zeros((2, 6, 8), jnp.float32)
    pool = jnp.zeros((4, 8, 3, 8), jnp.float32)  # nkv=3, tp=2
    with pytest.raises(ValueError, match="divide tp"):
        paged_flash_decode_tp(
            q, pool, pool, jnp.zeros((2, 2), jnp.int32),
            jnp.zeros((2,), jnp.int32), mesh=st.mesh,
        )


# -- engine parity matrix --------------------------------------------------


MATRIX_GEN = GenerationConfig(max_new_tokens=8)


@pytest.fixture(scope="module")
def matrix_ref(params):
    """(prompts, dense outputs) — identical across every matrix cell, so
    computed once per module (the matrix only varies scheduling knobs)."""
    rng = np.random.default_rng(7)
    # repetitive + free-text mix so the spec variants actually accept drafts
    pat = rng.integers(1, TINY.vocab_size, size=3).tolist()
    prompts = [(pat * 7)[:18]] + _prompts(rng, (5, 20, 9))
    return prompts, _dense_outputs(params, prompts, MATRIX_GEN)


# tier-1 time budget: the default tier runs a trio covering every
# chunk/loop/spec value and most pairs (whole-drained-spec completes the
# pairwise quartet from the slow tier); the rest of the cube also rides
# in the slow tier.
@pytest.mark.parametrize(
    "chunk,loop,spec",
    [
        pytest.param(6, "lookahead", 3, id="chunked-lookahead-spec"),
        pytest.param(6, "drained", 0, id="chunked-drained-plain"),
        pytest.param(None, "lookahead", 0, id="whole-lookahead-plain"),
        pytest.param(None, "drained", 3, id="whole-drained-spec",
                     marks=pytest.mark.slow),
        pytest.param(6, "drained", 3, id="chunked-drained-spec",
                     marks=pytest.mark.slow),
        pytest.param(6, "lookahead", 0, id="chunked-lookahead-plain",
                     marks=pytest.mark.slow),
        pytest.param(None, "lookahead", 3, id="whole-lookahead-spec",
                     marks=pytest.mark.slow),
        pytest.param(None, "drained", 0, id="whole-drained-plain",
                     marks=pytest.mark.slow),
    ],
)
def test_tp2_engine_parity_matrix(params, matrix_ref, chunk, loop, spec):
    """Greedy outputs identical: tp=2 engine == tp=1 engine == dense engine,
    across speculative × step loop × chunked-prefill, with the Pallas
    kernel eligible (no dense-gather fallback) on both sides."""
    gen = MATRIX_GEN
    prompts, ref = matrix_ref
    cfg = dict(
        block_size=8, num_blocks=64, prefill_chunk_tokens=chunk,
        spec_draft_tokens=spec,
    )
    p1 = _paged(params, gen, PagedConfig(**cfg), TINY_KERNEL, loop=loop)
    assert p1.model._paged_kernel_eligible(1, None)
    out_tp1 = _run(p1, prompts)
    _tp_mesh()
    p2 = _paged(params, gen, PagedConfig(**cfg), TINY_KERNEL, loop=loop)
    assert p2.model._paged_kernel_eligible(1, None), "tp=2 must not fall back"
    out_tp2 = _run(p2, prompts)
    assert out_tp2 == out_tp1
    assert out_tp2 == ref
    m = p2.metrics
    assert m.tp_size == 2
    if spec:
        assert m.verify_steps > 0 and m.accepted_tokens > 0
    if loop == "lookahead" and not spec:
        # with spec on, verify steps are drained and this short well-drafting
        # workload may never re-enter the lookahead — plain cells must
        assert m.decode_steps_async > 0


# -- residency + jaxpr under the mesh --------------------------------------


def test_tp2_steady_state_is_fully_resident(params):
    """PR 4's acceptance check survives the mesh: replicated resident
    tables/positions mean a steady-state async step still uploads nothing
    and its readback lags dispatch by exactly one step."""
    _tp_mesh()
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=32, num_blocks=8),
        TINY_KERNEL,
    )
    paged.submit(_prompts(np.random.default_rng(0), (4,))[0])
    paged.step()  # admission + prefill
    paged.step()  # first async dispatch flushes the dirty lane
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas)
        assert paged.step()
        assert (m.h2d_uploads, m.lane_syncs, m.table_deltas) == before
        assert paged._last_readback_lag == 1
    paged.run_to_completion()


def test_tp2_decode_jaxpr_has_no_gather(params):
    """Under the tp=2 mesh the kernel-path decode jaxpr must still not
    materialize the (b, kv_limit, NKV, D) gathered K/V copy — neither at
    full NKV nor at the per-rank NKV/tp slice — while the gather-path
    jaxpr (use_paged_kernel off) does contain its sharded gather."""
    from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import all_shapes
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    b, kv_limit, nb, bs, w = 4, 32, 16, 8, 8

    _tp_mesh()
    nkv = TINY.num_kv_heads
    forbidden = {
        (b, kv_limit, nkv, TINY.head_dim),          # full gather
        (b, kv_limit, nkv // 2, TINY.head_dim),     # per-rank gather
    }
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
        hit = bool(forbidden & shapes)
        assert hit is expect_gather, (
            f"use_paged_kernel={flag}: gather aval "
            f"{'missing' if expect_gather else 'present'} in tp decode jaxpr"
        )


# -- pool sizing / metrics -------------------------------------------------


def test_pool_bytes_per_rank_arithmetic():
    from neuronx_distributed_llama3_2_tpu.serving.block_allocator import (
        kv_pool_bytes_per_rank,
    )

    dims = dict(
        num_layers=4, num_blocks=64, block_size=8, num_kv_heads=4,
        head_dim=8, dtype_bytes=4,
    )
    total = kv_pool_bytes_per_rank(**dims)
    assert total == 2 * 4 * 64 * 8 * 4 * 8 * 4
    # divisible: the tp× aggregate-capacity identity
    assert kv_pool_bytes_per_rank(**dims, tp_size=2) * 2 == total
    # non-divisible heads replicate: per-rank bytes do not shrink
    odd = dict(dims, num_kv_heads=3)
    assert kv_pool_bytes_per_rank(**odd, tp_size=2) == kv_pool_bytes_per_rank(**odd)


def test_tp_rows_in_metrics_snapshot(params):
    """tp_size and the pool-byte rows land in snapshot(); at tp=2 the
    per-rank bytes are exactly half the logical pool."""
    gen = GenerationConfig(max_new_tokens=4)
    p1 = _paged(params, gen, PagedConfig(block_size=8, num_blocks=32), TINY_KERNEL)
    snap1 = p1.metrics.snapshot(p1.allocator, p1.index)
    assert snap1["tp_size"] == 1
    assert snap1["pool_bytes_per_rank"] == snap1["pool_bytes_total"] > 0
    _tp_mesh()
    p2 = _paged(params, gen, PagedConfig(block_size=8, num_blocks=32), TINY_KERNEL)
    snap2 = p2.metrics.snapshot(p2.allocator, p2.index)
    assert snap2["tp_size"] == 2
    assert snap2["pool_bytes_total"] == snap1["pool_bytes_total"]
    assert snap2["pool_bytes_per_rank"] * 2 == snap2["pool_bytes_total"]
