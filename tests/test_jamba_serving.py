"""A stack that keeps a state a lane in some layers and rows a token in the
others (Jamba: Mamba-1 state-space layers beside attention layers) under the
paged serving engine at the tiny size, float32 on the CPU: logits and tokens
against the plain reference's full forward (which has no state, no cache and
no chunk), and what such a stack forces on the engine — a slot a lane beside
the allocator's blocks, the live length of a padded chunk, the zero state of a
reused slot, an idle lane kept off a state, no prefix sharing, the refusals.
Seeded weights remember for tens of tokens (Mamba-1's own initialisation), so
a state that kept or lost something it should not have shows rows later."""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig, HybridCache, InferenceEngine, JambaDecode,
)
from neuronx_distributed_llama3_2_tpu.inference.model import (
    CacheKind, cache_block_bytes, cache_row_bytes, decode_model_for,
)
from neuronx_distributed_llama3_2_tpu.inference.speculative import SpeculativeDecoder
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.models.jamba import JAMBA_CONFIGS, JambaForCausalLM
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine, audit_engine
from neuronx_distributed_llama3_2_tpu.serving.accounting import EngineDims
from tests.drained_policy import LOOPS, loop_policy

TINY = dataclasses.replace(JAMBA_CONFIGS["tiny-jamba"], max_seq_len=96)
BS, CHUNK = 16, 16
SIZES = {"lanes": 4, "block_size": BS, "max_seq_len": 96, "pool_blocks": 40,
         "prefill_chunk_tokens": CHUNK, "prefill_buckets": [8, 16], "kv_buckets": [96]}
# float32 against float32 at "highest": what is left is the order of the sums
TOL = 1e-4
STATE_BYTES = 3 * (8 * 128 * 4 + 16 * 128 * 4)     # Mamba layers x (h + the tail's 16 rows of lanes), float32 at this size
# the kernel modes the CPU runs, and how a decode step reads the state kind in each (``JambaDecode.decode_read``)
MODES = ("reference", "interpret")
STATE_READ = {"reference": "pass", "interpret": "kernel"}


def _tool(name):
    path = os.path.join(spec.HERE, "tools", name + ".py")
    mod = importlib.util.module_from_spec(importlib.util.spec_from_file_location(name, path))
    mod.__spec__.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("jamba")


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every kernel and the embedding five times as large:
    at this width seeded scores are near zero, every softmax near uniform and
    a state's term a small part of its layer's output, so a fault would move
    the logits by parts in ten thousand; scaled, each moves them by percent."""
    params = jax.jit(JambaForCausalLM(TINY).init)(jax.random.key(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 5.0 if path[-1].key in ("kernel", "embedding", "gate_up") else a, params)


def engine(params, **kw):
    return InferenceEngine(TINY, params, max_batch=kw.pop("max_batch", 4), max_seq_len=96,
                           buckets=[8, 16, 32, 96], **kw)


def serving(params, new_tokens=6, policy=None, **paged):
    paged = {"block_size": BS, "num_blocks": 40, "prefill_chunk_tokens": CHUNK,
             "prefill_buckets": (8, 16), "kv_buckets": (96,), **paged}
    return PagedServingEngine(engine(params), GenerationConfig(max_new_tokens=new_tokens),
                              PagedConfig(**paged), policy=policy)


def reference_logits(fam, params, sequence):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, i: fam.reference.forward_logits(p, fam.reference_config(TINY), i)
        )(params, jnp.asarray([sequence]))[0])


def reference_tokens(fam, params, prompt, new_tokens):
    """Greedy continuation by the reference's full forward, one token at a
    time, jitted once at a fixed length (the model is causal: padding after
    the last token changes no earlier row)."""
    seq, forward = list(prompt), _jitted_reference(fam)
    for _ in range(new_tokens):
        padded = jnp.asarray([seq + [0] * (96 - len(seq))])
        with jax.default_matmul_precision("highest"):
            seq.append(int(jnp.argmax(forward(params, padded)[0, len(seq) - 1])))
    return seq[len(prompt):]


@functools.lru_cache(maxsize=None)
def _jitted_reference(fam):
    cfg = fam.reference_config(TINY)
    return jax.jit(lambda p, i: fam.reference.forward_logits(p, cfg, i))


def prompts_of(rng, lengths):
    return [rng.integers(1, TINY.vocab_size, size=(n,)).tolist() for n in lengths]


def clean(srv):
    assert srv.allocator.leak_check() == [] and audit_engine(srv) == []


def as_the_engine_runs_it(model, params, pool, prompt, fed, *, lane=0, lanes=3, first_block=1):
    """Logits of every real row of ``prompt + fed`` through the calls the
    engine's programs make, lengths and slots included: ``pctx`` over the
    first chunk, ``psfx`` over the later ones — the last one padded to its
    bucket, its live length handed on — then ``pdecode`` steps in a batch of
    ``lanes`` whose other lanes are idle (null table, their own slot named).
    The request sits on lane ``lane``: slot 1 + lane, blocks from
    ``first_block``. Returns (rows, the pool)."""
    blocks = -(-(len(prompt) + len(fed)) // BS)
    width = 96 // BS + CHUNK // BS
    table = np.zeros((lanes, width), np.int32)
    table[lane, :blocks] = first_block + np.arange(blocks)
    slots = jnp.asarray(1 + np.arange(lanes, dtype=np.int32))[:, None]
    head = model._model()._logits
    rows = []
    for start in range(0, len(prompt), CHUNK):
        piece = prompt[start:start + CHUNK]
        bucket = next(b for b in (8, 16) if b >= len(piece))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(piece)] = piece
        hidden, pool = jax.jit(lambda p, c, i, s, n, fresh=start == 0: model.forward(
            p, c, i, s, None, context_encode=fresh, return_hidden=True, kv_limit=None if fresh else 96,
            block_tables=jnp.asarray(table[lane:lane + 1]), row_live=n,
            state_tables=slots[lane:lane + 1]))(
                params, pool, jnp.asarray(ids), jnp.full((1,), start, jnp.int32),
                jnp.asarray([len(piece)], jnp.int32))
        rows.append(head(params, hidden)[0, :len(piece)])
    mask = jnp.zeros((lanes,), jnp.int32).at[lane].set(1)
    step = jax.jit(lambda p, c, t, pos: model.decode_step(
        p, c, t, pos, jnp.asarray(table), kv_limit=96, state_tables=slots))
    for i, token in enumerate(fed):
        logits, _, pool = step(params, pool, mask * token, mask * (len(prompt) + i))
        rows.append(logits[lane:lane + 1])
    return np.asarray(jnp.concatenate(rows, axis=0)), pool


# ---------------------------------------------------------------------------
# the decode class, its cache and what the engine lays out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_the_family_gets_its_decode_class_and_a_cache_of_two_kinds(mode, monkeypatch):
    """The state kind's decode read is the step kernel's visit a live lane
    wherever Pallas kernels run, the pass over every slot in the CPU tier's
    ``reference`` mode."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    assert isinstance(model, JambaDecode) and model.cache_is_positional and model.keeps_state
    assert model.cache_kinds == (CacheKind("rows", 2, None), CacheKind("state", 3, 0, state=True))
    assert [model.decode_read(kind) for kind in model.cache_kinds] == ["gather", STATE_READ[mode]]
    assert model.uses_state_kernel() == (mode == "interpret")
    pool = model.init_paged_cache(7, BS, state_blocks=5)
    assert isinstance(pool, HybridCache) and (pool.num_blocks, pool.block_size) == (7, BS)
    # rows: a token's heads side by side, the layer axis the attention layers' alone
    assert pool.rows.k.shape == pool.rows.v.shape == (2, 7, BS, 1 * 16)
    # a state a slot: h (N, D) float32 with the wide axis minor, the tail's rows side by side
    assert pool.state.h.shape == (3, 5, 8, 128) and pool.state.h.dtype == jnp.float32
    assert pool.state.tail.shape == (3, 5, 16, 128)          # a slot whole tiles: 384 values in 16 rows of lanes
    assert model.init_paged_cache(7, BS).state.h.shape[1] == 7          # as many slots as blocks where not said
    assert model.init_paged_cache(7, BS, jnp.bfloat16).state.h.dtype == jnp.bfloat16
    assert cache_block_bytes(pool.state) == STATE_BYTES >= 3 * TINY.state_bytes_per_layer()      # as laid out
    assert cache_row_bytes(pool.rows) == 2 * 16 * 4
    with pytest.raises(NotImplementedError, match="tree verification"):
        model.forward({}, pool, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                      tree=(jnp.zeros((2,), jnp.int32), jnp.ones((2, 2), bool)))


def test_the_engine_lays_a_slot_a_lane_beside_the_allocators_blocks(params):
    srv = serving(params)
    assert srv._lane_kind.state and srv._lane_blocks == 1 and srv._has_state and not srv._share_prefixes
    np.testing.assert_array_equal(srv._lane_tables, [[1], [2], [3], [4]])
    assert srv.cache.state.h.shape[1] == 1 + 4 and srv.cache.rows.k.shape[1] == 40
    # a prefill's table: the request's blocks, null past them, the lane's slot after the table's columns
    row = srv._prefill_table([5, 6], 2)
    assert row.shape == (1, srv.table_width + 1) and row[0, :3].tolist() == [5, 6, 0] and row[0, -1] == 3
    assert srv._prefill_table([5], None)[0, -1] == 0                   # a warm-up call: the null slot
    dims = EngineDims.from_engine(srv)
    assert dims.ring_is_state and dims.ring_bytes == STATE_BYTES
    rows = 2 * 2 * 16 * 4                                              # attention layers x (k + v) x width x float32
    assert dims.kv_row_bytes() == rows
    assert dims.request_cache_bytes(20) == 2 * BS * rows + STATE_BYTES      # blocks of rows and one state
    assert dims.state_bytes(3) == 2 * 3 * STATE_BYTES                       # read once and written once
    clean(srv)


@pytest.mark.parametrize("knobs,word", [
    ({"spec_draft_tokens": 2}, "spec_draft_tokens > 0"),
    ({"fused_step": True}, "fused_step"),
    ({"spill_enabled": True, "host_tier_bytes": 1 << 20}, "spill_enabled"),
])
def test_what_a_state_cannot_undo_is_refused_at_construction(params, knobs, word):
    with pytest.raises(ValueError, match=f"{word} is not available for JambaDecode.*state layers keep a state a lane"):
        serving(params, **knobs)


@pytest.mark.parametrize("kv", ["int8", "fp8_e4m3"])
def test_a_quantized_pool_is_refused(params, kv):
    with pytest.raises(NotImplementedError, match="state has no quantized form"):
        serving(params, kv_cache_dtype=kv)


@pytest.mark.parametrize("mode", MODES)
def test_the_dense_path_refuses_draft_and_verify_and_generates(fam, params, mode, monkeypatch):
    """(The dense cache has no null slot: under the step kernel every slot is
    a live lane's, slot 0 too.)"""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    eng = engine(params, max_batch=2)
    with pytest.raises(ValueError, match="not available for JambaDecode.*rejected draft"):
        SpeculativeDecoder(eng, eng, gamma=2).generate([1, 2, 3], 4)
    prompt = prompts_of(np.random.default_rng(41), (21,))[0]      # 21 rows in a bucket of 32: the length reaches the model
    got = eng.generate([prompt], GenerationConfig(max_new_tokens=6))
    assert isinstance(eng.cache, HybridCache) and eng.cache.rows.k.shape[:3] == (2, 2, 96)
    assert got.sequences[0] == reference_tokens(fam, params, prompt, 6)


# ---------------------------------------------------------------------------
# logits against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 16], ids=["whole-prompt", "chunked"])
def test_the_checks_prefill_then_decode_logits_match_the_reference(fam, params, chunk):
    """``pctx`` over the whole prompt, or ``pctx`` + ``psfx`` chunks, then
    ``pdecode`` steps, as ``benchmarks/check.py`` drives them (it passes no
    length and no slot: every piece a whole rung, the slot the first block's)."""
    srv = serving(params)
    rng = np.random.default_rng(5)
    prompt, fed = rng.integers(1, 256, 48).tolist(), rng.integers(1, 256, 6).tolist()
    sizes = {**SIZES, "prefill_chunk_tokens": chunk, "prefill_buckets": [16, 48]}
    got = check.paged_logits(srv, srv.engine.params, srv.model.init_paged_cache(5, BS), prompt, fed, sizes)
    np.testing.assert_allclose(got, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("n_prompt", [37, 16, 5], ids=["padded-last-chunk", "one-whole-chunk", "under-a-bucket"])
def test_chunks_a_padded_last_one_and_an_idle_lane_match_the_reference(fam, params, n_prompt):
    """37 = 16 + 16 + 5 rows in a bucket of 8: two chunk boundaries inside the
    prompt and a padded last chunk; the request on lane 1 of three, lanes 0
    and 2 idle beside it on the null table — whose slots (a parked state, a
    lane's mid-prefill one) must come back bit for bit."""
    model = decode_model_for(TINY)
    rng = np.random.default_rng(n_prompt)
    prompt, fed = rng.integers(1, 256, n_prompt).tolist(), rng.integers(1, 256, 5).tolist()
    pool = jax.tree.map(
        lambda a: 0.1 * jax.random.normal(jax.random.key(a.ndim), a.shape, a.dtype),
        model.init_paged_cache(8, BS, state_blocks=4))
    # a slot's tail holds its 3 x 128 values in 16 rows of lanes; the places past them are never written but with zeros
    tail = pool.state.tail.reshape(3, 4, -1).at[..., 3 * 128:].set(0).reshape(pool.state.tail.shape)
    pool = pool._replace(state=pool.state._replace(tail=tail))
    got, after = as_the_engine_runs_it(model, params, pool, prompt, fed, lane=1)
    np.testing.assert_allclose(got, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)
    for leaf, leaf_after in zip(pool.state, after.state):
        for slot in (1, 3):                             # lanes 0 and 2
            assert bool((leaf_after[:, slot] == leaf[:, slot]).all())
        assert float(jnp.abs(leaf_after[:, 2] - leaf[:, 2]).max()) > 0


def test_a_second_request_through_the_same_slot_and_blocks_matches_the_reference(fam, params):
    model = decode_model_for(TINY)
    first, second = prompts_of(np.random.default_rng(13), (44, 21))
    fed = [7, 11, 13, 17]
    _, pool = as_the_engine_runs_it(model, params, model.init_paged_cache(8, BS, state_blocks=4), first, fed)
    assert float(jnp.abs(pool.state.h[:, 1]).max()) > 0                # the slot is not zero
    got, _ = as_the_engine_runs_it(model, params, pool, second, fed)
    np.testing.assert_allclose(got, reference_logits(fam, params, second + fed), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("fault", ["no_carry", "no_tail", "no_reset", "rotary", "no_b_norm"])
def test_each_planted_fault_fails_the_comparison(fam, params, fault, monkeypatch):
    """The faults ``benchmarks/tools/check_ssm_variant.py`` plants on the chip,
    here against every row: each moves some row by 0.5 % (the rotary table) to 11-88 % (the others),
    where a sound run holds every row inside a hundredth of a percent. (``no_reset`` shows only through a slot that was used before.)"""
    for owner, name, value in _tool("check_ssm_variant").FAULTS[fault]():
        monkeypatch.setattr(owner, name, value)
    model = decode_model_for(TINY)
    first, second = prompts_of(np.random.default_rng(13), (44, 37))
    fed = [7, 11, 13, 17]
    pool = model.init_paged_cache(8, BS, state_blocks=4)
    _, pool = as_the_engine_runs_it(model, params, pool, first, fed)
    got, _ = as_the_engine_runs_it(model, params, pool, second, fed)
    want = reference_logits(fam, params, second + fed)
    error = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert error.max() > 30 * TOL, (fault, error.max())


def test_the_benchmarks_check_passes(fam, params):
    """(A state pool in bfloat16 failing it by the cache's tolerance is
    ``tests/benchmarks/test_bench_rehearsal_jamba.py``'s, through the tool.)"""
    spec_ = {"prompt_tokens": 48, "decode_steps": 4, "tolerance": TOL, "cache_tolerance": TOL}
    srv = serving(params)
    got = check.serving_engine(srv, fam, TINY, spec_, SIZES, seed=3)
    assert got["ok"] and got["engine_tokens"]["near_reference_max"] == 1.0, got
    assert got["all_rows"]["max"] < TOL and got["cache"]["plain_pool_is_own"]
    clean(srv)


# ---------------------------------------------------------------------------
# tokens through the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loop", LOOPS)
def test_chunked_padded_prompts_give_the_references_tokens(fam, params, loop):
    """Six requests on four lanes (so two run through used slots and blocks),
    prompts whose last chunk fills none of the buckets, continuous batching,
    look-ahead and drained steps alike."""
    prompts = prompts_of(np.random.default_rng(3), (37, 21, 5, 50, 16, 33))
    srv = serving(params, new_tokens=8, policy=loop_policy(loop))
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == reference_tokens(fam, params, prompt, 8), (rid, len(prompt))
    m = srv.metrics
    assert m.prefill_chunks > 0 and m.state_resets == len(prompts) and m.state_kernel_steps == 0
    assert (m.decode_steps_async > 0) == (loop == "lookahead")
    clean(srv)


def test_preempt_and_resume_rebuild_the_state_by_prefill(fam, params):
    """A request taken off its lane in the middle of decoding re-prefills
    prompt + output from the zero state on whatever lane it gets; one taken
    in the middle of a chunked prefill starts over."""
    long, short = prompts_of(np.random.default_rng(21), (45, 12))
    srv = serving(params, new_tokens=10)
    r_long, r_short = srv.submit(long), srv.submit(short)
    for _ in range(2):
        srv.step()
    victim = srv._requests[r_long]
    assert victim.prefilling
    srv._drain_pending() if srv._pending is not None else None
    srv._preempt(victim)
    while len(srv._requests[r_short].out) < 4:
        srv.step()
    if srv._pending is not None:
        srv._drain_pending()
    srv._preempt(srv._requests[r_short])
    out = srv.run_to_completion()
    assert srv.metrics.preemptions == 2 and srv.metrics.state_resets == 4
    assert out[r_long] == reference_tokens(fam, params, long, 10)
    assert out[r_short] == reference_tokens(fam, params, short, 10)
    clean(srv)


def test_a_common_start_shares_nothing(fam, params):
    rng = np.random.default_rng(31)
    start = rng.integers(1, 256, 40).tolist()
    p1, p2 = start + rng.integers(1, 256, 9).tolist(), start + rng.integers(1, 256, 23).tolist()
    srv = serving(params, new_tokens=8)
    assert srv.paged.enable_prefix_caching          # the default stays on; the engine asks the model
    r1 = srv.submit(p1)
    srv.run_to_completion()
    r2, r3 = srv.submit(p2), srv.submit(p1)          # a whole repeat shares nothing either
    out = srv.run_to_completion()
    for rid, prompt in ((r2, p2), (r3, p1)):
        assert out[rid] == reference_tokens(fam, params, prompt, 8)
        assert srv.request_info(rid)["cached_tokens"] == 0
    assert srv.allocator.cow_copies == 0 and srv.index.hit_tokens == 0 and srv.index.match(p1) == (0, [])
    clean(srv)


@pytest.mark.parametrize("mode", MODES)
def test_a_traced_engine_records_both_kinds_and_the_slots_a_pass_moves(params, mode, monkeypatch):
    """``reference``: the pass moves every slot, a live lane's or not, and no
    dispatch holds the kernel. ``interpret`` (named before the first trace):
    the step kernel visits the live lanes' slots alone, and every ``pdecode``
    is counted."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    kernel = mode == "interpret"
    srv = serving(params, trace_enabled=True, prewarm=True)
    for p in prompts_of(np.random.default_rng(2), (20, 33)):
        srv.submit(p)
    srv.run_to_completion()
    setup = srv.tracer.timeline()["setup"]
    assert setup["state_bytes_per_lane"] == STATE_BYTES and setup["cache_row_bytes"] == 2 * 16 * 4
    assert setup["window_ring_rows"] == 0 and setup["program_temp_bytes_max"] > 0
    assert setup["cache_kinds"] == {
        "rows": {"layers": 2, "rows_per_lane": None, "row_bytes": 2 * 16 * 4, "decode_read": "gather"},
        "state": {"layers": 3, "rows_per_lane": 0, "state_bytes": STATE_BYTES,
                  "chunk_scan": "kernel" if kernel else "loop", "decode_read": STATE_READ[mode]},
    }
    records = [args for step in srv.tracer.timeline()["steps"] for ph, name, _, _, args in step["events"]
               if ph == "X" and name == "dispatch"]
    assert records and all(a["state_lanes"] == a["lanes"] for a in records)
    assert all(a["state_slots_passed"] == (a["state_lanes"] if kernel else 1 + 4) for a in records)
    assert all(a["rows"] >= a["lanes"] for a in records)                 # the attention layers' live rows
    snap = srv.metrics.snapshot()
    assert snap["state_resets"] == 2
    assert snap["state_kernel_steps"] == (snap["decode_steps"] if kernel else 0) and snap["decode_steps"] > 0


def test_the_engines_tokens_through_the_step_kernel_are_the_reference_modes(params, monkeypatch):
    """Six requests on four lanes — idle lanes beside live ones, lanes
    mid-prefill beside the batch, two requests through used slots — in the
    ``interpret`` mode (the chunk scan and the step kernel) and in the
    ``reference`` mode (the loop a row and the pass over every slot): the same
    tokens."""
    prompts = prompts_of(np.random.default_rng(3), (37, 21, 5, 50, 16, 33))
    tokens = {}
    for mode in MODES:
        monkeypatch.setenv(KERNEL_MODE_ENV, mode)
        srv = serving(params, new_tokens=8)
        rids = [srv.submit(p) for p in prompts]
        out = srv.run_to_completion()
        tokens[mode] = [out[r] for r in rids]
        steps = srv.metrics.snapshot()
        assert steps["state_kernel_steps"] == (steps["decode_steps"] if mode == "interpret" else 0)
        clean(srv)
    assert tokens["interpret"] == tokens["reference"]


def test_the_audit_holds_the_lanes_slots(params):
    srv = serving(params)
    assert audit_engine(srv) == []
    srv._lane_tables = srv._lane_tables.copy()
    srv._lane_tables[2, 0] = 1                      # two lanes on one slot
    assert any("state kind" in v and "do not name each" in v for v in audit_engine(srv))
