"""Compiled-program catalog: ladder units, manifest expansion, prewarm.

The contract under test (serving/catalog.py + PagedConfig.prewarm): a
:class:`BucketLadder` declares every shape the engine may pad a dispatch
into, :class:`CatalogManifest` expands ladder x variant flags into the
exact legal ``_programs`` key set, ``prewarm=True`` compiles the whole
manifest before traffic and freezes the registry — after which an
arbitrarily heterogeneous workload must compile NOTHING
(``metrics.steadystate_compiles == 0``, graftcheck GC008) and hold no
key outside the manifest (GC007).
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.analysis import graftcheck as gc
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.inference import engine as inf_engine
from neuronx_distributed_llama3_2_tpu.inference.sampling import SamplingConfig
from neuronx_distributed_llama3_2_tpu.models import model_registry
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
)
from neuronx_distributed_llama3_2_tpu.serving import catalog as cat

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)
GREEDY = SamplingConfig()


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _engine(params, *, prewarm=False, **paged_kw):
    """Smallest real catalog: prefill/kv ladders both [8, 16]."""
    return PagedServingEngine(
        InferenceEngine(
            TINY_KERNEL, params, max_batch=2, max_seq_len=16, buckets=[8],
        ),
        GenerationConfig(max_new_tokens=4),
        PagedConfig(block_size=8, num_blocks=16, prewarm=prewarm, **paged_kw),
    )


# ------------------------------------------------------------ ladder units


def test_default_buckets_powers_of_two():
    assert cat.default_buckets(64, min_bucket=8) == [8, 16, 32, 64]
    assert cat.default_buckets(100, min_bucket=128) == [100]


def test_pick_bucket_smallest_covering():
    assert cat.pick_bucket([8, 16, 64], 1) == 8
    assert cat.pick_bucket([8, 16, 64], 16) == 16
    assert cat.pick_bucket([8, 16, 64], 17) == 64
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        cat.pick_bucket([8, 16], 20)


def test_inference_engine_reexports_delegate():
    """The historical inference.engine import path must keep working and
    agree with the canonical serving/catalog.py implementation."""
    assert inf_engine.default_buckets(64, 8) == cat.default_buckets(64, 8)
    assert inf_engine.pick_bucket([8, 16], 9) == cat.pick_bucket([8, 16], 9)


def test_complete_ladder_appends_max_seq_len():
    assert cat.complete_ladder([8], 64) == [8, 64]
    assert cat.complete_ladder([8, 64], 64) == [8, 64]


@pytest.mark.parametrize(
    "buckets, msg",
    [
        ([], "must not be empty"),
        ([0, 8], "must be positive"),
        ([16, 8], "strictly ascending"),
        ([8, 8], "strictly ascending"),
        ([128], "exceeds max_seq_len"),
    ],
)
def test_complete_ladder_rejects_malformed(buckets, msg):
    with pytest.raises(ValueError, match=msg):
        cat.complete_ladder(buckets, 64)


@pytest.mark.parametrize("buckets,max_len,longest,want", [
    # a chunk set: the ladder ends at the rung that holds the chunk
    ([128, 512], 8704, 512, [128, 512]),
    ([128], 8704, 512, [128, 512]),             # the chunk itself where the rungs top out under it
    ([128, 512], 8704, 300, [128, 512]),        # the smallest declared rung >= the chunk
    ([128, 512, 2048], 8704, 512, [128, 512]),  # rungs no dispatch can ask for are dropped
    ([16], 64, 64, [16, 64]),
    ([8], 64, 100, [8, 64]),                    # never past max_seq_len
    # no chunk: max_seq_len appended as before
    ([8], 64, None, [8, 64]),
    ([8, 64], 64, None, [8, 64]),
    ([128, 512], 8704, None, [128, 512, 8704]),
])
def test_complete_ladder_ends_at_the_longest_dispatch(buckets, max_len, longest, want):
    assert cat.complete_ladder(buckets, max_len, longest) == want
    # the contract: every dispatch length up to the longest routes to a rung
    top = min(longest or max_len, max_len)
    assert cat.pick_bucket(want, top) == want[-1] and cat.pick_bucket(want, 1) == want[0]


def test_bucket_ladder_routing():
    lad = cat.BucketLadder(
        decode_batch=4, max_seq_len=64,
        prefill_buckets=(8, 16, 64), kv_buckets=(8, 16, 64),
    )
    assert lad.kv_bucket(1) == 8
    assert lad.kv_bucket(8) == 8
    assert lad.kv_bucket(9) == 16
    assert lad.kv_bucket(65) == 64  # clamped to the full cache
    assert lad.prefill_bucket(0) == 8  # empty suffix still pads to a rung
    assert lad.prefill_bucket(17) == 64


def test_suffix_pairs_reachable_kv_limits_only():
    """psfx carries kv_limit = kv_bucket(min(cached + bucket, max)) with
    cached >= 1 — rungs below that floor are unreachable and must not be
    in the manifest (they would be dead prewarmed programs)."""
    lad = cat.BucketLadder(
        decode_batch=4, max_seq_len=64,
        prefill_buckets=(8, 16, 64), kv_buckets=(8, 16, 64),
    )
    assert lad.suffix_pairs() == [(8, 16), (8, 64), (16, 64), (64, 64)]


# ------------------------------------------------------ manifest expansion


def test_manifest_expansion_hand_checked(params):
    eng = _engine(params)
    assert eng.catalog.keys() == {
        ("copy_block", False), ("lane_set",), ("table_delta",),
        ("pctx", 8, GREEDY, False), ("pctx", 16, GREEDY, False),
        ("psfx", 8, 16, GREEDY, False), ("psfx", 16, 16, GREEDY, False),
        ("pdecode", GREEDY, 8, False, False),
        ("pdecode", GREEDY, 16, False, False),
    }


def test_manifest_spec_adds_verify_widths(params):
    eng = _engine(params, spec_draft_tokens=2)
    extra = eng.catalog.keys() - _engine(params).catalog.keys()
    assert extra == {
        ("pverify", 8, 2, False, False), ("pverify", 16, 2, False, False),
    }


def test_manifest_fused_step_shrinks_expansion(params):
    """The GC007 fused-shrink contract: fused_step swaps the psfx
    suffix-pair product for one pmixed rung per kv bucket, so the
    manifest must be STRICTLY smaller than the unfused expansion on the
    same ladder (the gate's catalog-fused entry asserts the same
    relation on the full int8 configuration)."""
    lad = cat.BucketLadder(
        decode_batch=4, max_seq_len=64,
        prefill_buckets=(8, 16, 64), kv_buckets=(8, 16, 64),
        verify_t=(4,), mixed_t=(6,),
    )
    fused = cat.CatalogManifest(ladder=lad, sampling=GREEDY, fused_step=True)
    unfused = cat.CatalogManifest(ladder=lad, sampling=GREEDY)
    fk, uk = fused.keys(), unfused.keys()
    assert not any(k[0] == "psfx" for k in fk)
    assert {k for k in fk if k[0] == "pmixed"} == {
        ("pmixed", 6, 8, GREEDY, False, False),
        ("pmixed", 6, 16, GREEDY, False, False),
        ("pmixed", 6, 64, GREEDY, False, False),
    }
    # 4 suffix pairs leave, 3 pmixed rungs arrive: strictly smaller
    assert len(fk) < len(uk)
    # everything else is shared — the shrink is pure psfx-for-pmixed
    assert {k for k in fk if k[0] != "pmixed"} == {
        k for k in uk if k[0] not in ("psfx", "pmixed")
    }
    # a small TINY engine pair shows the same routing end to end
    feng = _engine(
        params, fused_step=True, prefill_chunk_tokens=4,
        spec_draft_tokens=2,
    )
    assert not any(k[0] == "psfx" for k in feng.catalog.keys())
    assert any(k[0] == "pmixed" for k in feng.catalog.keys())


def test_manifest_gather_variants_legal_but_not_prewarmed(params):
    """degrade_after_faults arms the kernel-shed ladder: gather twins
    become LEGAL keys (GC007) but prewarm never compiles them (GC006
    forbids gather programs on a never-degraded engine)."""
    eng = _engine(params, degrade_after_faults=1)
    keys = eng.catalog.keys()
    assert ("pdecode", GREEDY, 8, True, False) in keys
    base = {k for k in keys if not _is_gather(k)}
    assert base == _engine(params).catalog.keys()
    warm = eng.catalog.prewarm_keys()
    assert not any(_is_gather(k) for k in warm)
    assert set(warm) == base


def _is_gather(key):
    kind = key[0]
    if kind in ("pctx", "psfx"):
        return key[-1]
    if kind in ("pdecode", "pverify"):
        return key[-2]
    return False


def test_ladder_override_knobs(params):
    eng = _engine(params, kv_buckets=(4, 16), prefill_buckets=(8,))
    assert eng._kv_buckets == [4, 16]
    assert eng._prefill_buckets == [8, 16]
    assert eng.catalog.ladder.kv_buckets == (4, 16)
    assert eng.catalog.ladder.prefill_buckets == (8, 16)


def test_catalog_describe_mentions_size(params):
    eng = _engine(params)
    assert f"{len(eng.catalog.keys())} keys" in eng.catalog.describe()


# ----------------------------------------------------------- key rendering


def test_format_key_house_style():
    assert cat.format_key(("lane_set",)) == "lane_set"
    assert cat.format_key(("copy_block", True)) == "copy_block[quantized=True]"
    assert (
        cat.format_key(("pdecode", GREEDY, 16, False, False))
        == "pdecode[kv_limit=16,cfg=greedy]"
    )
    assert (
        cat.format_key(("pdecode", GREEDY, 16, True, True))
        == "pdecode[kv_limit=16,cfg=greedy,gather,checked]"
    )
    assert (
        cat.format_key(("pverify", 16, 4, False, False))
        == "pverify[kv_limit=16,k=4]"
    )
    sampled = SamplingConfig(greedy=False, temperature=0.8, top_k=40)
    assert (
        cat.format_key(("psfx", 8, 16, sampled, False))
        == "psfx[bucket=8,kv_limit=16,cfg=T0.8-k40]"
    )


def test_nearest_key_ranks_by_bucket_distance(params):
    legal = _engine(params).catalog.keys()
    near = cat.nearest_key(("pdecode", GREEDY, 13, False, False), legal)
    assert near == "pdecode[kv_limit=16,cfg=greedy]"
    assert cat.nearest_key(("no_such_kind", 3), legal) is None


def test_catalog_file_roundtrip(tmp_path, params):
    path = str(tmp_path / "catalog.txt")
    manifest = _engine(params).catalog
    cat.write_catalog_file(path, {"a": manifest, "b": ["lane_set"]})
    back = cat.read_catalog_file(path)
    assert back == {"a": manifest.lines(), "b": ["lane_set"]}
    assert cat.read_catalog_file(str(tmp_path / "missing.txt")) == {}


def test_validate_ladder_flags_oversize_verify_width():
    class _Model:
        def paged_dispatch_path(self, t, tree=None):
            return "kernel" if t <= 4 else "gather"

    lad = cat.BucketLadder(
        decode_batch=4, max_seq_len=64,
        prefill_buckets=(8,), kv_buckets=(8,), verify_t=(8,),
    )
    (warning,) = cat.validate_ladder(_Model(), lad)
    assert "verify_t=8" in warning
    ok = dataclasses.replace(lad, verify_t=(3,))
    assert cat.validate_ladder(_Model(), ok) == []
    assert cat.validate_ladder(object(), lad) == []  # duck-typed: no hook


def test_validate_ladder_flags_oversize_mixed_width():
    class _Model:
        def paged_dispatch_path(self, t, tree=None):
            return "kernel" if t <= 4 else "gather"

    lad = cat.BucketLadder(
        decode_batch=4, max_seq_len=64,
        prefill_buckets=(8,), kv_buckets=(8,), mixed_t=(8,),
    )
    (warning,) = cat.validate_ladder(_Model(), lad)
    assert "mixed_t=8" in warning
    ok = dataclasses.replace(lad, mixed_t=(4,))
    assert cat.validate_ladder(_Model(), ok) == []


# ------------------------------------------------------- prewarm contract


def test_prewarm_compiles_exactly_the_manifest(params):
    eng = _engine(params, prewarm=True)
    assert set(eng.program_registry()) == eng.catalog.keys()
    assert eng.metrics.programs_compiled == len(eng.catalog.keys())
    assert eng.metrics.steadystate_compiles == 0
    # every program actually dispatched during prewarm (avals recorded),
    # so the full registry is auditable and lower()-able
    assert all(
        rec.example_args is not None
        for rec in eng.program_registry().values()
    )
    assert eng._frozen_keys == frozenset(eng.program_registry())
    assert gc.audit_programs(eng) == []


def test_prewarm_keeps_uploads_at_zero(params):
    """Prewarm feeds programs device-constructed arrays — it must not
    count as host->device traffic (h2d_uploads is a serving SLO)."""
    eng = _engine(params, prewarm=True)
    assert eng.metrics.h2d_uploads == 0


def test_first_request_hits_only_prewarmed_programs(params):
    eng = _engine(params, prewarm=True)
    before = eng.metrics.programs_compiled
    eng.submit([1, 2, 3, 4, 5])
    out = eng.run_to_completion()
    assert len(out[0]) == 4
    assert eng.metrics.programs_compiled == before
    assert eng.metrics.steadystate_compiles == 0


def test_frozen_registry_across_mixed_workload(params):
    """Heterogeneous traffic (every prompt length a different pad) on a
    prewarmed engine compiles nothing: the registry stays byte-identical
    to the manifest and GC007/GC008 stay quiet."""
    eng = _engine(params, prewarm=True)
    frozen = set(eng.program_registry())
    rng = np.random.default_rng(7)
    for wave in ((2, 5), (7, 11), (3, 9), (1, 10)):
        for n in wave:
            eng.submit(
                rng.integers(0, TINY.vocab_size, size=(n,)).tolist()
            )
        eng.run_to_completion()
    assert eng.metrics.finished == 8
    assert eng.metrics.decode_steps >= 12
    assert set(eng.program_registry()) == frozen == eng.catalog.keys()
    assert eng.metrics.steadystate_compiles == 0
    assert gc.audit_programs(eng) == []


def test_out_of_catalog_compile_is_caught(params):
    """The smuggle case the whole contract exists for: a compile the
    ladder does not cover fires GC007 (and, post-freeze, GC008)."""
    eng = _engine(params, prewarm=True)
    eng._decode_program(eng.gen.sampling, 12)  # no such rung
    rules = sorted(f.rule for f in gc.audit_programs(eng))
    # GC009 rides along on a cost-accounting engine: the smuggled key
    # was compiled after the prewarm harvest, so it has no CostProfile
    assert rules == ["GC007", "GC008", "GC009"]


# --------------------------------- prewarm the catalog, or compile at first dispatch


def test_an_engine_that_does_not_prewarm_builds_no_model_program(params):
    """A default ``PagedConfig`` on a four-rung ladder: construction registers
    the pool programs it builds itself and traces none of them; ``pctx``,
    ``psfx`` and ``pdecode`` wait for the dispatch that needs them."""
    eng = PagedServingEngine(
        InferenceEngine(TINY, params, max_batch=2, max_seq_len=64, buckets=[8, 16, 32, 64]),
        GenerationConfig(max_new_tokens=4), PagedConfig(),
    )
    assert eng._prefill_buckets == eng._kv_buckets == [8, 16, 32, 64]
    registry = eng.program_registry()
    assert {rec.kind for rec in registry.values()} <= {"copy_block", "block_save", "block_restore"}
    assert all(rec.example_args is None for rec in registry.values())
    assert eng.metrics.programs_compiled == len(registry) and eng._frozen_keys is None
    eng.submit([1, 2, 3])
    eng.run_to_completion()
    # one rung of each kind the request needed, of the eight the ladder declares
    kinds = [k[0] for k in eng.program_registry() if k[0] in ("pctx", "psfx", "pdecode")]
    assert sorted(kinds) == ["pctx", "pdecode"]


# the tiny preset of every family a cell serves, and Llama's as the control
FAMILIES = ("tiny", "tiny-brumby", "tiny-laguna", "tiny-moe", "tiny-olmoe", "tiny-sarvam", "tiny-xing")


@pytest.mark.parametrize("preset", FAMILIES)
def test_prewarmed_and_lazy_engines_of_every_served_family_agree(preset):
    """The tiny preset of each family a cell serves, one prefill rung and one
    kv rung: a prewarmed engine serves a short prompt beside a chunked one
    without compiling; an engine that compiles at first dispatch emits the
    same tokens from programs the prewarmed catalog holds."""
    entry = model_registry()[preset]
    cfg = dataclasses.replace(entry["config"], max_seq_len=64)
    weights = jax.jit(entry["model_cls"](cfg).init)(jax.random.key(0))
    inner = InferenceEngine(cfg, weights, max_batch=2, max_seq_len=64)
    # one block of a state pool is a whole sequence
    block_size, num_blocks = (64, 6) if preset == "tiny-brumby" else (16, 16)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=(n,)).tolist() for n in (5, 40)]

    def serve(prewarm):
        eng = PagedServingEngine(inner, GenerationConfig(max_new_tokens=4), PagedConfig(
            block_size=block_size, num_blocks=num_blocks, prefill_chunk_tokens=16,
            prefill_buckets=(16,), kv_buckets=(64,), prewarm=prewarm))
        rids = [eng.submit(p) for p in prompts]
        out = eng.run_to_completion()
        return eng, [out[r] for r in rids]

    warm, want = serve(True)
    assert set(warm.program_registry()) == warm.catalog.keys() == warm._frozen_keys
    assert warm.metrics.steadystate_compiles == 0 and warm.metrics.prefill_chunks >= 3
    lazy, got = serve(False)
    assert got == want and all(len(o) == 4 for o in got)
    dispatched = {k for k, rec in lazy.program_registry().items() if rec.example_args is not None}
    assert {k[0] for k in dispatched} >= {"pctx", "psfx", "pdecode"}
    assert dispatched <= warm.catalog.keys()
    assert lazy._frozen_keys is None and lazy.metrics.prewarm_compiles == 0


# ------------------------------------- the ladder of an engine that chunks

TRAFFIC = ("chat-steady", "docs-batch", "rag-batch", "prefix-pressure", "docqa-batch", "longgen-batch")


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_a_cells_catalog_holds_no_prefill_rung_above_its_chunks(params, traffic):
    """The engine sizes of each serving cell (on the tiny model: a ladder is
    lengths, not widths): no ``pctx`` / ``psfx`` rung above the chunk's, every
    ``kv`` rung the old ladder held, and a table whose overflow region is the
    top prefill rung's."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "traffic", f"{traffic}.json")) as fh:
        sizes = json.load(fh)["engine"]
    chunk, bs, max_len = sizes["prefill_chunk_tokens"], sizes["block_size"], sizes["max_seq_len"]
    eng = PagedServingEngine(
        InferenceEngine(TINY, params, max_batch=sizes["lanes"], max_seq_len=max_len),
        GenerationConfig(max_new_tokens=4),
        PagedConfig(
            block_size=bs, num_blocks=min(sizes["pool_blocks"], 32),
            prefill_chunk_tokens=chunk, prefill_buckets=tuple(sizes["prefill_buckets"]),
            kv_buckets=tuple(sizes["kv_buckets"]),
        ),
    )
    rung = cat.pick_bucket(sorted({*sizes["prefill_buckets"], chunk}), chunk)
    assert eng._prefill_buckets[-1] == rung == 512 < max_len
    keys = eng.catalog.keys()
    prefill = [k for k in keys if k[0] in ("pctx", "psfx")]
    assert prefill and max(k[1] for k in prefill) == rung
    kv_rungs = cat.complete_ladder(sizes["kv_buckets"], max_len)
    assert kv_rungs[-1] == max_len
    assert sorted({k[2] for k in keys if k[0] == "pdecode"}) == kv_rungs
    assert {k[2] for k in keys if k[0] == "psfx"} == {
        kv for b in eng._prefill_buckets for kv in kv_rungs if kv >= eng._kv_bucket(min(1 + b, max_len))
    }
    assert eng.table_width == -(-max_len // bs) + -(-rung // bs)
    assert set(eng.catalog.prewarm_keys()) == keys


def test_an_unchunked_engine_keeps_its_whole_prompt_rung(params):
    eng = _engine(params)
    assert eng._prefill_buckets == [8, 16] and eng.table_width == 2 + 2
    assert ("pctx", 16, GREEDY, False) in eng.catalog.keys()


def test_a_fused_step_wider_than_the_chunk_sizes_the_ladder(params):
    """chunk 4 under speculation k = 6: the mixed program is 7 rows wide, and
    the table's overflow region has to take them past the sequence cap."""
    eng = _engine(params, fused_step=True, prefill_chunk_tokens=4, spec_draft_tokens=6)
    assert eng._mixed_t == 7 and eng._prefill_buckets == [8]
    assert eng.table_width * 8 >= 16 + eng._mixed_t


def test_a_chunked_engine_dispatches_catalog_keys_alone(params):
    """Fresh, cached-suffix and preempt-resume admissions on a prewarmed
    engine whose ladder ends at the chunk's rung: every dispatch finds its
    program, nothing compiles, nothing is off the catalog."""
    eng = PagedServingEngine(
        InferenceEngine(TINY_KERNEL, params, max_batch=2, max_seq_len=64, buckets=[8, 16, 64]),
        GenerationConfig(max_new_tokens=6),
        PagedConfig(block_size=8, num_blocks=32, prewarm=True, prefill_chunk_tokens=8),
    )
    assert eng._prefill_buckets == [8] and eng._kv_buckets == [8, 16, 64]
    frozen = set(eng.program_registry())
    assert frozen == eng.catalog.keys()
    assert not any(k[0] in ("pctx", "psfx") and k[1] > 8 for k in frozen)
    rng = np.random.default_rng(11)
    shared = rng.integers(1, TINY.vocab_size, size=(24,)).tolist()

    def tail(n):
        return rng.integers(1, TINY.vocab_size, size=(n,)).tolist()

    fresh = eng.submit(shared + tail(13))          # 37 rows: five chunks from the zero cache
    eng.run_to_completion()
    cached = eng.submit(shared + tail(20))         # 24 rows matched, a chunked suffix of 20
    short = eng.submit(shared + tail(3))           # 24 matched, one psfx of 3 in the rung of 8
    eng.run_to_completion()
    assert eng.request_info(cached)["cached_tokens"] == 24 == eng.request_info(short)["cached_tokens"]
    victim = eng.submit(tail(30))                  # preempted mid-decode: re-prefills prompt + output
    while len(eng._requests[victim].out) < 3:
        eng.step()
    if eng._pending is not None:
        eng._drain_pending()
    eng._preempt(eng._requests[victim])
    out = eng.run_to_completion()
    assert len(out[fresh]) == len(out[victim]) == 6 and eng.metrics.preemptions == 1
    assert eng.metrics.prefill_chunks >= 5 + 3 + 4
    assert set(eng.program_registry()) == frozen
    assert eng.metrics.steadystate_compiles == 0
    assert gc.audit_programs(eng) == []
