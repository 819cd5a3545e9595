"""graftcheck: per-rule firing fixtures + baseline/suppression machinery
+ the program-catalog gate.

Every GC rule gets a deliberately-violating synthetic program proving it
fires (a gathering decode twin, a jit whose donation is dropped, a
shard_map body with a stray psum, an int8 dot without widening, a
fault-free engine holding a checked program key) and a clean twin proving
it stays quiet. ``test_self_audit`` is the CI gate itself: the real
program catalog (engine registry + decode/verify/tp=2/int8 traces) must
stay clean — or explicitly baselined — under the analyzer.
"""

import dataclasses
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.analysis import graftcheck as gc
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
)

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _decode_trace(cfg, params, b=4, kv_limit=32):
    model = LlamaDecode(cfg)
    cache = model.init_paged_cache(16, 8)
    closed = jax.make_jaxpr(
        lambda p, c, t, ps, tb: model.decode_step(
            p, c, t, ps, tb, kv_limit=kv_limit, pos_cap=63
        )
    )(
        params, cache, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, 8), jnp.int32),
    )
    return model, closed


# ---------------------------------------------------------------- GC001


def test_gc001_fires_on_gathering_decode_twin(params):
    """The use_paged_kernel=False twin materializes the gathered-KV copy;
    GC001 must name the offending shape."""
    model, closed = _decode_trace(TINY, params)
    forbidden = model.forbidden_gather_shapes(4, 32)
    fs = gc.check_no_gather(closed, forbidden, "gather-twin")
    assert [f.rule for f in fs] == ["GC001"]
    assert str((4, 32, TINY.num_kv_heads, TINY.head_dim)) in fs[0].message


def test_gc001_quiet_on_kernel_path(params):
    model, closed = _decode_trace(TINY_KERNEL, params)
    assert gc.check_no_gather(
        closed, model.forbidden_gather_shapes(4, 32), "kernel"
    ) == []


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_gc001_on_a_latent_decode_follows_the_read_it_holds(mode, monkeypatch):
    """A latent model's ``pdecode``: the gather's twin (the ``"reference"``
    kernel mode) materializes the (lanes, rung, pool row) copy and GC001 names
    it; where the block walk runs the trace holds no such array."""
    from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
    from neuronx_distributed_llama3_2_tpu.models.sarvam import SARVAM_CONFIGS, SarvamForCausalLM

    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    tiny = dataclasses.replace(SARVAM_CONFIGS["tiny-sarvam"], max_seq_len=64)
    model = decode_model_for(tiny)
    latent_params = jax.eval_shape(SarvamForCausalLM(tiny).init, jax.random.key(0))
    pool = jax.eval_shape(lambda: model.init_paged_cache(16, 8))
    zeros = jnp.zeros((4,), jnp.int32)
    closed = jax.make_jaxpr(lambda p, c: model.decode_step(
        p, c, zeros, zeros, jnp.zeros((4, 8), jnp.int32), kv_limit=32, pos_cap=63))(latent_params, pool)
    fs = gc.check_no_gather(closed, model.forbidden_gather_shapes(4, 32), "latent-pdecode")
    if mode == "reference":
        assert [f.rule for f in fs] == ["GC001"] and str((4, 32, 128)) in fs[0].message
    else:
        assert fs == []


# ---------------------------------------------------------------- GC002


def test_gc002_fires_when_donation_dropped():
    """No output matches the donated buffer's shape/dtype, so jax drops
    the donation at lowering — exactly the silent perf cliff GC002 exists
    to surface."""
    f = jax.jit(lambda c: c[1:] * 2.0, donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = f.lower(jax.ShapeDtypeStruct((8,), jnp.float32))
    fs = gc.check_donation(lowered, donated_leaves=1, program="dropped")
    assert [f_.rule for f_ in fs] == ["GC002"]
    assert "alias" in fs[0].message


def test_gc002_quiet_when_donation_holds():
    f = jax.jit(lambda c: c.at[0].set(1.0), donate_argnums=(0,))
    lowered = f.lower(jax.ShapeDtypeStruct((8,), jnp.float32))
    assert gc.check_donation(lowered, donated_leaves=1, program="held") == []


# ---------------------------------------------------------------- GC003


def test_gc003_fires_on_device_put_and_callback():
    closed = jax.make_jaxpr(lambda x: jax.device_put(x) + 1.0)(jnp.ones(3))
    fs = gc.check_host_transfers(closed, "uploads")
    assert [f.rule for f in fs] == ["GC003"]
    assert "device_put" in fs[0].detail

    def cb(x):
        jax.debug.print("x={x}", x=x)
        return x * 2.0

    closed = jax.make_jaxpr(cb)(jnp.ones(3))
    assert any(
        "debug_print" in f.detail
        for f in gc.check_host_transfers(closed, "cb")
    )

    def dbg(x):
        jax.debug.callback(lambda y: None, x)
        return x * 2.0

    closed = jax.make_jaxpr(dbg)(jnp.ones(3))
    assert any(
        "debug_callback" in f.detail
        for f in gc.check_host_transfers(closed, "dbg")
    )


def test_gc003_quiet_on_pure_compute(params):
    _model, closed = _decode_trace(TINY_KERNEL, params)
    assert gc.check_host_transfers(closed, "decode") == []


# ---------------------------------------------------------------- GC004


def _psum_region_trace(axis="tp"):
    mesh = Mesh(np.array(jax.devices()[:1]), (axis,))
    body = jax.shard_map(
        lambda x: jax.lax.psum(x, axis), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False,
    )
    return jax.make_jaxpr(body)(jnp.ones((4,)))


def test_gc004_fires_on_collective_inside_region():
    fs = gc.check_collectives(_psum_region_trace(), "region")
    assert [f.rule for f in fs] == ["GC004"]
    assert "shard_map" in fs[0].message


def test_gc004_fires_on_undeclared_axis():
    fs = gc.check_collectives(
        _psum_region_trace(axis="rogue"), "rogue",
        collective_free_regions=False,
    )
    assert [f.rule for f in fs] == ["GC004"]
    assert "rogue" in fs[0].message


def test_gc004_quiet_on_declared_axis_outside_free_region():
    assert gc.check_collectives(
        _psum_region_trace(), "ok", collective_free_regions=False
    ) == []


# ---------------------------------------------------------------- GC005


def test_gc005_fires_on_bf16_widen():
    x8 = jnp.ones((4, 4), jnp.int8)
    w = jnp.ones((4, 4), jnp.bfloat16)
    closed = jax.make_jaxpr(lambda a, b: a.astype(jnp.bfloat16) @ b)(x8, w)
    fs = gc.check_fp32_widening(closed, "bf16-widen")
    assert [f.rule for f in fs] == ["GC005"]
    assert "float32" in fs[0].message


def test_gc005_fires_on_non_fp32_dot():
    x8 = jnp.ones((4, 4), jnp.int8)
    closed = jax.make_jaxpr(
        lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    )(x8, x8)
    fs = gc.check_fp32_widening(closed, "int32-dot")
    assert [f.rule for f in fs] == ["GC005"]
    assert "dot_general" in fs[0].detail


def test_gc005_quiet_on_fp32_widen_and_structural_moves():
    x8 = jnp.ones((4, 4), jnp.int8)
    w = jnp.ones((4, 4), jnp.bfloat16)
    closed = jax.make_jaxpr(
        lambda a, b: a[:2].reshape(2, 2, 2).astype(jnp.float32).sum()
        + b.astype(jnp.float32).sum()
    )(x8, w)
    assert gc.check_fp32_widening(closed, "clean") == []


# ------------------------------------------------- GC006 / audit_programs


def _quiet_engine(params, **paged_kw):
    """Fault-free kernel engine, nothing compiled eagerly."""
    return PagedServingEngine(
        InferenceEngine(
            TINY_KERNEL, params, max_batch=4, max_seq_len=64,
            buckets=[8, 16],
        ),
        GenerationConfig(max_new_tokens=4),
        PagedConfig(block_size=8, num_blocks=32, **paged_kw),
    )


def test_gc006_fires_on_checked_program_in_fault_free_engine(params):
    eng = _quiet_engine(params)
    assert gc.audit_programs(eng) == []
    # smuggle a checked decode variant past the _check_logits gate — the
    # registry impurity GC006 exists to catch
    eng._check_logits = True
    eng._decode_program(eng.gen.sampling, 16)
    eng._check_logits = False
    # the smuggled variant is impure (GC006) AND, since the manifest
    # mirrors the engine's real checked bit, out-of-catalog (GC007)
    fs = gc.audit_programs(eng)
    assert sorted(f.rule for f in fs) == ["GC006", "GC007"]
    (f6,) = [f for f in fs if f.rule == "GC006"]
    assert f6.detail == "checked"


def test_gc006_fires_on_gather_program_in_undegraded_engine(params):
    eng = _quiet_engine(params)
    eng._degrade_level = 3
    eng._decode_program(eng.gen.sampling, 16)
    eng._degrade_level = 0
    assert eng.metrics.degradations == 0
    # gather twins are only catalog-legal when the ladder is armed
    # (degrade_after_faults > 0) — on this engine the smuggle is both
    # impure (GC006) and out-of-catalog (GC007)
    fs = gc.audit_programs(eng)
    assert sorted(f.rule for f in fs) == ["GC006", "GC007"]
    (f6,) = [f for f in fs if f.rule == "GC006"]
    assert f6.detail == "gather"


def test_gc006_quiet_when_fault_config_legitimizes_checked(params):
    eng = _quiet_engine(params, detect_nonfinite=True)
    assert eng._check_logits
    eng._decode_program(eng.gen.sampling, 16)
    assert gc.audit_programs(eng) == []


def test_audit_programs_clean_after_real_traffic(params):
    """End-to-end: a served engine's full registry passes every rule (the
    same call every serving-suite teardown now makes)."""
    eng = _quiet_engine(params)
    rng = np.random.default_rng(0)
    for n in (5, 9):
        eng.submit(rng.integers(0, TINY.vocab_size, size=(n,)).tolist())
    eng.run_to_completion()
    kinds = {r.kind for r in eng.program_registry().values()}
    assert {"pctx", "pdecode", "lane_set"} <= kinds
    assert gc.audit_programs(eng) == []


def test_program_registry_records_metadata(params):
    eng = _quiet_engine(params)
    rec = eng._decode_program(eng.gen.sampling, 16)
    assert rec.kind == "pdecode"
    assert rec.donate_argnums == (1, 3)
    assert rec.meta["kv_limit"] == 16
    assert rec.example_args is None  # never dispatched
    with pytest.raises(ValueError, match="never dispatched"):
        rec.lower()
    # the registry returns the same record for the same key
    assert eng._decode_program(eng.gen.sampling, 16) is rec


# ------------------------------------------------- GC007 / GC008 catalog


def test_gc007_fires_on_out_of_catalog_key(params):
    """A program key whose kv_limit is not a declared ladder rung is an
    out-of-catalog compile; the finding names the nearest legal bucket."""
    eng = _quiet_engine(params)
    assert gc.audit_programs(eng) == []
    eng._decode_program(eng.gen.sampling, 13)  # 13 is no rung of [8,16,64]
    fs = gc.audit_programs(eng)
    assert [f.rule for f in fs] == ["GC007"]
    assert "kv_limit=13" in fs[0].message
    assert "pdecode[kv_limit=16" in fs[0].message  # nearest bucket named


def test_gc007_quiet_on_manifest_keys_and_suppressable(params):
    eng = _quiet_engine(params)
    eng._decode_program(eng.gen.sampling, 64)  # legal rung: quiet
    assert gc.audit_programs(eng) == []
    eng._decode_program(eng.gen.sampling, 13)
    assert gc.audit_programs(eng, suppress={"GC007"}) == []


def test_gc008_fires_on_post_freeze_registry_growth(params):
    """A key compiled after mark_steady() is flagged even when it IS in
    the manifest — the freeze is about recompile stalls, not legality."""
    eng = _quiet_engine(params)
    rng = np.random.default_rng(0)
    for n in (5, 9):
        eng.submit(rng.integers(0, TINY.vocab_size, size=(n,)).tolist())
    eng.run_to_completion()
    eng.mark_steady()
    assert gc.audit_programs(eng) == []
    eng._decode_program(eng.gen.sampling, 64)  # legal but post-freeze
    fs = gc.audit_programs(eng)
    assert [f.rule for f in fs] == ["GC008"]
    assert fs[0].detail.startswith("new:")


def test_gc008_fires_on_post_freeze_relower(params):
    """Re-dispatching a frozen program at different avals grows its jit
    trace cache — the static twin of a mid-traffic recompile stall."""
    eng = _quiet_engine(params)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, TINY.vocab_size, size=(5,)).tolist())
    eng.run_to_completion()
    eng.mark_steady()
    assert gc.audit_programs(eng) == []
    rec = eng.program_registry()[("lane_set",)]
    # engine dispatches (4,) lanes; (8,) forces a second trace (donated
    # args must be distinct buffers)
    rec.jitted(
        jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32),
        jnp.zeros((8, eng.table_width), jnp.int32),
        jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((eng.table_width,), jnp.int32),
    )
    fs = gc.audit_programs(eng)
    assert [f.rule for f in fs] == ["GC008"]
    assert fs[0].detail.startswith("relower:")
    assert "lane_set" in fs[0].program


def test_gc008_quiet_before_freeze(params):
    """Engines that never mark_steady() (no prewarm) are exempt — GC008
    is a steady-state contract, not a construction-time one."""
    eng = _quiet_engine(params)
    eng._decode_program(eng.gen.sampling, 64)
    assert eng._frozen_keys is None
    assert gc.audit_programs(eng) == []


# ----------------------------------------------------------- machinery


def test_walker_descends_nested_subjaxprs():
    """all_shapes must see avals that exist only inside scan and
    shard_map sub-jaxprs — the property the three per-test walkers
    enforced before graftcheck unified them."""
    def scanned(x):
        def body(c, _):
            return c + 1.0, (c * 2.0).reshape(3, 7, 1)

        _, ys = jax.lax.scan(body, x, None, length=5)
        return ys

    closed = jax.make_jaxpr(scanned)(jnp.ones((3, 7)))
    assert (3, 7, 1) in gc.all_shapes(closed)

    paths = [p for _e, p in gc.walk_eqns(_psum_region_trace())]
    assert any("shard_map" in p for p in paths)


def test_suppression_silences_a_rule(params):
    model, closed = _decode_trace(TINY, params)
    forbidden = model.forbidden_gather_shapes(4, 32)
    assert gc.check_no_gather(closed, forbidden, "p") != []
    assert gc.check_no_gather(
        closed, forbidden, "p", suppress={"GC001"}
    ) == []
    up = jax.make_jaxpr(lambda x: jax.device_put(x))(jnp.ones(3))
    assert gc.check_host_transfers(up, "p", suppress={"GC003"}) == []


def test_baseline_round_trip(tmp_path, params):
    model, closed = _decode_trace(TINY, params)
    fs = gc.check_no_gather(
        closed, model.forbidden_gather_shapes(4, 32), "gather-twin"
    )
    assert fs
    path = str(tmp_path / "baseline.txt")
    gc.write_baseline(path, fs)
    baseline = gc.read_baseline(path)
    assert set(baseline) == {f.fingerprint for f in fs}
    # grandfathered findings filter out; a different program's do not
    assert gc.filter_baseline(fs, baseline) == []
    other = [dataclasses.replace(f, program="other") for f in fs]
    assert gc.filter_baseline(other, baseline) == other


def test_fingerprint_is_stable_and_detail_keyed():
    a = gc.Finding("GC001", "p", "msg", "hint", detail="(1, 2)")
    b = gc.Finding("GC001", "p", "different msg", "hint", detail="(1, 2)")
    c = gc.Finding("GC001", "p", "msg", "hint", detail="(3, 4)")
    assert a.fingerprint == b.fingerprint  # message-independent
    assert a.fingerprint != c.fingerprint  # locator-keyed


def test_rule_catalogue_complete():
    assert sorted(gc.GC_RULES) == [
        "GC001", "GC002", "GC003", "GC004", "GC005", "GC006",
        "GC007", "GC008", "GC009", "GC010", "GC011",
    ]


# ------------------------------------------------------------ the gate


def test_self_audit():
    """The tier-1 CI gate: the representative program catalog (engine
    registry + decode/verify/tp=2/int8 traces) must stay graftcheck-clean
    (modulo the reviewed baseline). Runs the real CLI so the exit-status
    contract is what's tested."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "graftcheck_gate.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        timeout=300,
    )
    assert proc.returncode == 0, (
        "graftcheck gate failed:\n" + proc.stdout + proc.stderr
    )
    assert "graftcheck: clean" in proc.stdout
