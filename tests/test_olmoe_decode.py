"""OLMoE through the paged engine: prefill then decode through the pool
(``pctx``, ``psfx``, ``pdecode``) against the plain float32 reference's full
forward, at and above ``T·k = E`` (where the expert dispatch once changed
paths; it is all-experts at every shape now); the serving
check failing what it has to fail; the finer device scopes; the routing
counters of a traced engine; HF names; weight placement; the joint QK-norm
under tensor parallelism."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
    MixtralDecode,
    SamplingConfig,
    decode_model_for,
)
from neuronx_distributed_llama3_2_tpu.models import (
    OLMOE_CONFIGS,
    OlmoeForCausalLM,
    params_from_hf_olmoe,
    params_to_hf_olmoe,
    resolve_model,
)
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine
from neuronx_distributed_llama3_2_tpu.serving.tracing import DETAIL_SCOPES

from benchmarks import check, program_trace, spec

TINY = OLMOE_CONFIGS["tiny-olmoe"]          # 8 experts, 2 per token
# the check's sizes, as the cell's rehearsal block has them; float32 on the
# CPU, so the system sits at rounding distance from the reference
SIZES = {"block_size": 16, "max_seq_len": 64, "prefill_chunk_tokens": 16,
         "prefill_buckets": [16], "kv_buckets": [64]}
CHECK = {"prompt_tokens": 40, "decode_steps": 4, "tolerance": 1e-4, "cache_tolerance": 1e-4}


@pytest.fixture(scope="module")
def params():
    params = OlmoeForCausalLM(TINY).init(jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    # norm scales start at one: move every leaf, the q/k norms' among them
    return jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)
    ])


def build(params, lanes, cfg=TINY, trace=False):
    engine = InferenceEngine(cfg, params, max_batch=lanes, max_seq_len=SIZES["max_seq_len"])
    return PagedServingEngine(
        engine,
        GenerationConfig(max_new_tokens=6, sampling=SamplingConfig(greedy=True), seed=0),
        PagedConfig(block_size=SIZES["block_size"], num_blocks=32, prewarm=True,
                    prefill_chunk_tokens=SIZES["prefill_chunk_tokens"],
                    prefill_buckets=tuple(SIZES["prefill_buckets"]),
                    kv_buckets=tuple(SIZES["kv_buckets"]), trace_enabled=trace),
    )


def run_check(serving, lanes, model_cfg=TINY):
    return check.serving_engine(
        serving, spec.load_family("olmoe"), model_cfg, CHECK, dict(SIZES, lanes=lanes), seed=5)


@pytest.fixture(scope="module")
def traced(params):
    return build(params, 4, trace=True)


def test_olmoe_is_served_by_the_mixtral_decode_model():
    assert isinstance(decode_model_for(TINY), MixtralDecode)
    assert resolve_model("olmoe-1b-7b")["config"].num_experts == 64
    with pytest.raises(ValueError, match="qk_norm with clip_qkv"):
        dataclasses.replace(TINY, clip_qkv=8.0)


@pytest.mark.parametrize("lanes,path", [(4, "all"), (8, "all")])
def test_paged_prefill_then_decode_equals_the_reference(params, traced, lanes, path):
    """Parts A, B and C of the benchmark's check on a tiny engine: the tokens
    the engine emits, and teacher-forced logits through ``pctx`` (first chunk),
    ``psfx`` (later chunks) and ``pdecode`` over the pool. 4 lanes x 2 = 8 = E,
    where the dispatch rule was once wrong, and 8 lanes above it both decode
    on all-experts, as a 16-token chunk always did."""
    serving = traced if lanes == 4 else build(params, lanes, trace=True)
    got = run_check(serving, lanes)
    assert got["ok"], got
    assert got["all_rows"]["max"] < 1e-5 and got["decode_rows_p50"] < 1e-5
    assert got["argmax_agree"] == 1.0 and got["rows"] == 44
    decode_paths = {rec.routing["paths"] for rec in serving.program_registry().values()
                    if rec.kind == "pdecode"}
    assert decode_paths == {(path,)}


@pytest.mark.parametrize("mistake,wrong", [
    ("qk_norm_skipped", dict(qk_norm=False)),
    ("gates_renormalised", dict(normalize_top_k=True)),
    ("last_expert_dropped", dict(top_k=TINY.top_k - 1)),
])
def test_the_check_fails_an_engine_that_is_not_olmoe(params, mistake, wrong):
    """The engine runs a config with one architecture fact wrong; the check is
    told the true one, as a cell's configuration file would."""
    got = run_check(build(params, 4, dataclasses.replace(TINY, **wrong)), 4)
    assert not got["ok"], mistake
    assert got["all_rows"]["p50"] > 100 * CHECK["tolerance"]


def scope_paths(rec):
    import re

    return set(re.findall(r'op_name="([^"]+)"', rec.lower().compile().as_text()))


def test_the_finer_scopes_say_which_path_a_program_took(traced):
    by_kind = {rec.kind: rec for rec in traced.program_registry().values()}
    want = {"pctx": "all", "psfx": "all", "pdecode": "all"}
    for kind, path in want.items():
        parts = [[inner for _, inner in program_trace.segments(p)] for p in scope_paths(by_kind[kind])]
        assert any("attn" in p and "qk_norm" in p[p.index("attn"):] for p in parts), kind
        under_experts = {p[p.index("experts") + 1] for p in parts
                         if "experts" in p and len(p) > p.index("experts") + 1}
        assert path in under_experts and not under_experts & (set(DETAIL_SCOPES["moe/experts"]) - {path})
    # the shared readers book the finer scopes to their parents
    assert program_trace.scopes_of("pdecode/attn/qk_norm/mul:") == ("pdecode", "attn")
    assert program_trace.scopes_of("psfx/moe/experts/all/dot_general:") == ("psfx", "moe", "moe/experts")


def test_routing_counters_ride_the_traced_programs_only(params, traced):
    plain = build(params, 4)
    prompt = list(range(1, 41))
    before = len(traced.tracer.timeline()["routed"])       # prewarm, and the tests above
    rids = [serving.submit(prompt) for serving in (plain, traced)]
    outs = [serving.run_to_completion()[rid] for serving, rid in zip((plain, traced), rids)]
    assert outs[0] == outs[1]
    assert plain.tracer.timeline()["routed"] == []
    rows = traced.tracer.timeline()["routed"][before:]
    kinds = {r[1] for r in rows}
    assert {"pctx", "psfx", "pdecode"} <= kinds
    L, E, k = TINY.num_layers, TINY.num_experts, TINY.top_k
    # one request of 40 tokens in chunks of 16: what is counted is its own
    # tokens — 16, 16, 8 of the three 16-row buckets, one of the four lanes —
    # each routed to k experts a layer; what is computed is every row's
    live = {"pctx": [16], "psfx": [16, 8], "pdecode": [1] * sum(r[1] == "pdecode" for r in rows)}
    for kind, want in live.items():
        assert [sum(r[4]) for r in rows if r[1] == kind] == [n * k * L for n in want], kind
    for step, kind, paths, computed, counts in rows:
        tokens = 4 if kind == "pdecode" else 16
        assert len(counts) == E
        assert paths == ("all",) and computed == tokens * E * L
    # the untraced programs are the ones an engine without a tracer builds:
    # no extra output, and no trace of the tap in their HLO
    for key, rec in plain.program_registry().items():
        assert rec.routing is None and rec.on_routed is None
        if rec.kind in ("pctx", "psfx", "pdecode"):
            text = rec.lower().as_text()
            assert text == jax.jit(rec.fn, donate_argnums=rec.donate_argnums).lower(*rec.example_args).as_text()
            twin = traced.program_registry()[key]
            out, counts = jax.eval_shape(twin.fn, *twin.example_args)
            assert counts.shape == (E,) and counts.dtype == jnp.int32
            assert jax.tree.map(lambda a: (a.shape, a.dtype), out) == jax.tree.map(
                lambda a: (a.shape, a.dtype), jax.eval_shape(rec.fn, *rec.example_args))


def test_hf_names_round_trip(params):
    sd = params_to_hf_olmoe(params, TINY)
    assert {"model.layers.0.self_attn.q_norm.weight", "model.layers.1.self_attn.k_norm.weight",
            "model.layers.0.mlp.gate.weight", "model.layers.1.mlp.experts.7.down_proj.weight",
            "model.layers.0.mlp.experts.0.gate_proj.weight", "lm_head.weight"} <= set(sd)
    assert not any("block_sparse_moe" in name for name in sd)
    back = params_from_hf_olmoe(sd, TINY)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hf_checkpoint_gives_hf_logits(params):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf = transformers.OlmoeConfig(
        vocab_size=TINY.vocab_size, hidden_size=TINY.hidden_size, intermediate_size=TINY.intermediate_size,
        num_hidden_layers=TINY.num_layers, num_attention_heads=TINY.num_heads,
        num_key_value_heads=TINY.num_kv_heads, num_experts=TINY.num_experts,
        num_experts_per_tok=TINY.top_k, norm_topk_prob=False, rope_theta=TINY.rope_theta,
        rms_norm_eps=TINY.rms_norm_eps, max_position_embeddings=TINY.max_seq_len,
        tie_word_embeddings=False,
    )
    model = transformers.OlmoeForCausalLM(hf).eval()
    model.load_state_dict({k: torch.tensor(v) for k, v in params_to_hf_olmoe(params, TINY).items()})
    ids = np.random.default_rng(2).integers(0, TINY.vocab_size, (2, 19))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    got = np.asarray(OlmoeForCausalLM(TINY)(params, jnp.asarray(ids, jnp.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_placement_relays_the_fused_leaf_and_the_head_split_projections(params):
    engine = InferenceEngine(TINY, params, max_batch=2, max_seq_len=32)
    gate_up = params["layers"]["moe"]["experts"]["gate_up"]
    qkv = params["layers"]["attn"]["qkv"]
    assert engine.placement["leaves"] == 4
    assert engine.placement["bytes"] == gate_up.nbytes + sum(
        qkv[name].nbytes for name in ("q_kernel", "k_kernel", "v_kernel"))
    assert jax.tree.structure(engine.params) == jax.tree.structure(params)
    placed = engine.params["layers"]["moe"]["experts"]["gate_up"]
    assert placed.format.layout.major_to_minor == (0, 1, 3, 2, 4)
    np.testing.assert_array_equal(np.asarray(placed), np.asarray(gate_up))
    for name in ("q_kernel", "k_kernel", "v_kernel"):
        placed = engine.params["layers"]["attn"]["qkv"][name]
        assert placed.format.layout.major_to_minor == (0, 2, 1)
        np.testing.assert_array_equal(np.asarray(placed), np.asarray(qkv[name]))


def test_joint_qk_norm_is_the_same_function_under_tensor_parallelism(params):
    """The norm's mean spans all heads, which tp shards: the block is global
    GSPMD math, and the partitioner supplies the cross-shard sum."""
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
    from neuronx_distributed_llama3_2_tpu.parallel.layers import shard_pytree

    model = OlmoeForCausalLM(TINY)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, TINY.vocab_size, (2, 16)), jnp.int32)
    want = np.asarray(jax.jit(model.__call__)(params, ids))
    parallel_state.initialize_model_parallel(tensor_model_parallel_size=2)      # conftest tears it down
    mesh = parallel_state.get_parallel_state().mesh
    sharded = shard_pytree(params, model.specs(), mesh)
    lowered = jax.jit(model.__call__).lower(sharded, ids)
    assert "all-reduce" in lowered.compile().as_text()
    got = np.asarray(jax.jit(model.__call__)(sharded, ids))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
