"""SmallThinker (router before attention, ReLU-gated experts, window rotary
layers beside full layers that carry no position) at the tiny size, float32 on
the CPU: the training-side model and ``SmallThinkerDecode`` under the paged
serving engine against the plain float32 reference — logits, every row — and
each architecture fact planted wrong, which has to fail; what the ``moe/``
block gained for it (routes made elsewhere, the activation) leaves the other
families' programs as they were; the block walk at an odd number of query heads
a kv head.

Tolerances. Program and reference both compute in float32 at "highest"
precision here, so what separates them is the order of float32 sums alone (a
grouped-query einsum against a repeat, a scan over experts against one einsum):
rows of logits of size ~1 agree to ~3e-7; ``TOL`` 1e-4 is the bound the other
families' CPU tests use and leaves that two orders of room. A planted fault
moves most rows by 1e-2 or more: ``FAULT`` 1e-3 is ten times the bound."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, spec
from neuronx_distributed_llama3_2_tpu.inference import (
    CacheKind, GenerationConfig, InferenceEngine, MixedKVCache, SmallThinkerDecode,
)
from neuronx_distributed_llama3_2_tpu.inference.model import LagunaDecode, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.models import model_registry
from neuronx_distributed_llama3_2_tpu.models.mixtral import MIXTRAL_CONFIGS
from neuronx_distributed_llama3_2_tpu.models.olmoe import OLMOE_CONFIGS
from neuronx_distributed_llama3_2_tpu.models.smallthinker import (
    SMALLTHINKER_CONFIGS, SmallThinkerForCausalLM,
    params_from_hf_smallthinker, params_to_hf_smallthinker,
)
from neuronx_distributed_llama3_2_tpu.moe import experts as moe_experts
from neuronx_distributed_llama3_2_tpu.moe.model import MoE, MoEConfig
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine
from tests.test_laguna_serving import (
    BS, CHUNK, LANES, RING, RING_BLOCKS, SIZES, clean, prompts_of, ringed_logits,
)
from tests.test_paged_decode_walk_kernel import _WALK, make, twin

TINY = dataclasses.replace(SMALLTHINKER_CONFIGS["tiny-smallthinker"], max_seq_len=128)
TOL, FAULT = 1e-4, 1e-3
PAGED_CONFIG_FIELDS = 45        # as at the parent of PR 57: this family brought none


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("smallthinker")


@pytest.fixture(scope="module")
def params():
    return jax.jit(SmallThinkerForCausalLM(TINY).init)(jax.random.key(0))


@pytest.fixture(scope="module")
def sequence():
    """83 prompt tokens + 15 decode steps: 98 positions through a window of 8
    and a ring of 24 rows — four times round."""
    rng = np.random.default_rng(5)
    return rng.integers(1, 256, 83).tolist(), rng.integers(1, 256, 15).tolist()


_REFERENCE = {}


def reference_logits(fam, params, ids):
    """The reference's logits for ``ids``: one causal pass over 128 rows (one
    compile), the rows past ``ids`` padding that no earlier row sees."""
    if "fn" not in _REFERENCE:
        cfg = fam.reference_config(TINY)
        _REFERENCE["fn"] = jax.jit(lambda p, i: fam.reference.forward_logits(p, cfg, i))
    padded = np.zeros((1, 128), np.int32)
    padded[0, :len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REFERENCE["fn"](params, jnp.asarray(padded)))[0, :len(ids)]


def reference_tokens(fam, params, prompt, new_tokens):
    seq = list(prompt)
    for _ in range(new_tokens):
        seq.append(int(np.argmax(reference_logits(fam, params, seq)[-1])))
    return seq[len(prompt):]


def serving(params, new_tokens=6, max_batch=LANES, **paged):
    paged = {"block_size": BS, "num_blocks": 140, "prefill_chunk_tokens": CHUNK,
             "prefill_buckets": (8, 16), "kv_buckets": (128,), **paged}
    eng = InferenceEngine(TINY, params, max_batch=max_batch, max_seq_len=128, buckets=[8, 16, 32, 128])
    return PagedServingEngine(eng, GenerationConfig(max_new_tokens=new_tokens), PagedConfig(**paged))


def test_the_family_gets_its_decode_class_and_no_option_of_its_own():
    model = decode_model_for(TINY)
    assert type(model) is SmallThinkerDecode and isinstance(model, LagunaDecode)
    assert model.cache_kinds == (CacheKind("full", 2, None), CacheKind("window", 3, 8))
    assert SmallThinkerDecode._attend is LagunaDecode._attend       # shared, not copied
    assert isinstance(model.init_paged_cache(9, BS, window_blocks=5), MixedKVCache)
    assert len(dataclasses.fields(PagedConfig)) == PAGED_CONFIG_FIELDS
    published = SMALLTHINKER_CONFIGS["smallthinker-21b-a3b"]
    assert decode_model_for(published).cache_kinds == (
        CacheKind("full", 13, None), CacheKind("window", 39, 4096))
    assert published.num_heads // published.num_kv_heads == 7 and published.moe_config().activation == "relu"
    assert not published.rotates("full") and published.rotates("window")
    entry = model_registry()["tiny-smallthinker"]
    assert entry["model_cls"] is SmallThinkerForCausalLM and entry["to_hf"] is params_to_hf_smallthinker
    with pytest.raises(NotImplementedError, match="tree verification"):
        model.forward({}, None, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                      tree=(jnp.zeros((2,), jnp.int32), jnp.ones((2, 2), bool)))


@pytest.mark.parametrize("lists,word", [
    (dict(rope_layout=(0, 1, 1)), "num_layers = 5 entries"),
    (dict(sliding_window_layout=(0, 1, 2, 1, 0)), "0s and 1s"),
    (dict(rope_layout=(0, 1, 0, 1, 0)), "window layers' weights are one stack"),
    (dict(sliding_window=0), "sliding_window must be positive"),
])
def test_the_two_lists_are_held_to_each_other_and_the_depth(lists, word):
    with pytest.raises(ValueError, match=word):
        dataclasses.replace(TINY, **lists)


def test_the_model_matches_the_reference_on_every_row(fam, params, sequence):
    ids = sequence[0] + sequence[1]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(SmallThinkerForCausalLM(TINY).__call__)(params, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got, reference_logits(fam, params, ids), rtol=TOL, atol=TOL)


def test_chunked_prefill_then_decode_match_the_reference_with_the_ring_wrapped(fam, params, sequence):
    """``pctx``, then ``psfx`` over chunks of 16 that cut the window of 8 (the
    last one 3 rows, bucket-padded to 16), then ``pdecode`` in a batch whose
    other lanes idle: the full kind through a block table, the window kind
    through the lane's ring of 24 rows, wrapped four times."""
    prompt, fed = sequence
    assert (len(prompt) + len(fed)) // RING >= 3
    got, cache = ringed_logits(decode_model_for(TINY), params, prompt, fed, [16] * 5 + [3], pad_last_to=16)
    np.testing.assert_allclose(got, reference_logits(fam, params, prompt + fed), rtol=TOL, atol=TOL)
    # the idle lanes of the decode batch wrote into the null block alone
    k = np.asarray(cache.window.k)
    assert np.abs(k[:, 1:1 + RING_BLOCKS]).max() == 0 and np.abs(k[:, 1 + RING_BLOCKS:1 + 2 * RING_BLOCKS]).min() > 0


def planted(name, monkeypatch):
    """The decode model with one architecture fact wrong."""
    if name == "router_after_attention":
        class Late(SmallThinkerDecode):
            def _early_routes(self, lp, h):
                return None             # the expert block then routes the post-attention state, like every other family
        return Late(TINY)
    if name == "silu_for_relu":
        monkeypatch.setitem(moe_experts.ACTIVATIONS, "relu", jax.nn.silu)
        return decode_model_for(dataclasses.replace(TINY, max_seq_len=127))    # a fresh trace
    change = {
        "rotary_on_a_full_layer": dict(rope_layout=(1,) * 5),
        "none_on_a_window_layer": dict(rope_layout=(0,) * 5),
        "no_lower_bound": dict(sliding_window=1 << 20),
        "window_one_key_short": dict(sliding_window=7),
        "gates_not_renormalised": dict(normalize_top_k=False),
    }[name]
    return decode_model_for(dataclasses.replace(TINY, **change))


@pytest.mark.parametrize("name", [
    "router_after_attention", "silu_for_relu", "rotary_on_a_full_layer", "none_on_a_window_layer",
    "no_lower_bound", "window_one_key_short", "gates_not_renormalised",
])
def test_each_planted_fault_fails_against_the_reference(fam, params, name, monkeypatch):
    """35 prompt tokens in three pieces + 4 decode steps, ring of 24: every
    fault reaches most rows (a window fault the rows past row 7)."""
    rng = np.random.default_rng(11)
    prompt, fed = rng.integers(1, 256, 35).tolist(), rng.integers(1, 256, 4).tolist()
    got, _ = ringed_logits(planted(name, monkeypatch), params, prompt, fed, [16, 16, 3])
    want = reference_logits(fam, params, prompt + fed)
    rows = np.abs(got - want).max(axis=-1)
    assert np.median(rows[8:]) > FAULT and rows[-1] > FAULT, (name, np.median(rows), rows.max())


def test_the_dense_slot_cache_runs_every_layer_at_full_length(fam, params):
    """``InferenceEngine.generate``: the window is a mask alone."""
    prompt = prompts_of(np.random.default_rng(17), (40,))[0]
    eng = InferenceEngine(TINY, params, max_batch=1, max_seq_len=128, buckets=[8, 16, 32, 128])
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=6))
    assert list(out.sequences[0]) == reference_tokens(fam, params, prompt, 6)


def test_a_second_request_through_used_blocks_and_a_used_ring(fam, params):
    """One lane: the second request reads a ring and blocks the first left
    full. Its tokens are the reference's, and the benchmark's check (logits)
    passes on the engine afterwards."""
    long, short = prompts_of(np.random.default_rng(13), (100, 30))
    srv = serving(params, new_tokens=10, max_batch=1)
    srv.submit(long)
    srv.run_to_completion()
    assert np.abs(np.asarray(srv.cache.window.k)[:, 1:]).min() > 0
    second = srv.submit(short)
    assert srv.run_to_completion()[second] == reference_tokens(fam, params, short, 10)
    got = check.serving_engine(
        srv, fam, TINY, {"prompt_tokens": 40, "decode_steps": 4, "tolerance": TOL,
                         "cache_tolerance": TOL, "clear_margin": 0.001}, {**SIZES, "lanes": 1}, seed=3)
    assert got["ok"] and got["all_rows"]["max"] < TOL and got["cache"]["plain_pool_is_own"], got
    clean(srv)


def test_mixed_lengths_through_the_engine_give_the_references_tokens(fam, params, monkeypatch):
    """More requests than lanes, prompts under the window and several rings
    long, both kinds' decode read the interpreted block walk (3 query heads
    a kv head) — the window kind's over its ring's visible blocks; the traced
    engine says which read each kind got and what the window read passed."""
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    prompts = prompts_of(np.random.default_rng(3), (37, 5, 90, 21, 60))
    srv = serving(params, new_tokens=6, trace_enabled=True)
    reads = {name: kind["decode_read"] for name, kind in srv._kind_facts()["cache_kinds"].items()}
    assert reads == {"full": "kernel", "window": "kernel"}
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == reference_tokens(fam, params, prompt, 6), (rid, len(prompt))
    tl = srv.tracer.timeline()
    records = [args for step in tl["steps"] for ph, name, _, _, args in step["events"]
               if ph == "X" and name == "dispatch"]
    assert records and all("window_rows" in a for a in records)
    assert all(a["window_rows"] <= a["window_rows_passed"] < LANES * RING for a in records)
    # the routing tap commits a layer's counts: 8 experts wide, 3 a live token a layer
    assert len(tl["routed"]) > 0 and all(len(row[4]) == 8 for row in tl["routed"])
    clean(srv)


def test_the_router_runs_ahead_of_attention_in_the_traced_program(params, monkeypatch):
    """A layer's body routes, then attends, then runs the experts; the
    program names the scopes the shared readers book (``moe/router``,
    ``attn/full``, ``attn/window``, ``moe/experts``) and no ``attn/out_gate``."""
    order = []
    for cls, name in ((MoE, "_route"), (LagunaDecode, "_attend"), (moe_experts.ExpertMLPs, "__call__")):
        inner = getattr(cls, name)
        monkeypatch.setattr(cls, name, lambda *a, _f=inner, _n=name, **k: (order.append(_n), _f(*a, **k))[1])
    model = decode_model_for(TINY)
    text = jax.jit(model.forward, static_argnames=("context_encode",)).lower(
        params, model.init_paged_cache(9, BS), jnp.zeros((1, 16), jnp.int32), jnp.zeros((1,), jnp.int32), None,
        context_encode=True, block_tables=jnp.ones((1, 8), jnp.int32),
    ).as_text(debug_info=True)
    assert order == ["_route", "_attend", "__call__"] * 3          # runs f, w w w, f: a body each
    for scope in ("moe/router", "attn/full/qkv", "attn/window/rope", "attn/window/kv_write", "moe/experts/all"):
        assert scope in text, scope
    assert "attn/full/rope" not in text and "out_gate" not in text


# -- what moe/ gained ----------------------------------------------------------

def test_routes_made_by_the_block_itself_are_the_default_bit_for_bit():
    cfg = MoEConfig(hidden_size=32, intermediate_size=16, num_experts=8, top_k=3, dtype=jnp.float32,
                    shared_intermediate_size=16)
    moe = MoE(cfg)
    p = moe.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, 5, 32), jnp.float32)
    default = jax.jit(moe.__call__)(p, x)
    routed = jax.jit(lambda p, x: moe(p, x, routes=moe.route(p, x)))(p, x)
    for a, b in zip(default, routed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # routes from another tensor are another result; relu is not silu
    other = jax.jit(lambda p, x: moe(p, x, routes=moe.route(p, x[::-1])))(p, x)
    assert np.abs(np.asarray(other[0]) - np.asarray(default[0])).max() > 1e-3
    relu = MoE(dataclasses.replace(cfg, activation="relu"))(p, x)[0]
    assert np.abs(np.asarray(relu) - np.asarray(default[0])).max() > 1e-4
    h = x.reshape(-1, 32)
    gates, idx = jnp.full((10, 3), 1 / 3), jnp.tile(jnp.arange(3), (10, 1))
    experts = MoE(dataclasses.replace(cfg, activation="relu"))._experts()
    relu_experts = experts.forward_all_experts(p["experts"], h, gates, idx)
    np.testing.assert_allclose(experts.forward_selective(p["experts"], h, gates, idx), relu_experts, atol=1e-6)
    with pytest.raises(ValueError, match="activation must be one of"):
        dataclasses.replace(cfg, activation="gelu")


# the lowered text of ``MoE.__call__(params, x)`` at the parent of PR 57
# (commit d8b06c4, jax 0.9.0), sha256: the default path is the program it was
LOWERED_AT_THE_PARENT = {
    "tiny-moe": "92927a42a7a5d60e", "tiny-olmoe": "052182efac17d95a",
}


@pytest.mark.parametrize("preset", sorted(LOWERED_AT_THE_PARENT))
def test_the_default_paths_lowered_text_is_unchanged(preset):
    if jax.__version__ != "0.9.0":
        pytest.skip("the pinned text is jax 0.9.0's")
    cfg = {**MIXTRAL_CONFIGS, **OLMOE_CONFIGS}[preset]
    moe = MoE(dataclasses.replace(cfg.moe_config(), capacity_factor=None))
    shapes = jax.eval_shape(moe.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 8, cfg.hidden_size), cfg.dtype)
    text = jax.jit(lambda p, x: moe(p, x)).lower(shapes, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED_AT_THE_PARENT[preset]


# -- 7 query heads a kv head ---------------------------------------------------

@pytest.mark.parametrize("heads", [(14, 2), (28, 4)])
def test_the_block_walk_takes_seven_query_heads_a_kv_head(heads, monkeypatch):
    """No family before this one had an odd group: the walk's mask gives query
    head m the columns of kv head ``m // 7``, as ``masked_attention``'s
    grouped einsum does."""
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    q, k_pool, v_pool, tables, positions = make(jnp.float32, seed=7, heads=heads)
    for layer in (0, 1):
        got = _WALK(q, k_pool, v_pool, tables, positions, layer)
        want = twin(q, k_pool, v_pool, tables, positions, layer)
        live = np.arange(len(positions)) != 4         # lane 4 idles on the null block
        np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)


def test_hf_names_round_trip(params):
    sd = params_to_hf_smallthinker(params, TINY)
    assert "model.layers.0.mlp.gate.weight" in sd and "model.layers.4.mlp.experts.7.down_proj.weight" in sd
    assert not any("g_proj" in n or "shared_expert" in n for n in sd)
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (6 * 16, 64)
    assert len(sd) == 3 + 5 * (7 + 3 * 8)
    back = params_from_hf_smallthinker(sd, TINY)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
