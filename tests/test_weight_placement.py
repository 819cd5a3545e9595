"""Weights rest on the device in the layout the matmul reads
(``inference/placement.py``): fused ``gate_up`` leaves, and the stacked
attention projections whose output is split into heads.

Two tiers. On the CPU: an engine over re-placed weights is the engine over
default-placed weights, bit for bit, and nothing but the physical layout of
the named leaves changed. For a described v5e (libtpu compiles for a topology
without a chip — sizes, never a time): the paged programs over re-placed
weights hold no copy of a layer's ``gate_up`` or of a layer's attention
kernel, the same programs over default-placed weights — the control — do, and
the layouts the rule names are the ones the compiler picks for itself when
the weights' layout is left to it (``Layout.AUTO``).
"""

import dataclasses
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
    SamplingConfig,
    decode_model_for,
)
from neuronx_distributed_llama3_2_tpu.analysis import graftcheck
from neuronx_distributed_llama3_2_tpu.inference import engine as engine_mod
from neuronx_distributed_llama3_2_tpu.inference.placement import (
    fused_rest_layout,
    head_split_rest_layout,
    rest_layout,
    rest_weights,
)
from neuronx_distributed_llama3_2_tpu.models import (
    LLAMA_CONFIGS,
    MIXTRAL_CONFIGS,
    LlamaForCausalLM,
    MixtralForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.brumby import BRUMBY_CONFIGS, BrumbyForCausalLM
from neuronx_distributed_llama3_2_tpu.models.jamba import JAMBA_CONFIGS, JambaForCausalLM
from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import SALA_CONFIGS, SalaForCausalLM
from neuronx_distributed_llama3_2_tpu.models.laguna import (
    LAGUNA_CONFIGS,
    LagunaForCausalLM,
    params_to_hf_laguna,
)
from neuronx_distributed_llama3_2_tpu.models.llama import params_to_hf
from neuronx_distributed_llama3_2_tpu.models.mixtral import params_to_hf_mixtral
from neuronx_distributed_llama3_2_tpu.models.olmoe import OLMOE_CONFIGS, OlmoeForCausalLM
from neuronx_distributed_llama3_2_tpu.models.sarvam import SARVAM_CONFIGS, SarvamForCausalLM
from neuronx_distributed_llama3_2_tpu.models.smallthinker import (
    SMALLTHINKER_CONFIGS,
    SmallThinkerForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.xing import XING_CONFIGS, XingForCausalLM
from neuronx_distributed_llama3_2_tpu.quantization import (
    QuantizationConfig,
    quantize_params,
)
from neuronx_distributed_llama3_2_tpu.quantization.quantize import walk_tree
from neuronx_distributed_llama3_2_tpu.serving import (
    PagedConfig,
    PagedServingEngine,
)

def heads(group, *names, order=(0, 2, 1)):
    return {f"{group}/{name}": order for name in names}


QKV = ("qkv/q_kernel", "qkv/k_kernel", "qkv/v_kernel")
MLA = {**heads("dense_layers/attn", "kv_a/kernel"), **heads("layers/attn", "kv_a/kernel"),
       **heads("dense_layers/attn", "kv_b/kernel", order=(0, 2, 1, 3)),
       **heads("layers/attn", "kv_b/kernel", order=(0, 2, 1, 3)),
       "dense_layers/mlp/gate_up": (0, 2, 1, 3), "layers/moe/shared/gate_up": (0, 2, 1, 3),
       "layers/moe/experts/gate_up": (0, 1, 3, 2, 4)}
FAMILIES = {
    # preset, model, to_hf (None where the family has none), and every leaf
    # that rests in a layout of its own, with its order major to minor
    "mixtral": (MIXTRAL_CONFIGS["tiny-moe"], MixtralForCausalLM, params_to_hf_mixtral,
                {"layers/moe/experts/gate_up": (0, 1, 3, 2, 4), **heads("layers/attn", *QKV)}),
    "llama": (LLAMA_CONFIGS["tiny"], LlamaForCausalLM, params_to_hf,
              {"layers/mlp/gate_up": (0, 2, 1, 3), **heads("layers/attn", *QKV)}),
    # MLA (the query through a latent, q_b: the rule's unit cases and the
    # compiled stacks below)
    "sarvam": (SARVAM_CONFIGS["tiny-sarvam"], SarvamForCausalLM, None,
               {**MLA, **heads("dense_layers/attn", "q/kernel"), **heads("layers/attn", "q/kernel")}),
    # three layer groups, two head counts
    "laguna": (LAGUNA_CONFIGS["tiny-laguna"], LagunaForCausalLM, params_to_hf_laguna,
               {"full_dense_layers/mlp/gate_up": (0, 2, 1, 3),
                **{f"{g}/moe/shared/gate_up": (0, 2, 1, 3) for g in ("full_layers", "window_layers")},
                **{f"{g}/moe/experts/gate_up": (0, 1, 3, 2, 4) for g in ("full_layers", "window_layers")},
                **heads("full_dense_layers/attn", *QKV), **heads("full_layers/attn", *QKV),
                **heads("window_layers/attn", *QKV)}),
}


def leaf_at(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def order_of(leaf):
    return tuple(leaf.format.layout.major_to_minor)


def flat(tree):
    out = {}
    walk_tree(tree, lambda path, leaf: out.setdefault(path, leaf))
    return out


def serve(engine, prompts, new_tokens):
    """Tokens of a prewarmed paged run that dispatches pctx, psfx (the second
    prompt extends the first, so its prefix is cached) and pdecode; no
    program may have lowered twice (GC008: state born uncommitted beside
    committed weights would)."""
    paged = PagedServingEngine(
        engine, GenerationConfig(max_new_tokens=new_tokens),
        PagedConfig(block_size=8, num_blocks=32, prewarm=True),
    )
    out = []
    for prompt in prompts:
        paged.submit(prompt)
        out.append(paged.run_to_completion())
    assert graftcheck.audit_programs(paged) == []
    return out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_over_rested_weights_is_the_engine_over_default_weights(family, monkeypatch):
    cfg, model_cls, to_hf, rests = FAMILIES[family]
    params = model_cls(cfg).init(jax.random.key(0))
    kw = dict(max_batch=2, max_seq_len=64, buckets=[16, 32])
    rested = InferenceEngine(cfg, params, **kw)
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "rest_weights", lambda p: (p, {"leaves": 0, "bytes": 0}))
        plain = InferenceEngine(cfg, params, **kw)

    # the tree the engine holds: the same keys, shapes, dtypes and shardings;
    # the named leaves in another physical order, every other leaf the
    # caller's own
    before, after = flat(params), flat(rested.params)
    assert set(rests) <= set(before)
    assert rested.placement == {
        "leaves": len(rests), "bytes": sum(before[path].nbytes for path in rests)}
    assert plain.params is params
    assert all(order_of(leaf) == tuple(range(leaf.ndim)) for leaf in before.values())
    assert list(before) == list(after)
    for path, old in before.items():
        new = after[path]
        assert (new.shape, new.dtype, new.sharding) == (old.shape, old.dtype, old.sharding), path
        if path in rests:
            assert order_of(new) == rests[path], path
            np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        else:
            assert new is old, path
    # the caller's arrays are its own: nothing was deleted under it
    assert not any(before[path].is_deleted() for path in rests)
    # a second engine over the rested tree copies nothing
    again = InferenceEngine(cfg, rested.params, **kw)
    assert again.placement == {"leaves": 0, "bytes": 0}
    assert all(leaf_at(again.params, path) is after[path] for path in rests)

    # checkpoints leave from the rested tree as from the caller's
    if to_hf is not None:
        want_sd, got_sd = to_hf(params, cfg), to_hf(rested.params, cfg)
        assert list(want_sd) == list(got_sd)
        for name in want_sd:
            np.testing.assert_array_equal(np.asarray(got_sd[name]), np.asarray(want_sd[name]), err_msg=name)

    # the same logits and tokens, bit for bit: dense prefill, bucketed
    # generate (lazily jitted), AOT-compiled generate, and the paged programs
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, size=20).tolist()
    ids = jnp.asarray([prompt], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(rested.prefill_logits(ids)), np.asarray(plain.prefill_logits(ids))
    )
    for precompile in (False, True):
        gen = GenerationConfig(max_new_tokens=6, sampling=SamplingConfig(greedy=True),
                               precompile=precompile)
        assert rested.generate([prompt, prompt[:7]], gen).sequences == \
            plain.generate([prompt, prompt[:7]], gen).sequences
    prompts = [prompt, prompt + [3, 4]]
    assert serve(rested, prompts, 4) == serve(plain, prompts, 4)


def test_traced_engine_reports_what_construction_placed():
    cfg, model_cls, _, rests = FAMILIES["mixtral"]
    params = model_cls(cfg).init(jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_batch=2, max_seq_len=64, buckets=[16, 32])

    def paged(trace):
        return PagedServingEngine(
            engine, GenerationConfig(max_new_tokens=3),
            PagedConfig(block_size=8, num_blocks=32, prewarm=True, trace_enabled=trace),
        )

    traced = paged(True)
    setup = traced.tracer.timeline()["setup"]
    assert setup["relaid_leaves"] == len(rests) == 4
    assert setup["relaid_bytes"] == sum(leaf_at(params, path).nbytes for path in rests)
    assert setup["program_temp_bytes_max"] >= 0
    # a record re-lowers as it was dispatched: for the layout the weights rest in
    rec = next(r for r in traced.program_registry().values() if r.kind == "pdecode")
    assert {path: order_of(leaf_at(rec.example_args[0], path)) for path in rests} == rests
    # off unless tracing is
    assert paged(False).tracer.timeline()["setup"] == {}


def test_leaves_of_one_shape_share_one_compiled_relayout(monkeypatch):
    """``k`` and ``v`` of a stack, and the same projection in two layer
    groups, are one shape, dtype and pair of layouts: the relayout (compiled
    on every start, outside the persistent cache) compiles once for them."""
    from neuronx_distributed_llama3_2_tpu.inference import placement

    cfg, model_cls, _, rests = FAMILIES["laguna"]
    params = model_cls(cfg).init(jax.random.key(0))
    compiled, real = [], placement._relayout
    monkeypatch.setattr(
        placement, "_relayout",
        lambda leaf, layout: (compiled.append((leaf.shape, tuple(layout.major_to_minor))),
                              real(leaf, layout))[1])
    rested, placed = rest_weights(params)
    assert placed["leaves"] == len(rests) == 14
    assert len(compiled) == len(set(compiled)) == 9
    assert set(compiled) == {(leaf_at(params, path).shape, order) for path, order in rests.items()}
    for path, order in rests.items():
        assert order_of(leaf_at(rested, path)) == order
        np.testing.assert_array_equal(np.asarray(leaf_at(rested, path)), np.asarray(leaf_at(params, path)))


def test_placement_follows_the_leaf_and_not_the_model():
    f32 = jnp.float32
    stacked = jnp.zeros((2, 4, 16, 2, 32), f32)
    assert fused_rest_layout("layers/moe/experts/gate_up", stacked).major_to_minor == (0, 1, 3, 2, 4)
    assert fused_rest_layout("3/mlp/gate_up", jnp.zeros((16, 2, 32), f32)).major_to_minor == (1, 0, 2)
    # a second-minor axis as wide as a tile is a layout the matmul reads
    assert fused_rest_layout("layers/mlp/gate_up", jnp.zeros((2, 16, 8, 32), f32)) is None
    # not fused, not a float
    assert fused_rest_layout("layers/moe/experts/down", jnp.zeros((2, 4, 2, 16), f32)) is None
    assert fused_rest_layout("layers/mlp/gate_up", jnp.zeros((2, 16, 2, 32), jnp.int8)) is None
    # a stacked attention projection whose output is split into heads: the
    # contraction axis minor, whatever the stack is called
    kernel = jnp.zeros((2, 16, 32), f32)
    for path in ("layers/attn/qkv/q_kernel", "window_layers/attn/qkv/k_kernel",
                 "attention_layers/attention/qkv/v_kernel", "dense_layers/attn/q/kernel",
                 "layers/attn/q_b/kernel", "layers/attn/kv_a/kernel"):
        assert head_split_rest_layout(path, kernel).major_to_minor == (0, 2, 1), path
        assert rest_layout(path, kernel).major_to_minor == (0, 2, 1) and fused_rest_layout(path, kernel) is None
    assert head_split_rest_layout("layers/attn/kv_b/kernel", jnp.zeros((2, 8, 4, 16), f32)).major_to_minor == (0, 2, 1, 3)
    # not split into heads (the output projection, the query's latent), not
    # a stack, not under an attention block, not a float
    for path, leaf in (("layers/attn/o/kernel", kernel), ("layers/attn/q_a/kernel", kernel),
                       ("layers/attn/qkv/q_bias", jnp.zeros((2, 32), f32)),
                       ("3/attn/qkv/q_kernel", jnp.zeros((16, 32), f32)),
                       ("layers/mlp/up/kernel", kernel), ("layers/moe/router/kernel", kernel),
                       ("layers/attn/kv_b/kernel", kernel),
                       ("layers/attn/qkv/q_kernel", jnp.zeros((2, 16, 32), jnp.int8))):
        assert rest_layout(path, leaf) is None, path
    assert rest_layout("layers/mlp/gate_up", stacked[0]).major_to_minor == (0, 2, 1, 3)


def test_the_relayout_program_never_meets_the_persistent_compile_cache(monkeypatch):
    """An executable loaded back from the cache has lost its output layout
    (``placement._place``), so the relayout is compiled afresh and not
    written, whatever the threshold the process runs with."""
    from jax._src import compilation_cache

    written = []
    real = compilation_cache.put_executable_and_time
    monkeypatch.setattr(
        compilation_cache, "put_executable_and_time",
        lambda key, name, *rest: (written.append(name), real(key, name, *rest)),
    )
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        leaf = jax.random.normal(jax.random.key(0), (2, 4, 16, 2, 32), jnp.float32)
        tree = {"layers": {"moe": {"experts": {"gate_up": leaf}}}}
        rested, placed = rest_weights(tree)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        # the spy sees a write: a program no earlier run can have cached
        nonce = float(time.time_ns() % 1_000_003)
        jax.block_until_ready(jax.jit(lambda a: a + nonce)(leaf))
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
    assert placed["leaves"] == 1 and written and not any("rest_leaf" in n for n in written)
    new = leaf_at(rested, "layers/moe/experts/gate_up")
    assert order_of(new) == (0, 1, 3, 2, 4)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(leaf))


def test_quantized_payloads_stay_where_quantization_put_them():
    cfg = LLAMA_CONFIGS["tiny"]
    qparams = quantize_params(LlamaForCausalLM(cfg).init(jax.random.key(0)), QuantizationConfig())
    rested, placed = rest_weights(qparams)
    assert placed == {"leaves": 0, "bytes": 0}
    assert jax.tree.leaves(rested)[0] is jax.tree.leaves(qparams)[0]


def test_rested_weights_keep_their_sharding_on_a_mesh():
    from jax.sharding import NamedSharding

    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
    from neuronx_distributed_llama3_2_tpu.parallel.layers import shard_pytree

    cfg = MIXTRAL_CONFIGS["tiny-moe"]
    parallel_state.initialize_model_parallel(tensor_model_parallel_size=2)
    model = MixtralForCausalLM(cfg)
    params = shard_pytree(model.init(jax.random.key(0)), model.specs())
    rests = FAMILIES["mixtral"][3]
    engine = InferenceEngine(cfg, params, max_batch=2, max_seq_len=64, buckets=[16, 32])
    for path, order in rests.items():
        old, new = leaf_at(params, path), leaf_at(engine.params, path)
        assert isinstance(new.sharding, NamedSharding) and new.sharding == old.sharding, path
        assert order_of(new) == order, path
    # a leaf that jit would spread over the mesh itself is not pinned to one device
    loose = model.init(jax.random.key(0))
    rested_loose = rest_weights(loose)[0]
    assert all(leaf_at(rested_loose, path) is leaf_at(loose, path) for path in rests)
    prompt = list(range(1, 13))
    gen = GenerationConfig(max_new_tokens=4, sampling=SamplingConfig(greedy=True))
    got = engine.generate([prompt], gen).sequences
    parallel_state.destroy_model_parallel()
    want = InferenceEngine(
        cfg, model.init(jax.random.key(0)), max_batch=2, max_seq_len=64, buckets=[16, 32]
    ).generate([prompt], gen).sequences
    assert got == want


# ---------------------------------------------------------------------------
# compiled for a described v5e: the copy is gone, and the control has it
# ---------------------------------------------------------------------------

# widths at which one layer's gate_up (470 MB) cannot hide in a smaller memory
# space: at H 1024, I 3584 the control's copy of the 512-token program is
# placed outside HBM and the temporaries do not show it
AOT = dataclasses.replace(
    MIXTRAL_CONFIGS["tiny-moe"], hidden_size=2048, intermediate_size=7168, num_experts=8,
    num_layers=2, num_heads=8, num_kv_heads=2, head_dim=128, vocab_size=2048,
    max_seq_len=1024, dtype=jnp.bfloat16,
)
LAYER_SHAPE = (AOT.num_experts, AOT.hidden_size, 2, AOT.intermediate_size)
LAYER_BYTES = 2 * int(np.prod(LAYER_SHAPE))


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e, or a clean skip where libtpu cannot
    describe one."""
    for name, value in (("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                        ("TPU_WORKER_HOSTNAMES", "localhost"),
                        ("TPU_SKIP_MDS_QUERY", "1")):
        os.environ.setdefault(name, value)
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as exc:  # no libtpu, or one that cannot describe a topology
        pytest.skip(f"libtpu cannot describe a v5e here: {exc}")


def abstract_weights(cfg, train):
    return jax.eval_shape(train(cfg).init, jax.random.key(0))


def compile_paged(device, program, weights, cfg=AOT, train=MixtralForCausalLM, blocks=256, kv=None,
                  block_size=16):
    """The paged decode step at 16 lanes, or a 512-token suffix step, over
    ``cfg``'s layer stack (the 2-layer MoE where nothing else is asked) and a
    donated pool of ``blocks`` blocks, with the ``weights`` placed as the
    engine places them (``"rested"``), as ``model.init`` leaves them
    (``"default"``), or in whatever layout the compiler picks for each
    (``"auto"``: ``Layout.AUTO``, its choice to be read from ``input_formats``). A kind of cache the engine lays out a
    lane (a ring of blocks, a state's slot) gets its table as the engine
    would size it."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(device)
    model = decode_model_for(cfg)
    lanes, top = 16, 1024
    assert weights in ("default", "rested", "auto"), weights
    auto = weights == "auto"

    def place(path, a):
        layout = rest_layout(path, a) if weights == "rested" else None
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=None if auto else one if layout is None else Format(layout, one),
        )

    shapes = abstract_weights(cfg, train)
    params = walk_tree(shapes, place)
    a_lane_kind = next((kind for kind in model.cache_kinds if kind.rows is not None), None)
    sized, a_lane = {}, ()
    if a_lane_kind is not None:
        per_lane = 1 if a_lane_kind.state else -(-(a_lane_kind.rows - 1 + 512) // block_size)
        sized = {f"{a_lane_kind.name}_blocks": 1 + lanes * per_lane}
        a_lane = (f"{a_lane_kind.name}_tables", per_lane)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: model.init_paged_cache(blocks, block_size, kv_cache_dtype=kv, **sized)),
    )
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    width = -(-top // block_size) + -(-512 // block_size)
    if program == "pdecode":
        def fn(params, cache, tokens, positions, tables, *lane):
            by_lane = {a_lane[0]: lane[0]} if lane else {}
            return model.decode_step(params, cache, tokens, positions, tables, kv_limit=top, **by_lane)
        args = (i32(lanes), i32(lanes), i32(lanes, width)) + ((i32(lanes, a_lane[1]),) if a_lane else ())
    else:
        def fn(params, cache, ids, start, length, table, *lane):
            by_lane = {a_lane[0]: lane[0]} if lane else {}
            # a state keeps what a padded row does to it: it is told the live rows
            return model.forward(params, cache, ids, start, None, return_hidden=True,
                                 block_tables=table, kv_limit=top,
                                 row_live=length if model.keeps_state else None, **by_lane)
        args = (i32(1, 512), i32(1), i32(1), i32(1, width)) + ((i32(1, a_lane[1]),) if a_lane else ())
    layouts = {}
    if auto:
        layouts["in_shardings"] = (
            jax.tree.map(lambda a: Format(Layout.AUTO, one), shapes),
            jax.tree.map(lambda a: one, cache), *(one for _ in args))
    return jax.jit(fn, donate_argnums=(1,), **layouts).lower(params, cache, *args).compile()


@pytest.mark.parametrize("program", ["pdecode", "psfx"])
def test_paged_program_over_rested_weights_copies_no_layer_of_gate_up(v5e, program):
    one_layer = r"(\S*dynamic-slice\S*) = bf16\[1,%s\]" % ",".join(map(str, LAYER_SHAPE))
    control = compile_paged(v5e, program, "default")
    rested = compile_paged(v5e, program, "rested")
    # the control copies a layer's gate_up out of the stack (and re-tiles it)
    assert re.search(one_layer, control.as_text())
    assert not re.search(one_layer, rested.as_text())
    saved = (control.memory_analysis().temp_size_in_bytes
             - rested.memory_analysis().temp_size_in_bytes)
    # one layer's bytes, less the few MB by which other temporaries move
    assert saved >= 0.95 * LAYER_BYTES, (saved, LAYER_BYTES)
    # the parameter rests tiled as the dot reads it
    assert "bf16[2,%s]{4,2,3,1,0:T(8,128)(2,1)}" % ",".join(map(str, LAYER_SHAPE)) in rested.as_text()


# ---------------------------------------------------------------------------
# compiled for a described v5e: no copy of a layer's attention kernel, and the
# rule is the compiler's own choice
# ---------------------------------------------------------------------------

def published(presets, name, **changes):
    """A served family's published widths over a stack a few layers deep, with
    a small vocabulary: what a layer's kernels look like in a cell."""
    return dataclasses.replace(
        presets[name], vocab_size=2048, max_seq_len=1024, dtype=jnp.bfloat16, **changes)


def laguna_stack():
    from neuronx_distributed_llama3_2_tpu.models.laguna import _published_lists

    # f w w w f: two full layers (one dense, one sparse) and a window stack of three
    return published(LAGUNA_CONFIGS, "laguna-xs.2", num_layers=5, num_experts=8,
                     **_published_lists(5, 48, 64))


# family -> (config, training model, block size of the paged pool, its blocks)
STACKS = {
    "llama": lambda: (published(LLAMA_CONFIGS, "llama3.2-1b", num_layers=2), LlamaForCausalLM, 16, 256),
    "mixtral": lambda: (published(MIXTRAL_CONFIGS, "mixtral-8x7b", num_layers=2, num_experts=4,
                                  intermediate_size=3584), MixtralForCausalLM, 16, 256),
    "olmoe": lambda: (published(OLMOE_CONFIGS, "olmoe-1b-7b", num_layers=2, num_experts=8),
                      OlmoeForCausalLM, 16, 256),
    # MLA: one dense layer and two of experts
    "sarvam": lambda: (published(SARVAM_CONFIGS, "sarvam-105b", num_layers=3, num_experts=8),
                       SarvamForCausalLM, 16, 256),
    "xing": lambda: (published(XING_CONFIGS, "xing4.0-29b-a4b", num_layers=3, num_experts=8),
                     XingForCausalLM, 16, 256),
    "laguna": lambda: (laguna_stack(), LagunaForCausalLM, 16, 256),
    # a block of the pool is one sequence's state: 16 lanes and the null block
    "brumby": lambda: (published(BRUMBY_CONFIGS, "brumby-14b", num_layers=2), BrumbyForCausalLM, 1024, 17),
    # M A M M A: two multi-query attention layers among three state-space ones
    "jamba": lambda: (published(JAMBA_CONFIGS, "jamba2-3b", num_layers=5, attn_layer_period=3,
                                attn_layer_offset=1), JambaForCausalLM, 16, 256),
    # L S: a Lightning layer (32 kv heads) and a block-sparse one (2); a pool block is a selection block
    "sala": lambda: (published(SALA_CONFIGS, "minicpm-sala", num_layers=2, mixer_types=(
        "lightning-attn", "minicpm4")), SalaForCausalLM, 64, 512),
}
# where the compiler, left to itself, lays a leaf of a megabyte a layer or more
# out otherwise than the rule, and copies nothing either way (the first test
# below says so for the attention kernels; a prefill chunk expands latents
# through kv_b heads-major, where the decode step, which the rule follows,
# absorbs it; x_proj's three-way split of a state-space layer is no head split
# and the default-placed program holds no copy of it. The two full-width
# output gates of a MiniCPM-SALA layer — ``attn/gate/kernel``, no head split —
# are left default by the rule and by the compiler alike)
THE_COMPILER_ALONE = {
    ("sarvam", "psfx"): {"dense_layers/attn/kv_b/kernel": (0, 2, 3, 1), "layers/attn/kv_b/kernel": (0, 2, 3, 1)},
    ("xing", "psfx"): {"dense_layers/attn/kv_b/kernel": (0, 2, 3, 1), "layers/attn/kv_b/kernel": (0, 2, 3, 1)},
    ("jamba", "pdecode"): {"mamba_layers/mamba/x_proj/kernel": (0, 2, 1)},
    ("jamba", "psfx"): {"mamba_layers/mamba/x_proj/kernel": (0, 2, 1)},
}
A_LAYER_THAT_COUNTS = 1_000_000     # bytes: below it a leaf is re-laid or not at no cost


def copies_of_a_layer(text, leaves):
    """``copy`` instructions of the compiled text whose result is one layer of
    one of ``leaves`` (with or without the leading 1): (name, type) pairs."""
    dims = set()
    for leaf in leaves:
        dims |= {",".join(map(str, leaf.shape[1:])), ",".join(map(str, (1,) + leaf.shape[1:]))}
    return [
        (name, result)
        for name, result, op in re.findall(r"^\s*(?:ROOT )?%?(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(", text, re.M)
        if op == "copy" and result[result.index("[") + 1:-1] in dims
    ]


def compile_stack(device, family, program, weights, monkeypatch):
    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV

    monkeypatch.setenv(KERNEL_MODE_ENV, "compiled")
    cfg, train, block_size, blocks = STACKS[family]()
    compiled = compile_paged(device, program, weights, cfg=cfg, train=train, blocks=blocks,
                             block_size=block_size)
    return compiled, flat(abstract_weights(cfg, train))


@pytest.mark.parametrize("program", ["pdecode", "psfx"])
@pytest.mark.parametrize("family", sorted(STACKS))
def test_paged_program_over_rested_weights_copies_no_layer_of_an_attention_kernel(
        v5e, family, program, monkeypatch):
    control, shapes = compile_stack(v5e, family, program, "default", monkeypatch)
    rested, _ = compile_stack(v5e, family, program, "rested", monkeypatch)
    split = [leaf for path, leaf in shapes.items() if head_split_rest_layout(path, leaf) is not None]
    assert len(split) >= 3
    # the control slices a layer's kernel out of the stack and transposes it
    # before the matmul; over rested weights the slice is what the dot reads
    assert copies_of_a_layer(control.as_text(), split)
    assert not copies_of_a_layer(rested.as_text(), split), copies_of_a_layer(rested.as_text(), split)


@pytest.mark.parametrize("program", ["pdecode", "psfx"])
@pytest.mark.parametrize("family", sorted(STACKS))
def test_the_rule_is_the_compilers_own_choice(v5e, family, program, monkeypatch):
    """Lowered with ``Layout.AUTO`` on the weights, the compiled program says
    in ``input_formats`` how it wants each: for every leaf of a megabyte a
    layer or more that is the layout the rule gives — fused ``gate_up``
    (PR 24's rule) and the attention projections alike — and the default one
    where the rule gives none."""
    compiled, shapes = compile_stack(v5e, family, program, "auto", monkeypatch)
    # no layout: the program does not read the leaf (a suffix step stops at
    # the hidden state and never meets the head)
    chosen = {path: fmt.layout and tuple(fmt.layout.major_to_minor)
              for path, fmt in flat(compiled.input_formats[0][0]).items()}
    assert list(chosen) == list(shapes)
    alone = THE_COMPILER_ALONE.get((family, program), {})
    assert set(alone) <= set(shapes)
    judged = relaid = 0
    for path, leaf in shapes.items():
        if leaf.size * leaf.dtype.itemsize // leaf.shape[0] < A_LAYER_THAT_COUNTS or chosen[path] is None:
            continue
        layout = rest_layout(path, leaf)
        ruled = tuple(range(leaf.ndim)) if layout is None else tuple(layout.major_to_minor)
        assert chosen[path] == alone.get(path, ruled), (path, leaf.shape, chosen[path], ruled)
        assert path not in alone or alone[path] != ruled, path
        judged += 1
        relaid += layout is not None
    # every stack has its gate_up and its head-split projections among them
    assert judged > relaid >= 3, (judged, relaid)


# ---------------------------------------------------------------------------
# compiled for a described v5e: the pool is updated in place
# ---------------------------------------------------------------------------

# a Llama-shaped stack whose pool — (4, 1024, 16, 8, 128) twice, 268 MB in
# bf16 — is far larger than anything else a step keeps: a pool, or one layer
# of it, among the temporaries cannot hide
POOL_AOT = dataclasses.replace(
    LLAMA_CONFIGS["tiny"], hidden_size=1024, intermediate_size=2816, num_layers=4,
    num_heads=8, num_kv_heads=8, head_dim=128, vocab_size=2048, max_seq_len=1024,
    dtype=jnp.bfloat16,
)
POOL_BLOCKS = 1024
MOVES_A_POOL = ("copy", "dynamic-slice", "dynamic-update-slice", "dynamic_slice", "dynamic_update_slice")


def pool_sized_moves(text, shapes):
    """Instructions of the compiled text that copy, slice or update in bulk a
    result of one of ``shapes``: (name, type) pairs."""
    found = []
    for name, result, op in re.findall(r"^\s*(?:ROOT )?%?(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(", text, re.M):
        dims = result[result.index("[") + 1:-1]
        if dims in shapes and any(m in name or m == op for m in MOVES_A_POOL):
            found.append((name, result))
    return found


def test_a_pool_that_is_the_scans_xs_and_ys_is_copied_whole(v5e):
    """The control: the form the layer loop had. A loop cannot alias its
    ``xs`` with its ``ys``, so a donated pool is copied, and the search below
    finds that copy."""
    from jax.sharding import SingleDeviceSharding

    shape = (4, 256, 16, 8, 128)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=SingleDeviceSharding(v5e))

    def fn(pool):
        def body(x, layer):
            return x + 1, layer.at[x, 0].set(1)
        return jax.lax.scan(body, jnp.int32(0), pool)[1]

    compiled = jax.jit(fn, donate_argnums=(0,)).lower(pool).compile()
    dims = {",".join(map(str, shape)), ",".join(map(str, (1,) + shape[1:]))}
    assert pool_sized_moves(compiled.as_text(), dims)


@pytest.mark.parametrize("kv", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("program", ["pdecode", "psfx"])
def test_paged_program_updates_the_donated_pool_in_place(v5e, program, kv):
    compiled = compile_paged(
        v5e, program, "rested", cfg=POOL_AOT, train=LlamaForCausalLM,
        blocks=POOL_BLOCKS, kv=kv,
    )
    text = compiled.as_text()
    model = decode_model_for(POOL_AOT)
    pool = jax.eval_shape(lambda: model.init_paged_cache(POOL_BLOCKS, 16, kv_cache_dtype=kv))
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    # no bulk copy, slice or update of the pool's shape or of one layer's
    # (with or without its leading 1). A quantized pool's *scales* — a 64th
    # of its bytes — are re-tiled once on the way in and once on the way
    # out: their default layout is not the one the row scatter wants
    shapes = [pool.k.shape, (1,) + pool.k.shape[1:], pool.k.shape[1:]]
    if kv is not None:
        shapes += [(1,) + pool.k_scale.shape[1:], pool.k_scale.shape[1:]]
    dims = {",".join(map(str, s)) for s in shapes}
    assert not pool_sized_moves(text, dims), pool_sized_moves(text, dims)
    # every pool array is a parameter the program may write its output over
    n_params = len(jax.tree.leaves(jax.eval_shape(LlamaForCausalLM(POOL_AOT).init, jax.random.key(0))))
    aliased = re.search(r"input_output_alias=\{(.*?) \}, entry_computation_layout", text).group(1)
    assert (
        sorted(int(n) for n in re.findall(r"\((\d+), \{\}", aliased))
        == [n_params + i for i in range(len(jax.tree.leaves(pool)))]
    ), aliased
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    # as the scan's xs and ys the temporaries held a whole pool and more
    assert memory.temp_size_in_bytes < 0.2 * pool_bytes, (memory.temp_size_in_bytes, pool_bytes)


# ---------------------------------------------------------------------------
# compiled for a described v5e: a mixed stack's block gather re-tiles neither
# pool, and the gather of (bs, NKV, D) slices it replaced did at 4 kv heads
# ---------------------------------------------------------------------------

def smallthinker_stack():
    from neuronx_distributed_llama3_2_tpu.models.smallthinker import _published_layout

    # f w w w: one period at the published attention widths (28 query heads
    # over 4 kv heads of 128, window 4,096)
    return published(SMALLTHINKER_CONFIGS, "smallthinker-21b-a3b", num_layers=4, num_experts=8,
                     sliding_window_layout=_published_layout(4), rope_layout=_published_layout(4))


# family -> (config, training model, blocks a lane's ring takes at a chunk of 512)
MIXED_STACKS = {
    "laguna": lambda: (laguna_stack(), LagunaForCausalLM, 64),
    "smallthinker": lambda: (smallthinker_stack(), SmallThinkerForCausalLM, 288),
}
MIXED_BLOCKS = 4096       # the full kind's: 67 MB a layer at 4 kv heads, nothing VMEM hides


def pool_dims(*pools):
    """The spellings of a pool's shape a bulk move of it could have: whole, a
    layer of it with and without its leading 1, the run of blocks a gather
    indexes."""
    return {",".join(map(str, s)) for k in pools
            for s in (k, (1,) + k[1:], k[1:], (k[0] * k[1],) + k[2:])}


@pytest.mark.parametrize("nkv,found", [(4, True), (8, False)])
def test_a_gather_of_block_slices_re_tiles_a_pool_of_under_eight_kv_heads(v5e, nkv, found):
    """The control: the read ``LagunaDecode._attend`` had. A bf16 pool of 4 kv
    heads rests in half-tiles (``T(4,128)(2,1)``); asked for ``(bs, NKV, D)``
    slices in the attention dot's layout behind the row scatter, the compiler
    re-tiles the gather's operand — the whole pool — and the search finds that
    copy. At 8 kv heads (Laguna's) the tile is full and there never was one."""
    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_llama3_2_tpu.models.laguna import masked_attention

    layers, blocks, bs, d, lanes, width = 3, 1 + 16 * 288, 16, 128, 16, 288
    on = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=SingleDeviceSharding(v5e))
    shape = (layers, blocks, bs, nkv, d)

    def fn(kc, vc, table, q, k, v, pos):
        layer = jnp.int32(1)
        row = (layer * blocks + jnp.take_along_axis(table, (pos // bs) % width, axis=1)) * bs + pos % bs

        def write(a, fresh):
            return a.reshape((-1,) + a.shape[3:]).at[row].set(fresh).reshape(a.shape)

        def read(a):
            got = a.reshape((-1,) + a.shape[2:])[layer * blocks + table]
            return got.reshape((lanes, -1) + got.shape[3:])

        kc, vc = write(kc, k), write(vc, v)
        return masked_attention(q, read(kc), read(vc), jnp.ones((lanes, 1, width * bs), bool)), kc, vc

    text = jax.jit(fn, donate_argnums=(0, 1)).lower(
        on(shape), on(shape), on((lanes, width), jnp.int32), on((lanes, 1, 7 * nkv, d)),
        on((lanes, 1, nkv, d)), on((lanes, 1, nkv, d)), on((lanes, 1), jnp.int32)).compile().as_text()
    moves = pool_sized_moves(text, pool_dims(shape))
    assert bool(moves) == found, moves


@pytest.mark.parametrize("program", ["pdecode", "psfx"])
@pytest.mark.parametrize("family", sorted(MIXED_STACKS))
def test_a_mixed_stacks_block_gather_re_tiles_neither_pool(v5e, family, program, monkeypatch):
    """SmallThinker (4 kv heads: the case that was not) and Laguna (8: the one
    that always was) at their published attention widths, 16 lanes over the
    ring a lane the engine would lay out: neither ``pdecode`` nor a 512-row
    ``psfx`` holds a bulk copy, slice or update of the window pool's shape, the
    full pool's, or a layer of either."""
    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV

    monkeypatch.setenv(KERNEL_MODE_ENV, "compiled")
    cfg, train, ring = MIXED_STACKS[family]()
    model = decode_model_for(cfg)
    assert -(-(cfg.sliding_window - 1 + 512) // 16) == ring
    compiled = compile_paged(v5e, program, "rested", cfg=cfg, train=train, blocks=MIXED_BLOCKS)
    pool = jax.eval_shape(lambda: model.init_paged_cache(MIXED_BLOCKS, 16, window_blocks=1 + 16 * ring))
    assert pool.window.k.shape[1:] == (1 + 16 * ring, 16, cfg.num_kv_heads, 128)
    moves = pool_sized_moves(compiled.as_text(), pool_dims(pool.full.k.shape, pool.window.k.shape))
    assert not moves, moves
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


def ring_gathers(text, lanes, ring, nkv, bs=16):
    """Results of the compiled text in the shape of every lane's whole ring as
    ``LagunaDecode._attend``'s gather makes it: ``bf16[lanes · ring blocks,
    bs · NKV, 128]``, or the same with the lanes apart."""
    shapes = (f"bf16[{lanes * ring},{bs * nkv},128]", f"bf16[{lanes},{ring},{bs * nkv},128]")
    return [line.strip()[:120] for line in text.splitlines() if any(f" = {s}" in line for s in shapes)]


@pytest.mark.parametrize("family", sorted(MIXED_STACKS))
def test_a_mixed_stacks_decode_step_gathers_no_ring(v5e, family, monkeypatch):
    """``pdecode`` at the published attention widths, 16 lanes: a
    ``paged_decode_walk`` a run of layers, window runs among them, and no
    result of the shape of the lanes' whole rings (SmallThinker's
    ``bf16[16 · 288, 64, 128]`` was the cell's largest op) nor a bulk move of
    either pool's; the ``reference`` mode's program is the control that holds
    that gather, and a 512-row ``psfx`` keeps its own (one lane's)."""
    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
    from neuronx_distributed_llama3_2_tpu.models.laguna import layer_runs

    cfg, train, ring = MIXED_STACKS[family]()
    found = {}
    for mode in ("compiled", "reference"):
        monkeypatch.setenv(KERNEL_MODE_ENV, mode)
        text = compile_paged(v5e, "pdecode", "rested", cfg=cfg, train=train, blocks=MIXED_BLOCKS).as_text()
        walks = len(re.findall(r"custom_call_target=\"tpu_custom_call\".*paged_decode_walk", text))
        found[mode] = (walks, bool(ring_gathers(text, 16, ring, cfg.num_kv_heads)))
    assert found == {"compiled": (len(layer_runs(cfg)), False), "reference": (0, True)}, found
    monkeypatch.setenv(KERNEL_MODE_ENV, "compiled")
    text = compile_paged(v5e, "psfx", "rested", cfg=cfg, train=train, blocks=MIXED_BLOCKS).as_text()
    assert "paged_decode_walk" not in text and ring_gathers(text, 1, ring, cfg.num_kv_heads)


# ---------------------------------------------------------------------------
# compiled for a described v5e: a state-space decode step visits the state
# pool in place
# ---------------------------------------------------------------------------

def test_a_state_space_decode_step_visits_the_donated_state_pool_in_place(v5e, monkeypatch):
    """Jamba2-3B's widths at four layers (M A M M), 16 lanes over the cell's
    129 slots (a pool of a few MB the compiler parks in VMEM around the call,
    and copies back), as Mosaic compiles it: ``pdecode`` holds
    ``ssm_state_step`` once a run of state-space layers, the ``h`` pool
    aliased into and out of the program and of the call, and no bulk copy,
    slice or update of the pool's shape or of a layer of it — the pass over
    every slot it replaces was a ``dynamic-update-slice`` fusion of the whole
    pool."""
    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
    from neuronx_distributed_llama3_2_tpu.models.jamba import JAMBA_CONFIGS, JambaForCausalLM

    monkeypatch.setenv(KERNEL_MODE_ENV, "compiled")
    cfg = dataclasses.replace(
        JAMBA_CONFIGS["jamba2-3b"], num_layers=4, attn_layer_period=4, attn_layer_offset=1,
        vocab_size=4096, max_seq_len=1024)
    model = decode_model_for(cfg)
    assert model.uses_state_kernel()
    lanes, one = 16, SingleDeviceSharding(v5e)
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = on(jax.eval_shape(JambaForCausalLM(cfg).init, jax.random.key(0)))
    cache = on(jax.eval_shape(lambda: model.init_paged_cache(256, 16, state_blocks=129)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731

    def pdecode(params, cache, tokens, positions, tables, slots):
        return model.decode_step(params, cache, tokens, positions, tables, kv_limit=1024, state_tables=slots)

    compiled = jax.jit(pdecode, donate_argnums=(1,)).lower(
        params, cache, i32(lanes), i32(lanes), i32(lanes, 64), i32(lanes, 1)).compile()
    text = compiled.as_text()
    calls = re.findall(r"custom-call\(.*custom_call_target=\"tpu_custom_call\".*", text)
    assert len(calls) == 2 and all("ssm_state_step" in c and "output_to_operand_aliasing={{1}: (8, {})}" in c
                                   for c in calls), calls
    h = cache.state.h.shape                                           # (3, 129, 16, 5120)
    dims = {",".join(map(str, s)) for s in (h, (1,) + h[1:], h[1:], (h[0] * h[1],) + h[2:])}
    assert not pool_sized_moves(text, dims), pool_sized_moves(text, dims)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


# ---------------------------------------------------------------------------
# a sparse layer's chunk read: the Mosaic call in the program, and no tile of
# float32 scores in HBM
# ---------------------------------------------------------------------------

TILE_OF_SCORES = re.compile(r"f32\[[\d,]*512,2048\]")


@pytest.mark.parametrize("mode", ["compiled", "reference"])
def test_a_sparse_layers_chunk_read_keeps_its_scores_out_of_hbm(v5e, mode, monkeypatch):
    """MiniCPM-SALA's widths at two layers (L S), a ``psfx`` of 512 rows at
    the ladder's last rung as ``sala-longctx-steady`` runs it, compiled for the
    described v5e: with Pallas kernels on, the program holds one
    ``sparse_chunk_attend`` and no float32 array of a tile's scores — the
    ``(1, 2, 16, 512, 2048)`` that the tile walk, which the ``reference`` mode
    keeps, writes and reads back a tile (the control: the pattern finds it
    there)."""
    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV

    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    rows, rung, bs = 512, 33280, 64
    cfg = dataclasses.replace(
        SALA_CONFIGS["minicpm-sala"], num_layers=2, mixer_types=("lightning-attn", "minicpm4"),
        vocab_size=2048, max_seq_len=rung, dtype=jnp.bfloat16)
    model = decode_model_for(cfg)
    kernel = mode == "compiled"
    assert model.chunk_tiles(rows, rung - rows, rung) == ((True, 65, 65) if kernel else (False, 17, 17))
    one = SingleDeviceSharding(v5e)
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = on(abstract_weights(cfg, SalaForCausalLM))
    cache = on(jax.eval_shape(lambda: model.init_paged_cache(1024, bs, state_blocks=2)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731

    def psfx(params, cache, ids, start, length, table, slots):
        return model.forward(params, cache, ids, start, None, return_hidden=True, block_tables=table,
                             kv_limit=rung, row_live=length, state_tables=slots)

    text = jax.jit(psfx, donate_argnums=(1,)).lower(
        params, cache, i32(1, rows), i32(1), i32(1), i32(1, (rung + rows) // bs), i32(1, 1)).compile().as_text()
    calls = re.findall(r"custom-call\(.*custom_call_target=\"tpu_custom_call\".*", text)
    assert [("sparse_chunk_attend" in c) for c in calls] == ([True] if kernel else []), calls
    assert bool(TILE_OF_SCORES.search(text)) != kernel, TILE_OF_SCORES.findall(text)[:4]
