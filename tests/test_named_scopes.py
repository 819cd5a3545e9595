"""The device work's named scopes (serving/tracing.py ``SCOPES``).

Scopes change HLO metadata only, so nothing here needs a run: the tiny Llama,
GPT-NeoX and Mixtral train steps and the three paged programs are lowered and
compiled for the host, and every scope of the vocabulary has to appear as a
whole part of some instruction's ``op_name`` path — which is what a device
trace shows as ``tf_op`` and ``benchmarks/program_trace.py`` reads. Also the
pipeline executor's schedule counters, which no trace can see.
"""

import ast
import dataclasses
import os
import re

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.gptneox import (
    GPTNEOX_CONFIGS,
    GPTNeoXForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.mixtral import (
    MIXTRAL_CONFIGS,
    MixtralForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.pipeline import PipelinedCausalLM
from neuronx_distributed_llama3_2_tpu.pipeline import model as pipeline_model
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine
from neuronx_distributed_llama3_2_tpu.serving.tracing import (
    BLOCK_SCOPES,
    CHILD_SCOPES,
    DETAIL_SCOPES,
    PROGRAM_SCOPES,
    SCOPES,
)
from neuronx_distributed_llama3_2_tpu.trainer import (
    TrainingConfig,
    initialize_parallel_model,
    make_train_step,
)

from benchmarks import program_trace

PACKAGE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "neuronx_distributed_llama3_2_tpu",
)
ATTN = ("attn", "attn/qkv", "attn/rope", "attn/sdpa", "attn/o_proj")
TRAIN = ("train_step", "embed", "norm", "lm_head", "ce", "grad_clip", "optimizer") + ATTN


def op_names(lowered) -> set:
    """The ``op_name`` paths of the compiled program's instructions. Compiled,
    because before XLA inlines a scan body or a remat region the path inside it
    starts at that body's own root."""
    return set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))


def scopes_in(lowered) -> set:
    """Every vocabulary scope that is a whole part of some path."""
    found = set()
    for path in op_names(lowered):
        found.update(program_trace.scopes_of(path))
    return found


def build_train_step(model, **parallel):
    config = TrainingConfig(**parallel)
    config.initialize()
    step = make_train_step(model, config)
    state, _ = initialize_parallel_model(model, config, key=jax.random.key(0))
    ids = np.zeros((4, 16), np.int32)
    return step, state, {"input_ids": ids, "labels": ids}


@pytest.mark.parametrize("family", ["llama", "gpt-neox", "mixtral"])
def test_train_step_carries_every_training_scope(family):
    # remat on: the backward then holds `rematted_computation` parts too
    if family == "llama":
        model = LlamaForCausalLM(dataclasses.replace(
            LLAMA_CONFIGS["tiny"], remat="selective", loss_chunk_size=8))
        blocks = ("mlp",)
    elif family == "gpt-neox":
        model = GPTNeoXForCausalLM(GPTNEOX_CONFIGS["tiny-neox"])
        blocks = ("mlp",)
    else:
        model = MixtralForCausalLM(MIXTRAL_CONFIGS["tiny-moe"])
        blocks = ("moe", "moe/router", "moe/experts")
    step, state, batch = build_train_step(model, tensor_parallel_size=2)
    lowered = step.lower(state, batch)
    found = scopes_in(lowered)
    assert set(TRAIN + blocks) <= found, set(TRAIN + blocks) - found
    # the jitted callable keeps its name: scopes are not a rename
    assert "module @jit_train_step" in lowered.as_text()[:300]


def test_forward_recompute_and_backward_can_be_told_apart_in_a_train_step():
    model = LlamaForCausalLM(dataclasses.replace(LLAMA_CONFIGS["tiny"], remat="selective"))
    step, state, batch = build_train_step(model)
    phases = {}
    for path in op_names(step.lower(state, batch)):
        if "attn" in program_trace.scopes_of(path):
            phases.setdefault(program_trace.phase_of(path), path)
    # plain autodiff: the forward is the linearisation (`jvp`); what is left
    # outside it are loop-invariant pieces XLA hoists, never a layer's body
    assert {"replay", "recompute", "backward"} <= set(phases), phases
    assert "rematted_computation" in phases["recompute"]
    assert "transpose(jvp(" in phases["backward"] and "jvp(" in phases["replay"]


@pytest.fixture(scope="module")
def paged_programs():
    """Registry of a tiny engine that has dispatched pctx, psfx and pdecode:
    prefix caching gives the second request a cached prefix (psfx)."""
    out = {}
    for name, cfg, model_cls in (
        ("llama", LLAMA_CONFIGS["tiny"], LlamaForCausalLM),
        ("mixtral", MIXTRAL_CONFIGS["tiny-moe"], MixtralForCausalLM),
    ):
        params = model_cls(cfg).init(jax.random.key(0))
        eng = InferenceEngine(cfg, params, max_batch=2, max_seq_len=64, buckets=[16, 32])
        paged = PagedServingEngine(
            eng, GenerationConfig(max_new_tokens=3),
            PagedConfig(block_size=8, num_blocks=32),
        )
        prompt = list(range(1, 21))
        paged.submit(prompt)
        paged.run_to_completion()
        paged.submit(prompt + [30, 31])
        paged.run_to_completion()
        out[name] = paged.program_registry()
    return out


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_paged_programs_carry_their_kind_and_the_serving_scopes(paged_programs, family):
    by_kind = {}
    for rec in paged_programs[family].values():
        by_kind.setdefault(rec.kind, rec)
    assert {"pctx", "psfx", "pdecode"} <= set(by_kind)
    ffn = ("mlp",) if family == "llama" else ("moe", "moe/router", "moe/experts")
    common = ("embed", "norm", "lm_head", "sample", "attn/kv_write") + ATTN + ffn
    for kind in ("pctx", "psfx", "pdecode"):
        rec = by_kind[kind]
        lowered = rec.lower()
        found = scopes_in(lowered)
        want = set(common) | {kind}
        if kind != "pctx":                  # whole-prompt prefill reads no cache
            want.add("attn/kv_read")
        assert want <= found, (kind, want - found)
        assert not found & (set(PROGRAM_SCOPES) - {kind})
        # the module is still called after the callable, `fn`: the
        # benchmark's `jit_fn` filter keeps working
        assert rec.fn.__name__ == "fn"
        assert "module @jit_fn" in lowered.as_text()[:300]
    # the small programs are scoped by their kind too
    assert "lane_set" in by_kind or "table_delta" in by_kind


def test_the_vocabulary_is_covered_by_the_two_suites_above_and_nothing_else_is_used():
    covered = set(TRAIN) | {"mlp", "moe", "moe/router", "moe/experts", "sample",
                            "attn/kv_write", "attn/kv_read", "pctx", "psfx", "pdecode"}
    assert covered == set(SCOPES)
    assert tuple(program_trace.SCOPES) == tuple(SCOPES)      # the readers' copy
    # every literal scope name in the program belongs to the vocabulary
    # ... or to the finer scopes below it, which the shared readers book to
    # their parent (tests/test_olmoe_decode.py finds them in the programs)
    names = set(PROGRAM_SCOPES) | set(BLOCK_SCOPES) | {
        c for cs in (*CHILD_SCOPES.values(), *DETAIL_SCOPES.values()) for c in cs}
    used = set()
    for root, _, files in os.walk(PACKAGE):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(root, f)) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "named_scope" and node.args
                        and isinstance(node.args[0], ast.Constant)):
                    used.add(node.args[0].value)
    assert used and used <= names, used - names
    # pctx/psfx/pdecode are entered by kind, not by literal; "selective" names
    # the reference path no program enters since the no-drop dispatch is
    # all-experts at every shape (moe/experts.py); "full" / "window" are
    # entered by a layer's kind (models/laguna.py, LagunaDecode.forward)
    assert names - used == {"pctx", "psfx", "pdecode", "selective", "full", "window"}


@pytest.mark.parametrize("M,pp,bubble", [(8, 2, 0.2), (4, 4, 0.6)])
def test_1f1b_schedule_counters(M, pp, bubble):
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

    parallel_state.initialize_model_parallel(pipeline_model_parallel_size=pp)
    model = PipelinedCausalLM(
        LlamaForCausalLM(LLAMA_CONFIGS["tiny"]), num_microbatches=M, schedule="1f1b")
    c = model.schedule_counters()
    assert c == {"rotations": M + 2 * (pp - 1), "useful_lane_rotations": 2 * M,
                 "stage_forwards_per_slot": 1}
    assert 1 - c["useful_lane_rotations"] / (2 * c["rotations"]) == pytest.approx(bubble)
    gpipe = dataclasses.replace(model, schedule="gpipe").schedule_counters()
    assert gpipe == {"rotations": M + pp - 1, "useful_lane_rotations": 2 * M,
                     "stage_forwards_per_slot": 1}
    # the interleaved memory-bounded executor still replays a stage from its
    # stashed input; under autodiff the backward reads the forward's residuals
    replaying = dataclasses.replace(model, schedule="interleaved", num_model_chunks=1)
    assert replaying.schedule_counters()["stage_forwards_per_slot"] == 2
    autodiff = dataclasses.replace(replaying, memory_bounded_backward=False)
    assert autodiff.schedule_counters()["stage_forwards_per_slot"] == 1


def test_train_step_of_a_pipelined_model_returns_the_counters_as_python_ints():
    model = PipelinedCausalLM(
        LlamaForCausalLM(LLAMA_CONFIGS["tiny"]), num_microbatches=4, schedule="1f1b")
    step, state, batch = build_train_step(
        model, pipeline_parallel_size=2, pipeline_schedule="1f1b", num_model_chunks=1)
    before = len(pipeline_model.COMPILED_SCHEDULES)
    state, metrics = step(state, batch)
    assert (metrics["rotations"], metrics["useful_lane_rotations"]) == (6, 8)
    assert type(metrics["rotations"]) is int and type(metrics["useful_lane_rotations"]) is int
    assert float(metrics["loss"]) > 0
    # one stage forward a slot, and the ring its residuals wait in: three sets
    # of at least a stage's layer inputs (2 layers a stage, (1, 16, hidden) f32)
    ring = metrics["residual_ring_bytes"]
    assert metrics["stage_forwards_per_slot"] == 1 and type(ring) is int
    assert ring >= 3 * 2 * 16 * LLAMA_CONFIGS["tiny"].hidden_size * 4
    # the executor recorded the schedule it traced, once; the wrapper passes
    # the jitted step's own attributes through
    assert pipeline_model.COMPILED_SCHEDULES[before:] == [{
        "schedule": "1f1b", "pp": 2, "num_microbatches": 4,
        "rotations": 6, "useful_lane_rotations": 8,
        "stage_forwards_per_slot": 1, "residual_ring_bytes": ring,
    }]
    assert step._cache_size() == 1 and callable(step.lower)
    # an unpipelined model's step is the plain jitted function, no counters
    plain = make_train_step(LlamaForCausalLM(LLAMA_CONFIGS["tiny"]), TrainingConfig())
    assert not hasattr(plain, "_read")
