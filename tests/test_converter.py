"""Checkpoint converter tests (VERDICT missing #7): HF↔native roundtrips and
the CLI entry points (reference scripts/checkpoint_converter.py:238,393)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
    params_from_hf,
    params_to_hf,
)
from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import main as cli

TINY = LLAMA_CONFIGS["tiny"]


def _tiny_params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def test_hf_roundtrip_exact():
    """params → HF state dict → params is the identity (fp32 tiny)."""
    params = _tiny_params()
    back = params_from_hf(params_to_hf(params, TINY), TINY)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_hf_state_dict_names_match_transformers_convention():
    sd = params_to_hf(_tiny_params(), TINY)
    assert "model.embed_tokens.weight" in sd
    assert "model.layers.0.self_attn.q_proj.weight" in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd
    assert "model.norm.weight" in sd
    # tiny ties embeddings: no lm_head in the exported dict (HF convention)
    assert ("lm_head.weight" in sd) == (not TINY.tie_word_embeddings)
    # torch Linear layout (out, in)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (
        TINY.intermediate_size,
        TINY.hidden_size,
    )


def test_cli_hf_to_native_to_hf(tmp_path):
    from safetensors.numpy import save_file

    params = _tiny_params()
    sd = params_to_hf(params, TINY)
    hf_dir = tmp_path / "hf"
    hf_dir.mkdir()
    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
              str(hf_dir / "model.safetensors"))

    ckpt_dir = tmp_path / "native"
    cli([
        "--direction", "hf-to-native", "--model", "tiny",
        "--input", str(hf_dir), "--output", str(ckpt_dir), "--tag", "imported",
    ])
    assert (ckpt_dir / "imported" / "done").exists()

    out_dir = tmp_path / "hf_back"
    cli([
        "--direction", "native-to-hf", "--model", "tiny",
        "--input", str(ckpt_dir), "--output", str(out_dir), "--tag", "imported",
    ])
    from safetensors.numpy import load_file

    back = load_file(str(out_dir / "model.safetensors"))
    assert set(back) == set(sd)
    for k in sd:
        np.testing.assert_allclose(back[k], np.asarray(sd[k], np.float32), atol=1e-6)
    assert (out_dir / "config.json").exists()


def test_cli_strip_optimizer(tmp_path):
    from neuronx_distributed_llama3_2_tpu.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    params = _tiny_params()
    fake_opt = {"m": jax.tree.map(jnp.zeros_like, params)}
    src = tmp_path / "train"
    save_checkpoint(str(src), tag="step_5", model=params, optimizer=fake_opt)
    dst = tmp_path / "export"
    cli([
        "--direction", "strip-optimizer", "--model", "tiny",
        "--input", str(src), "--output", str(dst), "--tag", "step_5",
    ])
    template = jax.eval_shape(LlamaForCausalLM(TINY).init, jax.random.key(0))
    loaded = load_checkpoint(str(dst), tag="step_5", model=template)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded["model"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # optimizer state not carried over
    with pytest.raises(Exception):
        load_checkpoint(str(dst), tag="step_5", optimizer=fake_opt)


def test_cli_copy_tag_with_optimizer(tmp_path):
    """copy-tag: template-free offline move of a full training checkpoint
    (model + optimizer state) to a new root/tag; loads back identically
    (the role of the reference's convert_zero_checkpoints CLI,
    optimizer/convert_zero_checkpoints.py:176 — dp resharding itself
    dissolves into load-time specs)."""
    from neuronx_distributed_llama3_2_tpu.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    model = LlamaForCausalLM(TINY)
    params = model.init(jax.random.key(0))
    fake_opt = {"mu": jax.tree.map(lambda p: p * 0.5, params), "step": jnp.int32(7)}
    src, dst = tmp_path / "src", tmp_path / "dst"
    save_checkpoint(
        str(src), tag="step100", model=params, optimizer=fake_opt,
        scheduler={"lr": 1e-4}, user_content={"note": "x"},
    )

    cli([
        "--direction", "copy-tag", "--input", str(src),
        "--output", str(dst), "--tag", "step100", "--out-tag", "exported",
    ])

    loaded = load_checkpoint(
        str(dst), tag="exported",
        model=jax.eval_shape(lambda: params),
        optimizer=jax.eval_shape(lambda: fake_opt),
    )
    assert loaded["scheduler"] == {"lr": 1e-4}
    assert loaded["user_content"] == {"note": "x"}
    for a, b in zip(jax.tree.leaves(loaded["model"]), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree.leaves(loaded["optimizer"]), jax.tree.leaves(fake_opt)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cli_hf_to_native_all_families(tmp_path):
    """The registry covers every family: import a tiny HF checkpoint of each
    architecture through the CLI."""
    import torch
    from safetensors.numpy import save_file

    from neuronx_distributed_llama3_2_tpu.checkpoint import load_checkpoint
    from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import (
        _resolve_model,
    )

    # build tiny HF models per family (reuse the parity-test constructors)
    from tests.test_dbrx import _hf_tiny_dbrx, _hf_tiny_mixtral
    from tests.test_gptneox import _hf_codegen, _hf_neox
    from tests.test_bert import _hf_bert

    cases = {
        "tiny-moe": _hf_tiny_mixtral(),
        "tiny-dbrx": _hf_tiny_dbrx(),
        "tiny-neox": _hf_neox(),
        "tiny-codegen": _hf_codegen(),
        "tiny-bert": _hf_bert(),
    }
    for name, hf in cases.items():
        hf_dir = tmp_path / f"hf_{name}"
        hf_dir.mkdir()
        sd = {
            k: v.detach().numpy().astype(np.float32)
            for k, v in hf.state_dict().items()
        }
        save_file(sd, str(hf_dir / "model.safetensors"))
        out = tmp_path / f"native_{name}"
        cli([
            "--direction", "hf-to-native", "--model", name,
            "--input", str(hf_dir), "--output", str(out), "--tag", "imported",
        ])
        entry = _resolve_model(name)
        template = jax.eval_shape(
            entry["model_cls"](entry["config"]).init, jax.random.key(0)
        )
        loaded = load_checkpoint(str(out), tag="imported", model=template)
        assert loaded is not None, name


def test_cli_unknown_model_lists_choices():
    with pytest.raises(KeyError, match="tiny-neox"):
        cli([
            "--direction", "hf-to-native", "--model", "nope",
            "--input", "/tmp/x", "--output", "/tmp/y",
        ])


@pytest.mark.slow
def test_generate_cli_arg_validation():
    """examples/generate.py argument paths: unknown model lists choices,
    BERT is refused by the decode dispatcher, missing prompt errors, and
    malformed --prompt-ids fail rather than generate garbage."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "examples", "generate.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    def run(*args):
        return subprocess.run(
            [sys.executable, script, *args],
            capture_output=True, text=True, env=env, timeout=240,
        )

    r = run("--model", "nope", "--random-init", "--prompt-ids", "1,2")
    assert r.returncode != 0 and "tiny-neox" in (r.stderr + r.stdout)

    r = run(
        "--model", "tiny-bert", "--random-init", "--prompt-ids", "1,2",
        "--cpu-devices", "2",
    )
    assert r.returncode != 0
    assert "bidirectional" in (r.stderr + r.stdout)

    r = run("--model", "tiny", "--random-init", "--cpu-devices", "2")
    assert r.returncode != 0
    assert "--prompt" in (r.stderr + r.stdout)

    r = run(
        "--model", "tiny", "--random-init", "--prompt-ids", "1,a,2",
        "--cpu-devices", "2",
    )
    assert r.returncode != 0  # malformed ids must not silently generate



def test_to_hf_roundtrip_all_families():
    """Native→HF for every family (VERDICT r2 missing #3): (a) to_hf values
    bit-match the original HF state dict on every exported key; (b)
    from_hf(to_hf(params)) is the identity — no information loss."""
    from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import (
        _resolve_model,
    )
    from tests.test_dbrx import _hf_tiny_dbrx, _hf_tiny_mixtral
    from tests.test_gptneox import _hf_codegen, _hf_neox
    from tests.test_bert import _hf_bert

    cases = {
        "tiny-moe": _hf_tiny_mixtral(),
        "tiny-dbrx": _hf_tiny_dbrx(),
        "tiny-neox": _hf_neox(),
        "tiny-codegen": _hf_codegen(),
        "tiny-bert": _hf_bert(),
    }
    for name, hf in cases.items():
        entry = _resolve_model(name)
        sd = {
            k: v.detach().numpy().astype(np.float32)
            for k, v in hf.state_dict().items()
        }
        params = entry["from_hf"](sd, entry["config"])
        back = entry["to_hf"](params, entry["config"])
        for k, v in back.items():
            assert k in sd, (name, k)
            np.testing.assert_allclose(
                v, sd[k], atol=1e-6, err_msg=f"{name}:{k}"
            )
        again = entry["from_hf"](back, entry["config"])
        for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(again)[0],
        ):
            assert pa == pb
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"{name}:{pa}",
            )


def test_cli_include_optimizer_export(tmp_path):
    """--include-optimizer: fp32 master + moments exported to
    optimizer/*.safetensors with HF names, elementwise-aligned with the
    weight export (reference optimizer/convert_zero_checkpoints.py:176)."""
    from safetensors.numpy import load_file

    from neuronx_distributed_llama3_2_tpu.checkpoint import save_checkpoint
    from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
        OptimizerState,
    )

    params = _tiny_params()
    opt = OptimizerState(
        step=jnp.asarray(7, jnp.int32),
        master=jax.tree.map(lambda p: p.astype(jnp.float32), params),
        mu=jax.tree.map(lambda p: jnp.full(p.shape, 0.25, jnp.float32), params),
        nu=jax.tree.map(lambda p: jnp.full(p.shape, 0.5, jnp.float32), params),
    )
    ckpt = tmp_path / "native"
    save_checkpoint(str(ckpt), tag="trained", model=params, optimizer=opt)

    out = tmp_path / "hf"
    cli([
        "--direction", "native-to-hf", "--model", "tiny",
        "--input", str(ckpt), "--output", str(out), "--tag", "trained",
        "--include-optimizer",
    ])
    exported = load_file(str(out / "optimizer" / "optimizer.safetensors"))
    meta = json.loads((out / "optimizer" / "optimizer.json").read_text())
    assert meta["kinds"] == ["master", "mu", "nu"]
    assert meta["step"] == 7
    # moments carry the HF layout transforms; constant trees stay constant
    key = "mu::model.layers.0.self_attn.q_proj.weight"
    assert exported[key].dtype == np.float32
    np.testing.assert_array_equal(exported[key], 0.25)
    np.testing.assert_array_equal(
        exported["nu::model.norm.weight"], 0.5
    )
    # master round-trips the weights bit-exactly (fp32)
    from neuronx_distributed_llama3_2_tpu.models.llama import params_to_hf

    want = params_to_hf(params, TINY)
    for k, v in want.items():
        np.testing.assert_array_equal(exported[f"master::{k}"], v)


def test_cli_include_optimizer_without_master(tmp_path):
    """Pure-bf16 runs (use_master_weights=False) export mu/nu only."""
    from safetensors.numpy import load_file

    from neuronx_distributed_llama3_2_tpu.checkpoint import save_checkpoint
    from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
        OptimizerState,
    )

    params = _tiny_params()
    opt = OptimizerState(
        step=jnp.asarray(3, jnp.int32),
        master=None,
        mu=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        nu=jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), params),
    )
    ckpt = tmp_path / "native"
    save_checkpoint(str(ckpt), tag="trained", model=params, optimizer=opt)
    out = tmp_path / "hf"
    cli([
        "--direction", "native-to-hf", "--model", "tiny",
        "--input", str(ckpt), "--output", str(out), "--tag", "trained",
        "--include-optimizer",
    ])
    meta = json.loads((out / "optimizer" / "optimizer.json").read_text())
    assert meta["kinds"] == ["mu", "nu"]
    exported = load_file(str(out / "optimizer" / "optimizer.safetensors"))
    assert not any(k.startswith("master::") for k in exported)


def test_exported_config_json_loads_in_transformers():
    """config.json uses each family's real HF attribute names (review
    finding: Llama-style keys would make transformers build default-sized
    models and fail on shape mismatch)."""
    from transformers import (
        CodeGenConfig,
        DbrxConfig,
        GPTNeoXConfig,
        MixtralConfig,
    )

    from neuronx_distributed_llama3_2_tpu.models import (
        DBRX_CONFIGS,
        GPTNEOX_CONFIGS,
        MIXTRAL_CONFIGS,
    )
    from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import (
        _hf_config_dict,
    )

    d = _hf_config_dict(DBRX_CONFIGS["tiny-dbrx"])
    hc = DbrxConfig(**{k: v for k, v in d.items() if k != "architectures"})
    assert hc.d_model == 64 and hc.n_layers == 2 and hc.n_heads == 8
    assert hc.attn_config.kv_n_heads == 4 and hc.attn_config.clip_qkv == 8.0
    assert hc.ffn_config.moe_num_experts == 4 and hc.ffn_config.moe_top_k == 2

    d = _hf_config_dict(GPTNEOX_CONFIGS["tiny-codegen"])
    hc = CodeGenConfig(**{k: v for k, v in d.items() if k != "architectures"})
    cfg = GPTNEOX_CONFIGS["tiny-codegen"]
    assert hc.n_embd == cfg.hidden_size and hc.n_layer == cfg.num_layers
    assert hc.n_head == cfg.num_heads
    assert hc.rotary_dim == int(cfg.head_dim * cfg.rotary_pct)

    d = _hf_config_dict(GPTNEOX_CONFIGS["tiny-neox"])
    hc = GPTNeoXConfig(**{k: v for k, v in d.items() if k != "architectures"})
    cfg = GPTNEOX_CONFIGS["tiny-neox"]
    assert hc.hidden_size == cfg.hidden_size
    assert hc.rotary_pct == cfg.rotary_pct
    assert hc.use_parallel_residual == cfg.parallel_residual

    d = _hf_config_dict(MIXTRAL_CONFIGS["tiny-moe"])
    hc = MixtralConfig(**{k: v for k, v in d.items() if k != "architectures"})
    cfg = MIXTRAL_CONFIGS["tiny-moe"]
    assert hc.num_local_experts == cfg.num_experts
    assert hc.num_experts_per_tok == cfg.top_k
    assert hc.num_key_value_heads == cfg.num_kv_heads


def test_mllama_to_hf_roundtrip():
    """Vision family (beyond-reference) round-trips both directions: to_hf
    values match the HF state dict bit-exactly, and from_hf(to_hf(params))
    is the identity."""
    from tests.test_mllama import TINY as MLLAMA_TINY, _hf_tiny

    from neuronx_distributed_llama3_2_tpu.models.mllama import (
        mllama_params_from_hf,
        mllama_params_to_hf,
    )

    hf = _hf_tiny()
    sd = {
        k: v.detach().numpy().astype(np.float32)
        for k, v in hf.state_dict().items()
    }
    params = mllama_params_from_hf(sd, MLLAMA_TINY)
    back = mllama_params_to_hf(params, MLLAMA_TINY)
    assert set(back) == set(sd)  # every HF tensor exported, none extra
    for k, v in back.items():
        assert np.asarray(v).shape == np.asarray(sd[k]).shape, k
        np.testing.assert_allclose(np.asarray(v), sd[k], atol=1e-6, err_msg=k)
    again = mllama_params_from_hf(back, MLLAMA_TINY)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(again)[0],
    ):
        assert pa == pb
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=str(pa),
        )


def test_mllama_config_json():
    from neuronx_distributed_llama3_2_tpu.models import MLLAMA_CONFIGS
    from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import (
        _hf_config_dict,
    )

    d = _hf_config_dict(MLLAMA_CONFIGS["llama3.2-11b-vision"])
    assert d["model_type"] == "mllama"
    assert d["text_config"]["num_hidden_layers"] == 40
    assert d["text_config"]["rope_scaling"]["factor"] == 8.0
    assert d["vision_config"]["max_num_tiles"] == 4


def test_mllama_vision_config_loads_in_transformers():
    """Review finding: max_aspect_ratio_id is a read-only property on HF's
    MllamaVisionConfig — the export must carry supported_aspect_ratios and
    vision_output_dim instead, and they must reproduce our derived values."""
    from transformers.models.mllama.configuration_mllama import (
        MllamaVisionConfig as HFVision,
    )

    from neuronx_distributed_llama3_2_tpu.models import MLLAMA_CONFIGS
    from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import (
        _hf_config_dict,
    )

    for name in ("llama3.2-11b-vision", "tiny-mllama"):
        ours = MLLAMA_CONFIGS[name].vision
        d = _hf_config_dict(MLLAMA_CONFIGS[name])["vision_config"]
        hv = HFVision(**d)
        assert hv.max_aspect_ratio_id == ours.max_aspect_ratio_id, name
        assert hv.vision_output_dim == ours.output_dim, name
        assert hv.num_global_layers == ours.num_global_layers, name


def test_cli_refuses_mllama_for_text_only_entrypoints():
    """generate.py / pretrain_llama.py give mllama keys a clean refusal
    instead of an AttributeError traceback (review finding)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "generate.py"),
         "--model", "tiny-mllama", "--prompt-ids", "1,2,3",
         "--random-init", "--cpu-devices", "2"],
        capture_output=True, text=True, timeout=240,
    )
    assert r.returncode != 0
    assert "multimodal decode needs image inputs" in (r.stderr + r.stdout)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "pretrain_llama.py"),
         "--model", "tiny-mllama", "--ckpt-dir", "/tmp/nope",
         "--synthetic", "1000", "--steps", "1", "--cpu-devices", "2"],
        capture_output=True, text=True, timeout=240,
    )
    assert r.returncode != 0
    assert "vision family needs image inputs" in (r.stderr + r.stdout)
