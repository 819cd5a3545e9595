from neuronx_distributed_llama3_2_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LLAMA_CONFIGS,
)
from neuronx_distributed_llama3_2_tpu.models.mixtral import (  # noqa: F401
    MIXTRAL_CONFIGS,
    MixtralConfig,
    MixtralForCausalLM,
    params_from_hf_mixtral,
    params_to_hf_mixtral,
)
from neuronx_distributed_llama3_2_tpu.models.olmoe import (  # noqa: F401
    OLMOE_CONFIGS,
    OlmoeConfig,
    OlmoeForCausalLM,
    params_from_hf_olmoe,
    params_to_hf_olmoe,
)
from neuronx_distributed_llama3_2_tpu.models.sarvam import (  # noqa: F401
    SARVAM_CONFIGS,
    SarvamConfig,
    SarvamForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.xing import (  # noqa: F401
    XING_CONFIGS,
    XingConfig,
    XingForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.jamba import (  # noqa: F401
    JAMBA_CONFIGS,
    JambaConfig,
    JambaForCausalLM,
    params_from_hf_jamba,
    params_to_hf_jamba,
)
from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (  # noqa: F401
    SALA_CONFIGS,
    SalaConfig,
    SalaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.models.brumby import (  # noqa: F401
    BRUMBY_CONFIGS,
    BrumbyConfig,
    BrumbyForCausalLM,
    params_from_hf_brumby,
    params_to_hf_brumby,
)
from neuronx_distributed_llama3_2_tpu.models.laguna import (  # noqa: F401
    LAGUNA_CONFIGS,
    LagunaConfig,
    LagunaForCausalLM,
    params_from_hf_laguna,
    params_to_hf_laguna,
)
from neuronx_distributed_llama3_2_tpu.models.smallthinker import (  # noqa: F401
    SMALLTHINKER_CONFIGS,
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
    params_from_hf_smallthinker,
    params_to_hf_smallthinker,
)
from neuronx_distributed_llama3_2_tpu.models.dbrx import (  # noqa: F401
    DBRX_CONFIGS,
    DbrxConfig,
    DbrxForCausalLM,
    params_from_hf_dbrx,
    params_to_hf_dbrx,
)
from neuronx_distributed_llama3_2_tpu.models.bert import (  # noqa: F401
    BERT_CONFIGS,
    BertConfig,
    BertForPreTraining,
    params_from_hf_bert,
    params_to_hf_bert,
)
from neuronx_distributed_llama3_2_tpu.models.gptneox import (  # noqa: F401
    GPTNEOX_CONFIGS,
    GPTNeoXConfig,
    GPTNeoXForCausalLM,
    params_from_hf_codegen,
    params_from_hf_neox,
    params_to_hf_codegen,
    params_to_hf_neox,
)
from neuronx_distributed_llama3_2_tpu.models.mllama import (  # noqa: F401
    MLLAMA_CONFIGS,
    MllamaConfig,
    MllamaForConditionalGeneration,
    MllamaTextConfig,
    MllamaVisionConfig,
    mllama_params_from_hf,
    mllama_params_to_hf,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (  # noqa: F401
    params_from_hf,
    params_to_hf,
)


def model_registry():
    """name → {config, model_cls, from_hf, to_hf} across every family
    (the reference's per-family converter table,
    scripts/checkpoint_converter.py:33). Shared by the converter CLI and the
    pretrain example."""
    reg = {}
    for name, cfg in LLAMA_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": LlamaForCausalLM,
            "from_hf": params_from_hf, "to_hf": params_to_hf,
        }
    for name, cfg in MIXTRAL_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": MixtralForCausalLM,
            "from_hf": params_from_hf_mixtral, "to_hf": params_to_hf_mixtral,
        }
    for name, cfg in OLMOE_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": OlmoeForCausalLM,
            "from_hf": params_from_hf_olmoe, "to_hf": params_to_hf_olmoe,
        }
    for name, cfg in SARVAM_CONFIGS.items():
        # the catalog publishes no tensor names for sarvam_mla: no HF map
        reg[name] = {
            "config": cfg, "model_cls": SarvamForCausalLM,
            "from_hf": None, "to_hf": None,
        }
    for name, cfg in XING_CONFIGS.items():
        # as sarvam's: the catalog publishes no tensor names for xing4_0
        reg[name] = {
            "config": cfg, "model_cls": XingForCausalLM,
            "from_hf": None, "to_hf": None,
        }
    for name, cfg in BRUMBY_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": BrumbyForCausalLM,
            "from_hf": params_from_hf_brumby, "to_hf": params_to_hf_brumby,
        }
    for name, cfg in JAMBA_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": JambaForCausalLM,
            "from_hf": params_from_hf_jamba, "to_hf": params_to_hf_jamba,
        }
    for name, cfg in SALA_CONFIGS.items():
        # the catalog publishes the configuration and no tensor names: no HF map
        reg[name] = {
            "config": cfg, "model_cls": SalaForCausalLM,
            "from_hf": None, "to_hf": None,
        }
    for name, cfg in LAGUNA_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": LagunaForCausalLM,
            "from_hf": params_from_hf_laguna, "to_hf": params_to_hf_laguna,
        }
    for name, cfg in SMALLTHINKER_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": SmallThinkerForCausalLM,
            "from_hf": params_from_hf_smallthinker, "to_hf": params_to_hf_smallthinker,
        }
    for name, cfg in DBRX_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": DbrxForCausalLM,
            "from_hf": params_from_hf_dbrx, "to_hf": params_to_hf_dbrx,
        }
    for name, cfg in GPTNEOX_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": GPTNeoXForCausalLM,
            "from_hf": (
                params_from_hf_codegen if cfg.rotary_interleaved
                else params_from_hf_neox
            ),
            "to_hf": (
                params_to_hf_codegen if cfg.rotary_interleaved
                else params_to_hf_neox
            ),
        }
    for name, cfg in BERT_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": BertForPreTraining,
            "from_hf": params_from_hf_bert, "to_hf": params_to_hf_bert,
        }
    for name, cfg in MLLAMA_CONFIGS.items():
        reg[name] = {
            "config": cfg, "model_cls": MllamaForConditionalGeneration,
            "from_hf": mllama_params_from_hf, "to_hf": mllama_params_to_hf,
        }
    return reg


def resolve_model(name: str):
    reg = model_registry()
    if name not in reg:
        raise KeyError(
            f"unknown model {name!r}; known: {', '.join(sorted(reg))}"
        )
    return reg[name]
