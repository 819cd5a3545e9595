"""OLMoE model family (Muennighoff et al., arXiv:2409.02060; HF
``OlmoeForCausalLM``), TPU-native.

A pre-norm Llama-style block with multi-head attention and a sparse
feed-forward of many small experts (64 of width 1,024, 8 per token, no
shared expert). Two things set it apart from Mixtral, both facts of the
*model* config that the shared block machinery reads:

- **QK-norm** (``LlamaConfig.qk_norm``): an RMSNorm with a learned scale over
  the whole projected query and the whole projected key — all heads jointly —
  between the projection and the rotary embedding
  (:meth:`..models.llama.LlamaAttention._qk_norm`);
- **top-k gates as they are** (``MixtralConfig.normalize_top_k = False``,
  HF ``norm_topk_prob: false``): the router's softmax runs over all experts
  and the chosen probabilities are *not* renormalised, so a token's gates sum
  to less than one.

Training (TP/SP/EP/ZeRO-1, pipeline) and KV-cache decode
(:class:`..inference.MixtralDecode` — ``OlmoeConfig`` is a ``MixtralConfig``,
dispatched by :func:`..inference.model.decode_model_for`) work unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp

from neuronx_distributed_llama3_2_tpu.models.mixtral import (
    MixtralConfig,
    MixtralForCausalLM,
    params_from_hf_mixtral,
    params_to_hf_mixtral,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(MixtralConfig):
    """MixtralConfig with OLMoE's two architecture facts and its defaults
    (HF ``OlmoeConfig``: ``norm_topk_prob``, ``router_aux_loss_coef``)."""

    qk_norm: bool = True
    normalize_top_k: bool = False
    num_experts: int = 64
    top_k: int = 8
    router_aux_loss_coef: float = 0.01
    tie_word_embeddings: bool = False


OLMOE_CONFIGS: Dict[str, OlmoeConfig] = {
    # allenai/OLMoE-1B-7B-0125-Instruct config.json values (head_dim =
    # hidden / heads; intermediate_size is one expert's width).
    # capacity_factor = E / k: the training dispatch then drops nothing
    # (Mixtral's preset follows the same rule) — required for ep > 1
    "olmoe-1b-7b": OlmoeConfig(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=4096, rope_theta=10000.0, capacity_factor=8.0,
    ),
    # E > k > 1 and E >= 8: T tokens sit below, at and above T * k = E
    # (T < 4, = 4, > 4); the no-drop dispatch is all-experts at each
    "tiny-olmoe": OlmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
        max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32,
        remat="none", num_experts=8, top_k=2,
    ),
}


@dataclasses.dataclass(frozen=True)
class OlmoeForCausalLM(MixtralForCausalLM):
    """OLMoE = the Mixtral MoE causal LM running under an OlmoeConfig (the
    block differences are all config-driven)."""

    config: OlmoeConfig


# HF ``OlmoeSparseMoeBlock`` is ``mlp`` with ``gate`` (the router) and
# ``experts.<e>.{gate,up,down}_proj``; attention adds ``q_norm``/``k_norm``
OLMOE_HF_NAMES = ("mlp", "gate_proj", "up_proj", "down_proj")


def params_from_hf_olmoe(state_dict: Dict[str, Any], config: OlmoeConfig) -> Params:
    """Convert an HF ``OlmoeForCausalLM`` ``state_dict`` to the stacked pytree."""
    return params_from_hf_mixtral(state_dict, config, OLMOE_HF_NAMES)


def params_to_hf_olmoe(params: Params, config: OlmoeConfig) -> Dict[str, Any]:
    """Inverse of :func:`params_from_hf_olmoe`."""
    return params_to_hf_mixtral(params, config, OLMOE_HF_NAMES)
