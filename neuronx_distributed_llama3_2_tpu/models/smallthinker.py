"""SmallThinker model family (``PowerInfer/SmallThinker-21BA3B-Instruct``,
``model_name: smallthinker_21b_instruct``), TPU-native: window and full
attention layers in one stack like :mod:`.laguna`, whose machinery this file
reads (one stack of weights a layer kind, :func:`..laguna.layer_runs` /
:func:`..laguna.scan_run`, :func:`..laguna.visible`,
:func:`..laguna.masked_attention`, :class:`..laguna.LagunaAttention`) — and three facts
of its own, each read from the *model* config:

- **The router runs before attention.** A layer's experts are chosen from its
  RMS-normed *input*, and the routes are applied to the post-attention state:
  ``MoE.route(h)`` ahead of the attention block, ``MoE(h2, routes=...)`` after
  it. Every other family here routes the tensor it dispatches.
- **ReLU-gated experts** (sparse ReGLU): ``down(relu(gate·x) * up·x)``,
  ``MoEConfig.activation = "relu"``; softmax over all experts, the ``top_k``
  largest renormalised; no shared expert, no dense layer.
- **Position by the published list** ``rope_layout``: a layer with 1 rotates q
  and k (the whole head, rotate-half, ``rope_theta``, no scaling), a layer
  with 0 carries **no position at all**. ``sliding_window_layout`` says which
  layers see only the last ``sliding_window`` keys, the token itself among
  them (``i - window < j <= i``). As published the two lists are equal — the
  window layers rotate, the full layers do not — and the layers of a kind are
  one stack of weights run by one body, so the lists have to agree a kind.

One query-head count over the kv heads (28 over 4 as published: 7 a kv head),
no output gate, no bias, no QK-norm.

The training-side model (:class:`SmallThinkerForCausalLM`) makes the weights
and runs every layer at full length with the window as a mask; the paged
serving engine runs :class:`..inference.model.SmallThinkerDecode`, whose window
layers keep a ring of rows a lane.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_llama3_2_tpu.models.laguna import (
    FULL,
    WINDOW,
    LagunaAttention,
    LagunaForCausalLM,
    params_from_hf_laguna,
    params_to_hf_laguna,
)
from neuronx_distributed_llama3_2_tpu.models.llama import make_norm, precompute_rope
from neuronx_distributed_llama3_2_tpu.models.mixtral import MixtralConfig
from neuronx_distributed_llama3_2_tpu.moe.loss import load_balancing_loss
from neuronx_distributed_llama3_2_tpu.moe.model import MoE, MoEConfig

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(MixtralConfig):
    """The published keys of ``smallthinker`` on top of the shared Llama/MoE
    fields: ``intermediate_size`` is the experts' width
    (``moe_ffn_hidden_size``), ``num_experts`` / ``top_k`` the primary experts
    and how many a token takes."""

    # one entry a layer, as published: 1 = window / rotary, 0 = full / none
    sliding_window_layout: Tuple[int, ...] = ()
    rope_layout: Tuple[int, ...] = ()
    sliding_window: int = 4096
    num_experts: int = 64
    top_k: int = 6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        lists = (self.sliding_window_layout, self.rope_layout)
        if {len(l) for l in lists} != {self.num_layers}:
            raise ValueError(
                f"sliding_window_layout and rope_layout need num_layers = "
                f"{self.num_layers} entries each, got {[len(l) for l in lists]}"
            )
        if not set(self.sliding_window_layout) | set(self.rope_layout) <= {0, 1}:
            raise ValueError("sliding_window_layout and rope_layout hold 0s and 1s")
        for kind in (FULL, WINDOW):
            if len({r for r, k in zip(self.rope_layout, self.kinds) if k == kind}) > 1:
                raise ValueError(
                    f"the {kind} layers' weights are one stack run by one body: "
                    f"rope_layout must say the same for each of them"
                )
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be positive")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``full`` / ``window``, a layer."""
        return tuple(WINDOW if w else FULL for w in self.sliding_window_layout)

    @property
    def mlp_layer_types(self) -> Tuple[str, ...]:
        """Every layer's feed-forward is the expert block (what
        :func:`..laguna.layer_runs` reads)."""
        return ("sparse",) * self.num_layers

    def layers_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    def rotates(self, kind: str) -> bool:
        """Whether the layers of ``kind`` rotate q and k."""
        return any(r for r, k in zip(self.rope_layout, self.kinds) if k == kind)

    def moe_config(self) -> MoEConfig:
        return dataclasses.replace(super().moe_config(), activation="relu")


def _published_layout(num_layers: int) -> Tuple[int, ...]:
    """21BA3B's two lists at ``num_layers``: 0, then 1 x 3, repeated."""
    return tuple(int(i % 4 != 0) for i in range(num_layers))


SMALLTHINKER_CONFIGS: Dict[str, SmallThinkerConfig] = {
    # PowerInfer/SmallThinker-21BA3B-Instruct config.json values
    "smallthinker-21b-a3b": SmallThinkerConfig(
        vocab_size=151936, hidden_size=2560, intermediate_size=768,
        num_layers=52, num_heads=28, num_kv_heads=4, head_dim=128,
        max_seq_len=16384, rope_theta=1.5e6, sliding_window=4096,
        sliding_window_layout=_published_layout(52), rope_layout=_published_layout(52),
    ),
    # five layers f, w, w, w, f at 6 query heads over 2 kv heads (an odd 3 a
    # kv head, as the published 7), a window of 8 (a rehearsal's chunk + prompt
    # wrap the ring), 8 experts top-3
    "tiny-smallthinker": SmallThinkerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_layers=5, num_heads=6, num_kv_heads=2, head_dim=16, max_seq_len=128,
        rope_theta=100.0, sliding_window=8, num_experts=8, top_k=3,
        sliding_window_layout=_published_layout(5), rope_layout=_published_layout(5),
        dtype=jnp.float32, remat="none",
    ),
}


@dataclasses.dataclass(frozen=True)
class SmallThinkerAttention(LagunaAttention):
    """The attention block of one kind: :class:`..llama.LlamaAttention`'s
    fused q/k/v and ``o`` projections, q and k rotated where the kind's
    ``rope_layout`` says so (``sin`` None: no position), no output gate.
    Scopes as :class:`..laguna.LagunaAttention`'s but ``attn/out_gate``."""

    @property
    def heads(self) -> int:
        return self.config.num_heads

    def init(self, key: jax.Array) -> Params:
        return self._llama().init(key)

    def specs(self) -> Params:
        return self._llama().specs()

    def output(self, params: Params, h: jax.Array, att: jax.Array) -> jax.Array:
        b, t = att.shape[:2]
        with jax.named_scope("o_proj"):
            return self._llama()._o()(params["o"], att.reshape(b, t, -1))


@dataclasses.dataclass(frozen=True)
class SmallThinkerDecoderLayer:
    """Pre-norm block: route from the normed input, attention of ``kind``,
    then the experts of the post-attention state under those routes."""

    config: SmallThinkerConfig
    kind: str = FULL

    def _attn(self) -> SmallThinkerAttention:
        return SmallThinkerAttention(self.config, self.kind)

    def _moe(self) -> MoE:
        return MoE(self.config.moe_config())

    def init(self, key: jax.Array) -> Params:
        ka, km = jax.random.split(key)
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.init(key), "attn": self._attn().init(ka),
            "mlp_norm": norm.init(key), "moe": self._moe().init(km),
        }

    def specs(self) -> Params:
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.specs(), "attn": self._attn().specs(),
            "mlp_norm": norm.specs(), "moe": self._moe().specs(),
        }

    def __call__(self, params, x, sin, cos, positions):
        norm = make_norm(self.config)
        moe = self._moe()
        h = norm(params["attn_norm"], x)
        routes = moe.route(params["moe"], h)
        x = x + self._attn()(params["attn"], h, sin, cos, positions)
        y, router_logits, idx = moe(params["moe"], norm(params["mlp_norm"], x), routes=routes)
        return x + y, load_balancing_loss(router_logits, idx, self.config.num_experts)


@dataclasses.dataclass(frozen=True)
class SmallThinkerForCausalLM(LagunaForCausalLM):
    """:class:`..laguna.LagunaForCausalLM`'s protocol, stacks and layer order
    with this family's layer and tables: ``params["full_layers"]``,
    ``["window_layers"]``."""

    config: SmallThinkerConfig

    def _layer(self, kind: str, sparse: bool = True) -> SmallThinkerDecoderLayer:
        return SmallThinkerDecoderLayer(self.config, kind)

    def _ropes(self, s: int) -> Dict[str, Tuple[Optional[jax.Array], Optional[jax.Array]]]:
        """A (sin, cos) pair a kind, (s, head_dim) fp32 — (None, None) where
        the kind carries no position."""
        c = self.config
        table = precompute_rope(c.head_dim, s, c.rope_theta)
        return {kind: table if c.rotates(kind) else (None, None) for kind in (FULL, WINDOW)}


# HF names: the catalog publishes the configuration and no tensor names; the
# map is Laguna's (the Qwen-MoE lineage's: ``mlp.gate`` the router,
# ``mlp.experts.N``), which writes and reads an output gate and a shared expert
# only where a layer has them — this family's layers have neither.
params_to_hf_smallthinker = params_to_hf_laguna
params_from_hf_smallthinker = params_from_hf_laguna
