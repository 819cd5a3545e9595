"""Mixtral-style MoE causal LM.

TPU-native counterpart of the reference's MoE pretraining model
(``examples/training/mixtral/modeling_mixtral_moe_nxd.py``, 889 LoC, which
wires ``MoE(RouterTopK, ExpertMLPs)`` into HF Mixtral) and the Mixtral
inference model (``examples/inference/mixtral/neuron_modeling_mixtral.py``).
Reuses the Llama attention/norm blocks (Mixtral's attention IS Llama GQA
attention) and swaps the dense MLP for the :class:`..moe.MoE` block; the
per-layer router logits feed the Switch load-balancing loss
(``modules/moe/loss_function.py:5``) accumulated across the scanned layers.

Implements the same model protocol as :class:`.llama.LlamaForCausalLM`
(init/specs/__call__/loss/loss_from_hidden), so the trainer and checkpoint
layers work unchanged. Both pipeline executors support MoE
(:class:`..pipeline.PipelinedCausalLM`): the GPipe stage scan carries a
router-aux stream alongside the hidden state (validity-masked over
fill/drain rotations), and the 1F1B manual-VJP executor feeds the aux term
in as a constant cotangent on each stage's aux output.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    LlamaForCausalLM,
    _remat_policy,
    make_norm,
    precompute_rope,
)
from neuronx_distributed_llama3_2_tpu.moe.loss import load_balancing_loss
from neuronx_distributed_llama3_2_tpu.moe.model import MoE, MoEConfig
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import BATCH_AXES, constrain
from neuronx_distributed_llama3_2_tpu.parallel.state import TP_AXIS

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    """LlamaConfig + MoE knobs (HF MixtralConfig fields)."""

    num_experts: int = 8
    top_k: int = 2
    capacity_factor: Optional[float] = None
    routing: str = "topk"
    normalize_top_k: bool = True
    router_aux_loss_coef: float = 0.02

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_experts=self.num_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            routing=self.routing,
            normalize_top_k=self.normalize_top_k,
            dtype=self.dtype,
        )


MIXTRAL_CONFIGS: Dict[str, MixtralConfig] = {
    # HF mistralai/Mixtral-8x7B config.json values; capacity_factor sized for
    # no dropping at balance (E/k = 4) with headroom — required for ep > 1
    "mixtral-8x7b": MixtralConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=32768, rope_theta=1e6, tie_word_embeddings=False,
        num_experts=8, top_k=2, capacity_factor=4.0,
    ),
    "tiny-moe": MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=8, num_kv_heads=4, head_dim=8,
        max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32,
        remat="none", num_experts=4, top_k=2,
    ),
}


@dataclasses.dataclass(frozen=True)
class MixtralDecoderLayer:
    config: MixtralConfig

    def _norm(self):
        return make_norm(self.config)

    def _moe(self) -> MoE:
        return MoE(self.config.moe_config())

    def init(self, key: jax.Array) -> Params:
        ka, km = jax.random.split(key)
        return {
            "attn_norm": self._norm().init(key),
            "attn": LlamaAttention(self.config).init(ka),
            "mlp_norm": self._norm().init(key),
            "moe": self._moe().init(km),
        }

    def specs(self) -> Params:
        return {
            "attn_norm": self._norm().specs(),
            "attn": LlamaAttention(self.config).specs(),
            "mlp_norm": self._norm().specs(),
            "moe": self._moe().specs(),
        }

    def __call__(self, params, x, sin, cos, positions):
        """Returns (x, aux_loss) — aux is this layer's load-balancing loss."""
        c = self.config
        h = self._norm()(params["attn_norm"], x)
        x = x + LlamaAttention(c)(params["attn"], h, sin, cos, positions)
        h = self._norm()(params["mlp_norm"], x)
        y, router_logits, idx = self._moe()(params["moe"], h)
        aux = load_balancing_loss(router_logits, idx, c.num_experts)
        return x + y, aux


@dataclasses.dataclass(frozen=True)
class MixtralForCausalLM:
    """Same protocol as LlamaForCausalLM; ``loss`` adds
    ``router_aux_loss_coef · mean(per-layer aux)``."""

    config: MixtralConfig
    # shardlint SL002 — see models/llama.py LlamaAttention
    __layout_deps__ = ("sequence_parallel_enabled",)

    def _llama(self) -> LlamaForCausalLM:
        # reuse embed/lm-head/final-norm/logits/loss-tail machinery
        return LlamaForCausalLM(self.config)

    def _layer(self) -> MixtralDecoderLayer:
        return MixtralDecoderLayer(self.config)

    # protocol delegators (checkpoint converters and facades call these on
    # any causal-LM model)
    def _embed(self):
        return self._llama()._embed()

    def _norm(self):
        return self._llama()._norm()

    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        return self._llama()._logits(params, hidden)

    def _rope(self, s: int):
        return self._llama()._rope(s)

    def _zigzag_enter(self, x, positions):
        # cp zigzag layout (kernels/ring_attention.py): shared machinery,
        # needed here because the pipeline executor calls it on any model
        return self._llama()._zigzag_enter(x, positions)

    _zigzag_exit = staticmethod(LlamaForCausalLM._zigzag_exit)

    def init(self, key: jax.Array) -> Params:
        c = self.config
        ke, kl, kh = jax.random.split(key, 3)
        layer_keys = jax.random.split(kl, c.num_layers)
        layers = jax.vmap(self._layer().init)(layer_keys)
        params = {
            "embed": self._llama()._embed().init(ke),
            "layers": layers,
            "final_norm": self._llama()._norm().init(kh),
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = self._llama()._lm_head().init(kh)
        return params

    def specs(self) -> Params:
        c = self.config
        layer_specs = jax.tree.map(
            lambda s: P(None, *s), self._layer().specs(),
            is_leaf=lambda s: isinstance(s, P),
        )
        specs = {
            "embed": self._llama()._embed().specs(),
            "layers": layer_specs,
            "final_norm": self._llama()._norm().specs(),
        }
        if not c.tie_word_embeddings:
            specs["lm_head"] = self._llama()._lm_head().specs()
        return specs

    def _backbone(
        self, params: Params, input_ids: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Embed + MoE decoder stack + final norm.
        Returns (hidden (B,S,H), mean aux loss)."""
        c = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        sin, cos = self._rope(s)
        x = self._llama()._embed()(params["embed"], input_ids)
        x, positions, zz_inv = self._zigzag_enter(x, positions)
        if parallel_state.sequence_parallel_enabled():
            x = constrain(x, P(BATCH_AXES, TP_AXIS, None))

        layer = self._layer()

        def body(x, layer_params):
            y, aux = layer(layer_params, x, sin, cos, positions)
            return y, aux

        policy = _remat_policy(c.remat)
        if policy is not None:
            body = jax.checkpoint(body, policy=policy)
        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import (
            cp_layout_from_inv,
        )

        with cp_layout_from_inv(zz_inv):
            if c.scan_layers:
                x, aux = lax.scan(body, x, params["layers"])
                aux = jnp.mean(aux)
            else:
                auxes = []
                for i in range(c.num_layers):
                    x, a = body(
                        x, jax.tree.map(lambda p: p[i], params["layers"])
                    )
                    auxes.append(a)
                aux = jnp.mean(jnp.stack(auxes))
        x = self._llama()._norm()(params["final_norm"], x)
        x = self._zigzag_exit(x, zz_inv)
        if parallel_state.sequence_parallel_enabled():
            x = constrain(x, P(BATCH_AXES, None, None))
        return x, aux

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        hidden, _ = self._backbone(params, input_ids)
        return self._llama()._logits(params, hidden)

    def loss_from_hidden(self, params, hidden, labels):
        return self._llama().loss_from_hidden(params, hidden, labels)

    def loss(
        self, params: Params, input_ids: jax.Array, labels: jax.Array
    ) -> jax.Array:
        hidden, aux = self._backbone(params, input_ids)
        ce = self._llama().loss_from_hidden(params, hidden, labels)
        return ce + self.config.router_aux_loss_coef * aux


# HF module names of a Mixtral-shaped sparse block: (the MoE module, and
# inside ``experts.<e>.``: gate, up and down projections). OLMoE's differ
# (models/olmoe.py); the tensors and their layouts do not.
MIXTRAL_HF_NAMES = ("block_sparse_moe", "w1", "w3", "w2")


def params_from_hf_mixtral(
    state_dict: Dict[str, Any], config: MixtralConfig,
    hf_names: Tuple[str, str, str, str] = MIXTRAL_HF_NAMES,
) -> Params:
    """Convert an HF Mixtral ``state_dict`` to the stacked pytree.

    HF ``MixtralSparseMoeBlock``: per-expert w1 (gate, (I,H)), w3 (up, (I,H)),
    w2 (down, (H,I)); router ``gate.weight`` (E,H). Attention maps exactly as
    Llama (same GQA block); with ``config.qk_norm`` the block's
    ``q_norm``/``k_norm`` weights come along."""
    import numpy as np

    def t(name):
        w = state_dict[name]
        if hasattr(w, "detach"):
            w = w.detach().cpu().numpy()
        return np.asarray(w, dtype=np.float32)

    c = config
    L, E = c.num_layers, c.num_experts
    moe_name, gate_name, up_name, down_name = hf_names

    def stack(fmt, transform=lambda w: w.T, dtype=None):
        return jnp.asarray(
            np.stack([transform(t(fmt.format(i))) for i in range(L)]),
            dtype or c.dtype,
        )

    def scale(fmt):
        return {"scale": stack(fmt, transform=lambda w: w, dtype=jnp.float32)}

    gate_ups, downs, routers = [], [], []
    for i in range(L):
        moe = f"model.layers.{i}.{moe_name}"
        routers.append(t(f"{moe}.gate.weight").T)  # (H, E)
        gate = np.stack([t(f"{moe}.experts.{e}.{gate_name}.weight").T for e in range(E)])
        up = np.stack([t(f"{moe}.experts.{e}.{up_name}.weight").T for e in range(E)])
        gate_ups.append(np.stack([gate, up], axis=2))  # (E, H, 2, I)
        downs.append(
            np.stack([t(f"{moe}.experts.{e}.{down_name}.weight").T for e in range(E)])
        )  # (E, I, H)

    attn = {
        "qkv": {
            "q_kernel": stack("model.layers.{}.self_attn.q_proj.weight"),
            "k_kernel": stack("model.layers.{}.self_attn.k_proj.weight"),
            "v_kernel": stack("model.layers.{}.self_attn.v_proj.weight"),
        },
        "o": {"kernel": stack("model.layers.{}.self_attn.o_proj.weight")},
    }
    if c.qk_norm:
        attn["q_norm"] = scale("model.layers.{}.self_attn.q_norm.weight")
        attn["k_norm"] = scale("model.layers.{}.self_attn.k_norm.weight")
    params: Params = {
        "embed": {
            "embedding": jnp.asarray(t("model.embed_tokens.weight"), c.dtype)
        },
        "layers": {
            "attn_norm": scale("model.layers.{}.input_layernorm.weight"),
            "attn": attn,
            "mlp_norm": scale("model.layers.{}.post_attention_layernorm.weight"),
            "moe": {
                "router": {
                    "kernel": jnp.asarray(np.stack(routers), jnp.float32)
                },
                "experts": {
                    "gate_up": jnp.asarray(np.stack(gate_ups), c.dtype),
                    "down": jnp.asarray(np.stack(downs), c.dtype),
                },
            },
        },
        "final_norm": {
            "scale": jnp.asarray(t("model.norm.weight"), jnp.float32)
        },
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = {
            "kernel": jnp.asarray(t("lm_head.weight").T, c.dtype)
        }
    return params


def params_to_hf_mixtral(
    params: Params, config: MixtralConfig,
    hf_names: Tuple[str, str, str, str] = MIXTRAL_HF_NAMES,
) -> Dict[str, Any]:
    """Inverse of :func:`params_from_hf_mixtral`: stacked pytree → HF Mixtral
    ``state_dict`` (numpy fp32, torch (out, in) Linear layout). The
    native→HF direction of the reference's family-generic converter
    (scripts/checkpoint_converter.py:685)."""
    import numpy as np

    c = config
    L, E = c.num_layers, c.num_experts
    moe_name, gate_name, up_name, down_name = hf_names

    def np32(x):
        return np.asarray(x, dtype=np.float32)

    lyr = params["layers"]
    sd: Dict[str, Any] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
    }
    attn_norm = np32(lyr["attn_norm"]["scale"])
    mlp_norm = np32(lyr["mlp_norm"]["scale"])
    q_k = np32(lyr["attn"]["qkv"]["q_kernel"])
    k_k = np32(lyr["attn"]["qkv"]["k_kernel"])
    v_k = np32(lyr["attn"]["qkv"]["v_kernel"])
    o_k = np32(lyr["attn"]["o"]["kernel"])
    router = np32(lyr["moe"]["router"]["kernel"])      # (L, H, E)
    gate_up = np32(lyr["moe"]["experts"]["gate_up"])   # (L, E, H, 2, I)
    down = np32(lyr["moe"]["experts"]["down"])         # (L, E, I, H)
    for i in range(L):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = attn_norm[i]
        sd[p + "post_attention_layernorm.weight"] = mlp_norm[i]
        sd[p + "self_attn.q_proj.weight"] = q_k[i].T
        sd[p + "self_attn.k_proj.weight"] = k_k[i].T
        sd[p + "self_attn.v_proj.weight"] = v_k[i].T
        sd[p + "self_attn.o_proj.weight"] = o_k[i].T
        if c.qk_norm:
            sd[p + "self_attn.q_norm.weight"] = np32(lyr["attn"]["q_norm"]["scale"][i])
            sd[p + "self_attn.k_norm.weight"] = np32(lyr["attn"]["k_norm"]["scale"][i])
        moe = p + moe_name + "."
        sd[moe + "gate.weight"] = router[i].T
        for e in range(E):
            sd[moe + f"experts.{e}.{gate_name}.weight"] = gate_up[i, e, :, 0, :].T
            sd[moe + f"experts.{e}.{up_name}.weight"] = gate_up[i, e, :, 1, :].T
            sd[moe + f"experts.{e}.{down_name}.weight"] = down[i, e].T
    if not c.tie_word_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]["kernel"]).T
    return sd
