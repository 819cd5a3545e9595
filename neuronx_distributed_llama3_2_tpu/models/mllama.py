"""Llama-3.2 Vision (Mllama): multimodal model family.

TPU-native implementation of the 11B-Vision architecture named by
BASELINE.json ("Llama-3.2 11B-Vision multimodal"). The reference repo ships
no vision modeling code — its conv TP layers (``parallel_layers/layers.py``
:1033/:1134) exist *for* this model family; we build the whole family:

- **Vision encoder**: tiled ViT — channel-parallel patch conv, gated
  aspect-ratio/tile/position embeddings, pre/post layernorm, N local +
  M tanh-gated global transformer layers, intermediate-feature collection.
- **Text decoder**: Llama self-attention layers (reused from
  :mod:`.llama`) interleaved with tanh-gated cross-attention layers
  (q/k-normed GQA attending over projected vision tokens).
- **MllamaForConditionalGeneration**: vision encoder → multimodal
  projector → text decoder with cross-attention masking.

Semantics match HF ``transformers`` Mllama (modeling_mllama.py) — gating
formulas (``(1-tanh(g))·pos + tanh(g)·tile`` :146-163, ``π/4``-init encoder
gates :293-313, zero-init cross-attn gates :673-724), the 8-multiple patch
padding (:1070-1076), intermediate states collected *after* each local layer
(:353-361), and the cross-attention full-text-row mask (:48-73) — verified
by logits-parity tests against the HF implementation
(tests/test_mllama.py).

TP mapping: vision attention/MLP shard like text attention/MLP
(Column→Row); the patch conv is an OutputChannelParallelConv2d
(parallel/conv.py) with gathered output; embeddings/gates replicate.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.lora import model as lora_model
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaConfig,
    LlamaDecoderLayer,
    RMSNorm,
    precompute_rope,
)
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    BATCH_AXES,
    constrain,
)
from neuronx_distributed_llama3_2_tpu.parallel.state import TP_AXIS
from neuronx_distributed_llama3_2_tpu.parallel.conv import (
    OutputChannelParallelConv2d,
)
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
    default_kernel_init,
)
from neuronx_distributed_llama3_2_tpu.parallel.loss import (
    fused_linear_cross_entropy,
)

Params = Dict[str, Any]

# a numpy scalar: a jax one would start the backend when the module is imported
NEG = np.float32(-1e30)


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MllamaVisionConfig:
    """HF MllamaVisionConfig counterpart (configuration_mllama.py)."""

    hidden_size: int = 1280
    intermediate_size: int = 5120
    num_hidden_layers: int = 32
    num_global_layers: int = 8
    attention_heads: int = 16
    image_size: int = 448
    patch_size: int = 14
    num_channels: int = 3
    max_num_tiles: int = 4
    max_aspect_ratio_id: int = 8
    intermediate_layers_indices: Tuple[int, ...] = (3, 7, 15, 23, 30)
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    # activation checkpointing over the 40 vision layers. The tower runs a
    # plain layer loop (heterogeneous gated/ungated blocks), and its
    # (BM, heads, 4128, 4128) attention activations dominate 11B training
    # memory without remat — docs/mllama_memory_plan.md quantifies.
    remat: str = "none"

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2 + 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.attention_heads

    @property
    def output_dim(self) -> int:
        # final hidden + one slice per collected intermediate layer
        return self.hidden_size * (1 + len(self.intermediate_layers_indices))


@dataclasses.dataclass(frozen=True)
class MllamaTextConfig:
    """HF MllamaTextConfig counterpart: a Llama decoder plus gated
    cross-attention layers at ``cross_attention_layers`` indices."""

    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    cross_attention_layers: Tuple[int, ...] = (3, 8, 13, 18, 23, 28, 33, 38)
    rope_theta: float = 500000.0
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.float32
    # activation checkpointing over decoder layers ("none"/"full"/
    # "selective" — the LlamaConfig policies): required for 11B training
    # memory (docs/mllama_memory_plan.md); default off to keep small-model
    # inference/parity paths recompute-free
    remat: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def self_attn_layer_config(self) -> LlamaConfig:
        """LlamaConfig for the (reused) self-attention decoder layers."""
        return LlamaConfig(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=1,
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            rms_norm_eps=self.rms_norm_eps,
            max_seq_len=self.max_seq_len,
            dtype=self.dtype,
            remat="none",
            tie_word_embeddings=False,
        )


@dataclasses.dataclass(frozen=True)
class MllamaConfig:
    vision: MllamaVisionConfig = MllamaVisionConfig()
    text: MllamaTextConfig = MllamaTextConfig()


MLLAMA_CONFIGS: Dict[str, MllamaConfig] = {
    # HF meta-llama/Llama-3.2-11B-Vision config.json: the dataclass defaults
    # above ARE the 11B values; the text tower adds the llama3 rope scaling
    # (factor 8, low 1, high 4, original 8192) and bf16 compute
    "llama3.2-11b-vision": MllamaConfig(
        vision=dataclasses.replace(MllamaVisionConfig(), dtype=jnp.bfloat16),
        text=dataclasses.replace(
            MllamaTextConfig(),
            rope_scaling=(8.0, 1.0, 4.0, 8192),
            max_seq_len=131072,
            dtype=jnp.bfloat16,
        ),
    ),
    "tiny-mllama": MllamaConfig(
        vision=MllamaVisionConfig(
            hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_global_layers=1, attention_heads=2, image_size=28,
            patch_size=14, max_num_tiles=2, max_aspect_ratio_id=3,
            intermediate_layers_indices=(0, 1),
        ),
        text=MllamaTextConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_heads=4, num_kv_heads=2,
            cross_attention_layers=(1,), rope_theta=10000.0, max_seq_len=64,
        ),
    ),
}


# ---------------------------------------------------------------------------
# small building blocks
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerNorm:
    """Standard layernorm with bias (the vision tower is pre/post-LN ViT;
    the text side keeps RMSNorm)."""

    dim: int
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def init(self, key) -> Params:
        return {
            "scale": jnp.ones((self.dim,), jnp.float32),
            "bias": jnp.zeros((self.dim,), jnp.float32),
        }

    def specs(self) -> Params:
        return {"scale": P(None), "bias": P(None)}

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        h = x.astype(jnp.float32)
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + self.eps)
        return (h * params["scale"] + params["bias"]).astype(self.dtype)


def _mha(q, k, v, bias, num_heads, head_dim):
    """Dense multi-head attention with an additive bias mask (the vision
    tower's sequences are ~1K tokens per tile-set; dense is the right call
    on the MXU). q/k/v (B, S, H_flat)."""
    b, sq, _ = q.shape
    skv = k.shape[1]
    q = q.reshape(b, sq, num_heads, head_dim)
    k = k.reshape(b, skv, num_heads, head_dim)
    v = v.reshape(b, skv, num_heads, head_dim)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32)
    scores = scores * (head_dim ** -0.5)
    if bias is not None:
        scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnqk,bknd->bqnd", probs, v)
    return out.reshape(b, sq, num_heads * head_dim)


@dataclasses.dataclass(frozen=True)
class VisionAttention:
    """MllamaVisionAttention (modeling_mllama.py:219): MHA, no bias terms,
    q/k/v Column-parallel + o Row-parallel."""

    config: MllamaVisionConfig

    def _proj(self) -> ColumnParallelLinear:
        c = self.config
        return ColumnParallelLinear(c.hidden_size, c.hidden_size, dtype=c.dtype)

    def _o(self) -> RowParallelLinear:
        c = self.config
        return RowParallelLinear(c.hidden_size, c.hidden_size, dtype=c.dtype)

    def init(self, key) -> Params:
        kq, kk, kv, ko = jax.random.split(key, 4)
        return {
            "q": self._proj().init(kq),
            "k": self._proj().init(kk),
            "v": self._proj().init(kv),
            "o": self._o().init(ko),
        }

    def specs(self) -> Params:
        return {
            "q": self._proj().specs(),
            "k": self._proj().specs(),
            "v": self._proj().specs(),
            "o": self._o().specs(),
        }

    def __call__(self, params: Params, x: jax.Array, bias) -> jax.Array:
        c = self.config
        q = self._proj()(params["q"], x)
        k = self._proj()(params["k"], x)
        v = self._proj()(params["v"], x)
        attn = _mha(q, k, v, bias, c.attention_heads, c.head_dim)
        return self._o()(params["o"], attn)


@dataclasses.dataclass(frozen=True)
class VisionMLP:
    """CLIP-style MLP: fc1/gelu/fc2, with biases (modeling_mllama.py:164)."""

    config: MllamaVisionConfig

    def _fc1(self) -> ColumnParallelLinear:
        c = self.config
        return ColumnParallelLinear(
            c.hidden_size, c.intermediate_size, use_bias=True, dtype=c.dtype
        )

    def _fc2(self) -> RowParallelLinear:
        c = self.config
        return RowParallelLinear(
            c.intermediate_size, c.hidden_size, use_bias=True, dtype=c.dtype
        )

    def init(self, key) -> Params:
        k1, k2 = jax.random.split(key)
        return {"fc1": self._fc1().init(k1), "fc2": self._fc2().init(k2)}

    def specs(self) -> Params:
        return {"fc1": self._fc1().specs(), "fc2": self._fc2().specs()}

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        h = self._fc1()(params["fc1"], x)
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=False).astype(x.dtype)
        return self._fc2()(params["fc2"], h)


def _stack_trees(trees):
    """Per-layer param dicts → stacked (L, ...) leaves (scan layout)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def text_group_pattern(t: "MllamaTextConfig"):
    """(G, k, xpos) when the cross-attention layers form the regular
    pattern ``xpos + g*k`` (true of every HF Mllama config: 11B has
    stride-5 groups at offset 3). None for irregular configs, which fall
    back to the per-layer list layout + Python loop."""
    xl = tuple(t.cross_attention_layers)
    G = len(xl)
    if G == 0 or t.num_hidden_layers % G:
        return None
    k = t.num_hidden_layers // G
    # k == 1 means EVERY layer is cross-attention: a group would hold zero
    # plain layers (empty stack) — use the list layout instead
    if k < 2:
        return None
    xpos = xl[0]
    if xpos >= k or xl != tuple(xpos + g * k for g in range(G)):
        return None
    return G, k, xpos


# the grouped text stack lifts the plain layers' 2-D kernels to
# (G, k-1, in, out); declare which kernel names those are so the LoRA
# split can tell them from single-stack fused (L, in, t, out) kernels —
# the registry keeps this naming next to the code that packs the stack
# (_pack_text_layers below) instead of an allowlist in lora/model.py
lora_model.register_grouped_stack(
    "layers/plain/", (r"q_kernel$", r"k_kernel$", r"v_kernel$", r"/kernel$")
)


def _pack_text_layers(layer_list, pattern):
    """Per-layer trees → grouped scan layout {"plain": (G, k-1, ...),
    "xattn": (G, ...)} following ``text_group_pattern``."""
    G, k, xpos = pattern
    plains, xatts = [], []
    for g in range(G):
        grp = layer_list[g * k:(g + 1) * k]
        xatts.append(grp[xpos])
        plains.append(_stack_trees([grp[j] for j in range(k) if j != xpos]))
    return {"plain": _stack_trees(plains), "xattn": _stack_trees(xatts)}


def text_layer_slice(layers, i: int, pattern):
    """(per-layer tree, is_cross) for absolute layer ``i`` of the grouped
    layout — the accessor the decode path uses (static python index)."""
    G, k, xpos = pattern
    g, j = divmod(i, k)
    if j == xpos:
        return jax.tree.map(lambda x: x[g], layers["xattn"]), True
    p = j if j < xpos else j - 1
    return jax.tree.map(lambda x: x[g, p], layers["plain"]), False


@dataclasses.dataclass(frozen=True)
class VisionEncoderLayer:
    """Pre-LN ViT block; global layers tanh-gate both residual branches
    (gates init pi/4, modeling_mllama.py:274-313)."""

    config: MllamaVisionConfig
    is_gated: bool = False

    def _ln(self) -> LayerNorm:
        c = self.config
        return LayerNorm(c.hidden_size, c.norm_eps, c.dtype)

    def init(self, key) -> Params:
        ka, km = jax.random.split(key)
        p = {
            "input_layernorm": self._ln().init(key),
            "self_attn": VisionAttention(self.config).init(ka),
            "post_attention_layernorm": self._ln().init(key),
            "mlp": VisionMLP(self.config).init(km),
        }
        if self.is_gated:
            p["gate_attn"] = jnp.full((1,), math.pi / 4, jnp.float32)
            p["gate_ffn"] = jnp.full((1,), math.pi / 4, jnp.float32)
        return p

    def specs(self) -> Params:
        s = {
            "input_layernorm": self._ln().specs(),
            "self_attn": VisionAttention(self.config).specs(),
            "post_attention_layernorm": self._ln().specs(),
            "mlp": VisionMLP(self.config).specs(),
        }
        if self.is_gated:
            s["gate_attn"] = P(None)
            s["gate_ffn"] = P(None)
        return s

    def __call__(self, params: Params, x: jax.Array, bias) -> jax.Array:
        h = VisionAttention(self.config)(
            params["self_attn"], self._ln()(params["input_layernorm"], x), bias
        )
        if self.is_gated:
            h = jnp.tanh(params["gate_attn"]) * h
        x = x + h
        h = VisionMLP(self.config)(
            params["mlp"], self._ln()(params["post_attention_layernorm"], x)
        )
        if self.is_gated:
            h = jnp.tanh(params["gate_ffn"]) * h
        return x + h


# ---------------------------------------------------------------------------
# vision model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MllamaVisionModel:
    """Tiled ViT encoder (modeling_mllama.py:943): returns
    (B, num_media, tiles, patches+1, output_dim) features — final hidden
    concatenated with the configured intermediate layer states."""

    config: MllamaVisionConfig

    def _patch_conv(self) -> OutputChannelParallelConv2d:
        c = self.config
        return OutputChannelParallelConv2d(
            c.num_channels, c.hidden_size, kernel_size=c.patch_size,
            stride=c.patch_size, use_bias=False, gather_output=True,
            dtype=c.dtype,
        )

    def init(self, key) -> Params:
        c = self.config
        keys = jax.random.split(key, 8 + c.num_hidden_layers + c.num_global_layers)
        scale = c.hidden_size ** -0.5
        p: Params = {
            "patch_embedding": self._patch_conv().init(keys[0]),
            "class_embedding": scale
            * jax.random.normal(keys[1], (c.hidden_size,), jnp.float32),
            "gated_positional_embedding": {
                "embedding": scale
                * jax.random.normal(
                    keys[2], (c.num_patches, c.hidden_size), jnp.float32
                ),
                "tile_embedding": default_kernel_init(
                    keys[3],
                    (
                        c.max_aspect_ratio_id + 1,
                        c.max_num_tiles * c.num_patches * c.hidden_size,
                    ),
                    jnp.float32,
                ),
                "gate": jnp.zeros((1,), jnp.float32),
            },
            "pre_tile_positional_embedding": {
                "embedding": default_kernel_init(
                    keys[4],
                    (c.max_aspect_ratio_id + 1, c.max_num_tiles * c.hidden_size),
                    jnp.float32,
                ),
                "gate": jnp.zeros((1,), jnp.float32),
            },
            "post_tile_positional_embedding": {
                "embedding": default_kernel_init(
                    keys[5],
                    (c.max_aspect_ratio_id + 1, c.max_num_tiles * c.hidden_size),
                    jnp.float32,
                ),
                "gate": jnp.zeros((1,), jnp.float32),
            },
            "layernorm_pre": LayerNorm(c.hidden_size, dtype=c.dtype).init(keys[6]),
            "layernorm_post": LayerNorm(c.hidden_size, dtype=c.dtype).init(keys[7]),
            # both stacks are internally homogeneous → stacked (L, ...)
            # leaves scanned like the text stack (the Python layer loop
            # carried 0.337 GB/layer of unreusable temp under remat —
            # docs/mllama_memory_plan.md)
            "transformer": _stack_trees(
                [
                    VisionEncoderLayer(c, is_gated=False).init(keys[8 + i])
                    for i in range(c.num_hidden_layers)
                ]
            ),
            "global_transformer": _stack_trees(
                [
                    VisionEncoderLayer(c, is_gated=True).init(
                        keys[8 + c.num_hidden_layers + i]
                    )
                    for i in range(c.num_global_layers)
                ]
            ),
        }
        return p

    def specs(self) -> Params:
        c = self.config
        rep2 = {"embedding": P(None, None), "gate": P(None)}
        return {
            "patch_embedding": self._patch_conv().specs(),
            "class_embedding": P(None),
            "gated_positional_embedding": {
                "embedding": P(None, None),
                "tile_embedding": P(None, None),
                "gate": P(None),
            },
            "pre_tile_positional_embedding": dict(rep2),
            "post_tile_positional_embedding": dict(rep2),
            "layernorm_pre": LayerNorm(c.hidden_size).specs(),
            "layernorm_post": LayerNorm(c.hidden_size).specs(),
            # stacked (L, ...) leaves: replicate the stack dim, keep each
            # layer's tp sharding on the trailing dims
            "transformer": jax.tree.map(
                lambda s: P(None, *s),
                VisionEncoderLayer(c, is_gated=False).specs(),
                is_leaf=lambda s: isinstance(s, P),
            ),
            "global_transformer": jax.tree.map(
                lambda s: P(None, *s),
                VisionEncoderLayer(c, is_gated=True).specs(),
                is_leaf=lambda s: isinstance(s, P),
            ),
        }

    def _tile_embedding(self, emb_params, hidden, aspect_ratio_ids):
        """Gated per-tile embedding (modeling_mllama.py:103-124);
        hidden (BM, tiles, patches, H)."""
        c = self.config
        emb = jnp.take(emb_params["embedding"], aspect_ratio_ids, axis=0)
        emb = emb.reshape(-1, c.max_num_tiles, 1, c.hidden_size)
        return hidden + jnp.tanh(emb_params["gate"]) * emb

    def _positional_embedding(self, pe, hidden, aspect_ratio_ids):
        """(1-tanh g)·pos + tanh g·tile-pos (modeling_mllama.py:146-163)."""
        c = self.config
        g = jnp.tanh(pe["gate"])
        hidden = hidden + (1.0 - g) * pe["embedding"].reshape(
            1, 1, c.num_patches, c.hidden_size
        )
        tile = jnp.take(pe["tile_embedding"], aspect_ratio_ids, axis=0).reshape(
            -1, c.max_num_tiles, c.num_patches, c.hidden_size
        )
        return hidden + g * tile

    def __call__(
        self,
        params: Params,
        pixel_values: jax.Array,       # (B, M, T, C, H, W) torch layout
        aspect_ratio_ids: jax.Array,   # (B, M)
        aspect_ratio_mask: jax.Array,  # (B, M, T)
    ) -> jax.Array:
        c = self.config
        b, m, t, ch, hgt, wid = pixel_values.shape
        x = pixel_values.reshape(b * m * t, ch, hgt, wid)
        # NCHW → NHWC (TPU conv layout)
        x = jnp.transpose(x, (0, 2, 3, 1)).astype(c.dtype)
        patches = self._patch_conv()(params["patch_embedding"], x)
        # (N, H/p, W/p, hidden) → (N, patches, hidden), row-major like
        # torch's flatten(2) of (N, hidden, H/p, W/p)
        n_pat = patches.shape[1] * patches.shape[2]
        hidden = patches.reshape(b * m * t, n_pat, c.hidden_size)

        ar_ids = aspect_ratio_ids.reshape(b * m)
        hidden = hidden.reshape(b * m, t, n_pat, c.hidden_size)
        hidden = self._tile_embedding(
            params["pre_tile_positional_embedding"], hidden, ar_ids
        )

        # class token
        cls = jnp.broadcast_to(
            params["class_embedding"].astype(c.dtype),
            (b * m * t, 1, c.hidden_size),
        )
        hidden = hidden.reshape(b * m * t, n_pat, c.hidden_size)
        hidden = jnp.concatenate([cls, hidden], axis=1)
        n_pat += 1

        hidden = hidden.reshape(b * m, t, n_pat, c.hidden_size)
        hidden = self._positional_embedding(
            params["gated_positional_embedding"], hidden, ar_ids
        )
        hidden = LayerNorm(c.hidden_size, c.norm_eps, c.dtype)(
            params["layernorm_pre"], hidden
        )

        # pad patch dim to a multiple of 8 (modeling_mllama.py:1070-1076)
        npad = (8 - n_pat % 8) % 8
        if npad:
            hidden = jnp.pad(hidden, ((0, 0), (0, 0), (0, npad), (0, 0)))
        tlen = n_pat + npad

        # tile-validity attention bias (modeling_mllama.py:76-101): token i
        # may attend token j iff both lie in valid (unpadded) positions of
        # valid tiles
        amask = aspect_ratio_mask.reshape(b * m, t).astype(jnp.float32)
        tok_ok = jnp.repeat(amask, tlen, axis=1)  # (BM, T*tlen)
        pad_pos = jnp.arange(tlen) >= n_pat
        tok_ok = tok_ok * jnp.where(
            jnp.tile(pad_pos, (t,)), 0.0, 1.0
        )[None, :]
        inv = 1.0 - tok_ok
        bias = (inv[:, :, None] @ inv[:, None, :]) * NEG  # (BM, S, S)
        bias = bias[:, None, :, :]  # (BM, 1, S, S)

        hidden = hidden.reshape(b * m, t * tlen, c.hidden_size)

        # scanned stacked layers (like the text stack): one layer's working
        # set is reused across iterations, and per-iteration jax.checkpoint
        # bounds the backward at one layer's recompute + the (BM, S, H)
        # boundary stash per layer. The static intermediate_layers_indices
        # split the stack into K+1 statically-sliced scan SEGMENTS with the
        # hidden state collected at each boundary — carrying a (K, BM, S,
        # H) slot buffer through one scan would multiply every boundary
        # stash by (1+K). bias/sin-style loop constants ride the closure,
        # same as the text side's _scan_stage.
        from neuronx_distributed_llama3_2_tpu.models.llama import _remat_policy

        policy = _remat_policy(c.remat)

        def plain_body(h, lp):
            return VisionEncoderLayer(c, is_gated=False)(lp, h, bias), None

        def gated_body(h, lp):
            return VisionEncoderLayer(c, is_gated=True)(lp, h, bias), None

        if policy is not None:
            plain_body = jax.checkpoint(plain_body, policy=policy)
            gated_body = jax.checkpoint(gated_body, policy=policy)

        intermediates: List[jax.Array] = []
        start = 0
        for idx in tuple(sorted(c.intermediate_layers_indices)) + (
            c.num_hidden_layers - 1,
        ):
            if idx < start:
                continue  # final bound may coincide with the last index
            seg = jax.tree.map(
                lambda p: p[start:idx + 1], params["transformer"]
            )
            hidden, _ = jax.lax.scan(plain_body, hidden, seg)
            if idx in c.intermediate_layers_indices:
                intermediates.append(hidden)
            start = idx + 1

        hidden = LayerNorm(c.hidden_size, c.norm_eps, c.dtype)(
            params["layernorm_post"], hidden
        )
        hidden = hidden.reshape(b * m, t, tlen, c.hidden_size)
        hidden = self._tile_embedding(
            params["post_tile_positional_embedding"], hidden, ar_ids
        )
        hidden = hidden.reshape(b * m, t * tlen, c.hidden_size)
        hidden, _ = jax.lax.scan(
            gated_body, hidden, params["global_transformer"]
        )

        # strip padding, collect (final, intermediates)
        hidden = hidden.reshape(b * m, t, tlen, c.hidden_size)[:, :, :n_pat]
        inter = jnp.stack(intermediates, axis=-1)  # (BM, S, H, K)
        inter = inter.reshape(b * m, t, tlen, -1)[:, :, :n_pat]
        out = jnp.concatenate(
            [hidden.reshape(b * m, t, n_pat, c.hidden_size), inter], axis=-1
        )
        return out.reshape(b, m, t, n_pat, c.output_dim)


# ---------------------------------------------------------------------------
# text side: cross-attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TextCrossAttention:
    """MllamaTextCrossAttention (modeling_mllama.py:385): GQA over vision
    tokens, per-head-dim RMSNorm on q and k, no rope."""

    config: MllamaTextConfig

    def _q(self) -> ColumnParallelLinear:
        c = self.config
        return ColumnParallelLinear(c.hidden_size, c.num_heads * c.head_dim, dtype=c.dtype)

    def _kv(self) -> ColumnParallelLinear:
        c = self.config
        return ColumnParallelLinear(
            c.hidden_size, c.num_kv_heads * c.head_dim, dtype=c.dtype
        )

    def _o(self) -> RowParallelLinear:
        c = self.config
        return RowParallelLinear(c.num_heads * c.head_dim, c.hidden_size, dtype=c.dtype)

    def _norm(self) -> RMSNorm:
        return RMSNorm(self.config.head_dim, self.config.rms_norm_eps, self.config.dtype)

    def init(self, key) -> Params:
        kq, kk, kv, ko = jax.random.split(key, 4)
        return {
            "q": self._q().init(kq),
            "k": self._kv().init(kk),
            "v": self._kv().init(kv),
            "o": self._o().init(ko),
            "q_norm": self._norm().init(key),
            "k_norm": self._norm().init(key),
        }

    def specs(self) -> Params:
        return {
            "q": self._q().specs(),
            "k": self._kv().specs(),
            "v": self._kv().specs(),
            "o": self._o().specs(),
            "q_norm": self._norm().specs(),
            "k_norm": self._norm().specs(),
        }

    def project_kv(self, params, vision_tokens):
        """K-normed K and raw V over the vision tokens — computed once per
        request at decode time (HF caches these the same way,
        modeling_mllama.py:429-447)."""
        c = self.config
        b, skv, _ = vision_tokens.shape
        k = self._kv()(params["k"], vision_tokens).reshape(
            b, skv, c.num_kv_heads, c.head_dim
        )
        v = self._kv()(params["v"], vision_tokens).reshape(
            b, skv, c.num_kv_heads, c.head_dim
        )
        return self._norm()(params["k_norm"], k), v

    def __call__(self, params, x, vision_tokens, bias, kv=None) -> jax.Array:
        """``kv``: optional precomputed (k, v) from :meth:`project_kv`
        (decode path); when absent they are projected from vision_tokens."""
        c = self.config
        b, sq, _ = x.shape
        q = self._q()(params["q"], x).reshape(b, sq, c.num_heads, c.head_dim)
        q = self._norm()(params["q_norm"], q)
        k, v = kv if kv is not None else self.project_kv(params, vision_tokens)
        skv = k.shape[1]
        group = c.num_heads // c.num_kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        attn = _mha(
            q.reshape(b, sq, -1),
            k.reshape(b, skv, -1),
            v.reshape(b, skv, -1),
            bias,
            c.num_heads,
            c.head_dim,
        )
        return self._o()(params["o"], attn)


@dataclasses.dataclass(frozen=True)
class CrossAttentionDecoderLayer:
    """MllamaCrossAttentionDecoderLayer (modeling_mllama.py:673): zero-init
    tanh gates on both branches; MLP output rows fully masked out for text
    rows that attend no vision token."""

    config: MllamaTextConfig

    def _norm(self) -> RMSNorm:
        c = self.config
        return RMSNorm(c.hidden_size, c.rms_norm_eps, c.dtype)

    def _mlp_cfg(self):
        return self.config.self_attn_layer_config()

    def init(self, key) -> Params:
        from neuronx_distributed_llama3_2_tpu.models.llama import LlamaMLP

        ka, km = jax.random.split(key)
        return {
            "input_layernorm": self._norm().init(key),
            "cross_attn": TextCrossAttention(self.config).init(ka),
            "cross_attn_attn_gate": jnp.zeros((1,), jnp.float32),
            "post_attention_layernorm": self._norm().init(key),
            "mlp": LlamaMLP(self._mlp_cfg()).init(km),
            "cross_attn_mlp_gate": jnp.zeros((1,), jnp.float32),
        }

    def specs(self) -> Params:
        from neuronx_distributed_llama3_2_tpu.models.llama import LlamaMLP

        return {
            "input_layernorm": self._norm().specs(),
            "cross_attn": TextCrossAttention(self.config).specs(),
            "cross_attn_attn_gate": P(None),
            "post_attention_layernorm": self._norm().specs(),
            "mlp": LlamaMLP(self._mlp_cfg()).specs(),
            "cross_attn_mlp_gate": P(None),
        }

    def __call__(self, params, x, vision_tokens, bias, full_row_mask, kv=None):
        from neuronx_distributed_llama3_2_tpu.models.llama import LlamaMLP

        h = TextCrossAttention(self.config)(
            params["cross_attn"],
            self._norm()(params["input_layernorm"], x),
            vision_tokens,
            bias,
            kv=kv,
        )
        # gates stay fp32 (zero-init trainability); the gated residual is
        # computed in fp32 then cast back so a bf16 stream STAYS bf16 —
        # the old promotion silently upcast every layer after the first
        # cross-attn block (and broke the grouped scan's fixed carry type)
        x = x + (
            jnp.tanh(params["cross_attn_attn_gate"]) * h.astype(jnp.float32)
        ).astype(x.dtype)
        h = LlamaMLP(self._mlp_cfg())(
            params["mlp"], self._norm()(params["post_attention_layernorm"], x)
        )
        if full_row_mask is not None:
            # (B, 1, S, 1) head-broadcast mask → (B, S, 1) for the hidden
            # stream (HF applies [:, 0], modeling_mllama.py:720)
            h = full_row_mask[:, 0] * h
        return x + (
            jnp.tanh(params["cross_attn_mlp_gate"]) * h.astype(jnp.float32)
        ).astype(x.dtype)


def prepare_cross_attention_mask(
    cross_attention_mask: jax.Array,  # (B, S_text, M, T) 1=attend
    num_vision_tokens: int,
):
    """HF _prepare_cross_attention_mask (modeling_mllama.py:48-73): expand
    per-tile mask to per-vision-token additive bias + the full-text-row
    mask zeroing rows that attend nothing."""
    b, s = cross_attention_mask.shape[:2]
    mask = jnp.repeat(cross_attention_mask, num_vision_tokens, axis=3)
    mask = mask.reshape(b, s, -1)[:, None, :, :].astype(jnp.float32)
    bias = (1.0 - mask) * NEG
    full_row = (bias != NEG).any(axis=-1).astype(jnp.float32)[..., None]
    bias = bias * full_row
    return bias, full_row


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MllamaForConditionalGeneration:
    """Vision encoder → projector → Llama decoder with interleaved gated
    cross-attention (modeling_mllama.py:1540). Model-protocol compatible
    (init/specs/__call__/loss) so trainer/checkpoint layers apply."""

    config: MllamaConfig
    # shardlint SL002 — see models/llama.py LlamaAttention
    __layout_deps__ = ("sequence_parallel_enabled", "tensor_parallel_size_or")

    def _self_layer(self) -> LlamaDecoderLayer:
        return LlamaDecoderLayer(self.config.text.self_attn_layer_config())

    @staticmethod
    def _tp() -> int:
        return parallel_state.tensor_parallel_size_or(1)

    def _embed(self) -> ParallelEmbedding:
        t = self.config.text
        rows = t.vocab_size + 8
        # +8 special tokens (HF reserves extra rows for the image token
        # etc.) make rows ≡ 8 (mod 16), so at tp=16 — the 11B fitting
        # config, docs/mllama_memory_plan.md — the EMBEDDING (alone; the
        # +8-free LM head still divides) falls back to embedding-dim
        # sharding: H=4096 divides any practical tp, GSPMD keeps the math
        # identical.
        return ParallelEmbedding(
            rows, t.hidden_size, dtype=t.dtype,
            shard_dim="vocab" if rows % self._tp() == 0 else "embed",
        )

    def _projector(self) -> ColumnParallelLinear:
        return ColumnParallelLinear(
            self.config.vision.output_dim,
            self.config.text.hidden_size,
            use_bias=True,
            gather_output=True,
            dtype=self.config.text.dtype,
        )

    def _lm_head(self):
        t = self.config.text
        if t.vocab_size % self._tp() == 0:
            return ColumnParallelLinear(
                t.hidden_size, t.vocab_size, dtype=t.dtype
            )
        # vocab-indivisible tp (NOT the tp=16 case — 128256 % 16 == 0, so
        # the 11B head stays ColumnParallel there; this covers odd vocabs
        # / tp choices generally): shard the head on its INPUT dim — same
        # {"kernel": (H, V)} param tree, XLA all-reduces the partial
        # logits; parallel_cross_entropy takes its plain-CE branch on the
        # replicated logits
        return RowParallelLinear(t.hidden_size, t.vocab_size, dtype=t.dtype)

    def init(self, key) -> Params:
        t = self.config.text
        keys = jax.random.split(key, t.num_hidden_layers + 5)
        layers = []
        for i in range(t.num_hidden_layers):
            if i in t.cross_attention_layers:
                layers.append(CrossAttentionDecoderLayer(t).init(keys[i]))
            else:
                layers.append(self._self_layer().init(keys[i]))
        pattern = text_group_pattern(t)
        if pattern is not None:
            # grouped scan layout: one group's program, G-fold buffer reuse
            layers = _pack_text_layers(layers, pattern)
        return {
            "vision_model": MllamaVisionModel(self.config.vision).init(keys[-5]),
            "multi_modal_projector": self._projector().init(keys[-4]),
            "embed": self._embed().init(keys[-3]),
            "layers": layers,
            "final_norm": RMSNorm(t.hidden_size, t.rms_norm_eps, t.dtype).init(keys[-2]),
            "lm_head": self._lm_head().init(keys[-1]),
        }

    def specs(self) -> Params:
        t = self.config.text
        pattern = text_group_pattern(t)
        if pattern is not None:
            is_p = lambda s: isinstance(s, P)  # noqa: E731
            layers = {
                # (G, k-1, ...) / (G, ...): replicate the stack dims
                "plain": jax.tree.map(
                    lambda s: P(None, None, *s),
                    self._self_layer().specs(),
                    is_leaf=is_p,
                ),
                "xattn": jax.tree.map(
                    lambda s: P(None, *s),
                    CrossAttentionDecoderLayer(t).specs(),
                    is_leaf=is_p,
                ),
            }
        else:
            layers = []
            for i in range(t.num_hidden_layers):
                if i in t.cross_attention_layers:
                    layers.append(CrossAttentionDecoderLayer(t).specs())
                else:
                    layers.append(self._self_layer().specs())
        return {
            "vision_model": MllamaVisionModel(self.config.vision).specs(),
            "multi_modal_projector": self._projector().specs(),
            "embed": self._embed().specs(),
            "layers": layers,
            "final_norm": RMSNorm(t.hidden_size, t.rms_norm_eps, t.dtype).specs(),
            "lm_head": self._lm_head().specs(),
        }

    def encode_images(
        self, params, pixel_values, aspect_ratio_ids, aspect_ratio_mask
    ) -> jax.Array:
        """(B, M·T·P, text_hidden) projected vision tokens."""
        v = MllamaVisionModel(self.config.vision)(
            params["vision_model"], pixel_values, aspect_ratio_ids, aspect_ratio_mask
        )
        b = v.shape[0]
        proj = self._projector()(
            params["multi_modal_projector"],
            v.astype(self.config.text.dtype),
        )
        return proj.reshape(b, -1, self.config.text.hidden_size)

    def __call__(
        self,
        params: Params,
        input_ids: jax.Array,            # (B, S)
        pixel_values: jax.Array,         # (B, M, T, C, H, W)
        aspect_ratio_ids: jax.Array,     # (B, M)
        aspect_ratio_mask: jax.Array,    # (B, M, T)
        cross_attention_mask: Optional[jax.Array] = None,  # (B, S, M, T)
    ) -> jax.Array:
        hidden = self._hidden(
            params, input_ids, pixel_values, aspect_ratio_ids,
            aspect_ratio_mask, cross_attention_mask,
        )
        return self._lm_head()(params["lm_head"], hidden)

    def _hidden(
        self, params, input_ids, pixel_values, aspect_ratio_ids,
        aspect_ratio_mask, cross_attention_mask,
    ) -> jax.Array:
        """Final-norm'ed decoder hidden states (pre LM-head)."""
        t = self.config.text
        vision_tokens = self.encode_images(
            params, pixel_values, aspect_ratio_ids, aspect_ratio_mask
        )
        bias = full_row = None
        if cross_attention_mask is not None:
            bias, full_row = prepare_cross_attention_mask(
                cross_attention_mask, self.config.vision.num_patches
            )
        b, s = input_ids.shape
        x = self._embed()(params["embed"], input_ids)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        sin, cos = precompute_rope(t.head_dim, s, t.rope_theta, t.rope_scaling)
        sp = parallel_state.sequence_parallel_enabled()
        if sp:
            # Megatron SP over the text stream (same GSPMD formulation as
            # llama._backbone): shard seq over tp between blocks, so every
            # (B, S, H) activation — incl. the remat stash that dominates
            # the 11B memory plan's Lt·S term — carries S/tp per chip. The
            # self layers adapt via the parallel-state flag; cross-attn
            # q/o projections gather/reduce-scatter at their boundaries
            # under the same constraint.
            x = constrain(x, P(BATCH_AXES, TP_AXIS, None))
        layer = self._self_layer()
        xlayer = CrossAttentionDecoderLayer(t)

        # vision_tokens / bias passed explicitly (not closure-captured):
        # jax.checkpoint must see differentiated operands as arguments
        def self_body(lp, x):
            return layer(lp, x, sin, cos, positions)

        def xattn_body(lp, x, vt):
            return xlayer(lp, x, vt, bias, full_row)

        from neuronx_distributed_llama3_2_tpu.models.llama import _remat_policy

        policy = _remat_policy(t.remat)
        if policy is not None:
            self_body = jax.checkpoint(self_body, policy=policy)
            xattn_body = jax.checkpoint(xattn_body, policy=policy)
        pattern = text_group_pattern(t)
        if pattern is not None:
            # grouped scan (program = ONE group of k layers; buffers reused
            # across the G groups — the Python loop carried ~0.17 GB/layer
            # of unreusable temp, docs/mllama_memory_plan.md)
            _, k, xpos = pattern

            def group_body(x, xs):
                plains, xat = xs
                p = 0
                for j in range(k):
                    if j == xpos:
                        x = xattn_body(xat, x, vision_tokens)
                    else:
                        lp = jax.tree.map(lambda a, _p=p: a[_p], plains)
                        x = self_body(lp, x)
                        p += 1
                return x, None

            x, _ = jax.lax.scan(
                group_body,
                x,
                (params["layers"]["plain"], params["layers"]["xattn"]),
            )
        else:
            for i, lp in enumerate(params["layers"]):
                if i in t.cross_attention_layers:
                    x = xattn_body(lp, x, vision_tokens)
                else:
                    x = self_body(lp, x)
        x = RMSNorm(t.hidden_size, t.rms_norm_eps, t.dtype)(
            params["final_norm"], x
        )
        if sp:
            # exit SP before the loss/lm-head consumers (reference
            # gather_from_sequence_parallel_region, modeling_llama_nxd.py:625)
            x = constrain(x, P(BATCH_AXES, None, None))
        return x

    def loss(
        self,
        params: Params,
        input_ids: jax.Array,
        labels: jax.Array,
        pixel_values: jax.Array,
        aspect_ratio_ids: jax.Array,
        aspect_ratio_mask: jax.Array,
        cross_attention_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        hidden = self._hidden(
            params, input_ids, pixel_values, aspect_ratio_ids,
            aspect_ratio_mask, cross_attention_mask,
        )
        # chunked fused CE over pre-head hidden states: the (B, S, vocab)
        # logits never materialize (same memory discipline as
        # LlamaForCausalLM.loss_from_hidden)
        shifted = labels[:, 1:]
        loss_sum, count = fused_linear_cross_entropy(
            hidden[:, :-1, :],
            lambda hc: self._lm_head()(params["lm_head"], hc),
            shifted,
            chunk_size=min(512, hidden.shape[1]),
        )
        return loss_sum / jnp.maximum(count, 1.0)


# ---------------------------------------------------------------------------
# HF weight conversion
# ---------------------------------------------------------------------------

def mllama_params_from_hf(state_dict: Dict[str, Any], config: MllamaConfig) -> Params:
    """HF Mllama state dict → this model's pytree (same role as
    llama.params_from_hf; torch Linear (out, in) → (in, out))."""
    import numpy as np

    def t(name):
        w = state_dict[name]
        if hasattr(w, "detach"):
            w = w.detach().cpu().numpy()
        return np.asarray(w, dtype=np.float32)

    def lin(name):
        return {"kernel": jnp.asarray(t(name + ".weight").T)}

    def lin_b(name):
        return {
            "kernel": jnp.asarray(t(name + ".weight").T),
            "bias": jnp.asarray(t(name + ".bias")),
        }

    def ln(name):
        return {
            "scale": jnp.asarray(t(name + ".weight")),
            "bias": jnp.asarray(t(name + ".bias")),
        }

    def rms(name):
        return {"scale": jnp.asarray(t(name + ".weight"))}

    vp = "model.vision_model."
    c = config.vision

    def vis_layer(prefix):
        p = {
            "input_layernorm": ln(prefix + "input_layernorm"),
            "self_attn": {
                "q": lin(prefix + "self_attn.q_proj"),
                "k": lin(prefix + "self_attn.k_proj"),
                "v": lin(prefix + "self_attn.v_proj"),
                "o": lin(prefix + "self_attn.o_proj"),
            },
            "post_attention_layernorm": ln(prefix + "post_attention_layernorm"),
            "mlp": {
                "fc1": lin_b(prefix + "mlp.fc1"),
                "fc2": lin_b(prefix + "mlp.fc2"),
            },
        }
        if prefix.startswith(vp + "global_transformer"):
            p["gate_attn"] = jnp.asarray(t(prefix + "gate_attn")).reshape(1)
            p["gate_ffn"] = jnp.asarray(t(prefix + "gate_ffn")).reshape(1)
        return p

    # patch conv: torch OIHW → HWIO
    conv_w = t(vp + "patch_embedding.weight")
    vision: Params = {
        "patch_embedding": {
            "kernel": jnp.asarray(np.transpose(conv_w, (2, 3, 1, 0)))
        },
        "class_embedding": jnp.asarray(t(vp + "class_embedding")),
        "gated_positional_embedding": {
            "embedding": jnp.asarray(t(vp + "gated_positional_embedding.embedding")),
            "tile_embedding": jnp.asarray(
                t(vp + "gated_positional_embedding.tile_embedding.weight")
            ),
            "gate": jnp.asarray(t(vp + "gated_positional_embedding.gate")).reshape(1),
        },
        "pre_tile_positional_embedding": {
            "embedding": jnp.asarray(
                t(vp + "pre_tile_positional_embedding.embedding.weight")
            ),
            "gate": jnp.asarray(
                t(vp + "pre_tile_positional_embedding.gate")
            ).reshape(1),
        },
        "post_tile_positional_embedding": {
            "embedding": jnp.asarray(
                t(vp + "post_tile_positional_embedding.embedding.weight")
            ),
            "gate": jnp.asarray(
                t(vp + "post_tile_positional_embedding.gate")
            ).reshape(1),
        },
        "layernorm_pre": ln(vp + "layernorm_pre"),
        "layernorm_post": ln(vp + "layernorm_post"),
        "transformer": _stack_trees(
            [
                vis_layer(f"{vp}transformer.layers.{i}.")
                for i in range(c.num_hidden_layers)
            ]
        ),
        "global_transformer": _stack_trees(
            [
                vis_layer(f"{vp}global_transformer.layers.{i}.")
                for i in range(c.num_global_layers)
            ]
        ),
    }

    tp_ = "model.language_model."
    tc = config.text
    layers = []
    for i in range(tc.num_hidden_layers):
        pre = f"{tp_}layers.{i}."
        if i in tc.cross_attention_layers:
            layers.append(
                {
                    "input_layernorm": rms(pre + "input_layernorm"),
                    "cross_attn": {
                        "q": lin(pre + "cross_attn.q_proj"),
                        "k": lin(pre + "cross_attn.k_proj"),
                        "v": lin(pre + "cross_attn.v_proj"),
                        "o": lin(pre + "cross_attn.o_proj"),
                        "q_norm": rms(pre + "cross_attn.q_norm"),
                        "k_norm": rms(pre + "cross_attn.k_norm"),
                    },
                    "cross_attn_attn_gate": jnp.asarray(
                        t(pre + "cross_attn_attn_gate")
                    ).reshape(1),
                    "post_attention_layernorm": rms(pre + "post_attention_layernorm"),
                    "mlp": _hf_mlp(t, pre),
                    "cross_attn_mlp_gate": jnp.asarray(
                        t(pre + "cross_attn_mlp_gate")
                    ).reshape(1),
                }
            )
        else:
            layers.append(
                {
                    "attn_norm": rms(pre + "input_layernorm"),
                    "attn": {
                        "qkv": {
                            "q_kernel": jnp.asarray(t(pre + "self_attn.q_proj.weight").T),
                            "k_kernel": jnp.asarray(t(pre + "self_attn.k_proj.weight").T),
                            "v_kernel": jnp.asarray(t(pre + "self_attn.v_proj.weight").T),
                        },
                        "o": lin(pre + "self_attn.o_proj"),
                    },
                    "mlp_norm": rms(pre + "post_attention_layernorm"),
                    "mlp": _hf_mlp(t, pre),
                }
            )

    pattern = text_group_pattern(tc)
    if pattern is not None:
        layers = _pack_text_layers(layers, pattern)
    return {
        "vision_model": vision,
        "multi_modal_projector": lin_b("model.multi_modal_projector"),
        "embed": {"embedding": jnp.asarray(t(tp_ + "embed_tokens.weight"))},
        "layers": layers,
        "final_norm": rms(tp_ + "norm"),
        "lm_head": lin("lm_head"),
    }


def _hf_mlp(t, pre):
    import numpy as np

    gate = t(pre + "mlp.gate_proj.weight").T
    up = t(pre + "mlp.up_proj.weight").T
    return {
        "gate_up": jnp.asarray(np.stack([gate, up], axis=1)),  # (H, 2, I)
        "down": {"kernel": jnp.asarray(t(pre + "mlp.down_proj.weight").T)},
    }


def mllama_params_to_hf(params: Params, config: MllamaConfig) -> Dict[str, Any]:
    """Inverse of :func:`mllama_params_from_hf`: pytree → HF Mllama state
    dict (numpy fp32, torch layouts — Linear (out, in), conv OIHW).
    Completes the native→HF direction for the vision family (reference
    converter role, scripts/checkpoint_converter.py:685)."""
    import numpy as np

    def np32(x):
        return np.asarray(x, dtype=np.float32)

    sd: Dict[str, Any] = {}

    def put_lin(name, p):
        sd[name + ".weight"] = np32(p["kernel"]).T
        if "bias" in p:
            sd[name + ".bias"] = np32(p["bias"])

    def put_ln(name, p):
        sd[name + ".weight"] = np32(p["scale"])
        if "bias" in p:
            sd[name + ".bias"] = np32(p["bias"])

    vp = "model.vision_model."
    vis = params["vision_model"]
    # HWIO → torch OIHW
    sd[vp + "patch_embedding.weight"] = np.transpose(
        np32(vis["patch_embedding"]["kernel"]), (3, 2, 0, 1)
    )
    sd[vp + "class_embedding"] = np32(vis["class_embedding"])
    gpe = vis["gated_positional_embedding"]
    sd[vp + "gated_positional_embedding.embedding"] = np32(gpe["embedding"])
    sd[vp + "gated_positional_embedding.tile_embedding.weight"] = np32(
        gpe["tile_embedding"]
    )
    sd[vp + "gated_positional_embedding.gate"] = np32(gpe["gate"]).reshape(1)
    for which in ("pre", "post"):
        tpe = vis[f"{which}_tile_positional_embedding"]
        sd[vp + f"{which}_tile_positional_embedding.embedding.weight"] = np32(
            tpe["embedding"]
        )
        sd[vp + f"{which}_tile_positional_embedding.gate"] = np32(
            tpe["gate"]
        ).reshape(1)
    put_ln(vp + "layernorm_pre", vis["layernorm_pre"])
    put_ln(vp + "layernorm_post", vis["layernorm_post"])

    def put_vis_layer(prefix, p, gated):
        put_ln(prefix + "input_layernorm", p["input_layernorm"])
        for k in ("q", "k", "v", "o"):
            put_lin(prefix + f"self_attn.{k}_proj", p["self_attn"][k])
        put_ln(
            prefix + "post_attention_layernorm", p["post_attention_layernorm"]
        )
        put_lin(prefix + "mlp.fc1", p["mlp"]["fc1"])
        put_lin(prefix + "mlp.fc2", p["mlp"]["fc2"])
        if gated:
            sd[prefix + "gate_attn"] = np32(p["gate_attn"]).reshape(1)
            sd[prefix + "gate_ffn"] = np32(p["gate_ffn"]).reshape(1)

    n_plain = jax.tree.leaves(vis["transformer"])[0].shape[0]
    for i in range(n_plain):
        put_vis_layer(
            f"{vp}transformer.layers.{i}.",
            jax.tree.map(lambda x: x[i], vis["transformer"]),
            gated=False,
        )
    n_global = jax.tree.leaves(vis["global_transformer"])[0].shape[0]
    for i in range(n_global):
        put_vis_layer(
            f"{vp}global_transformer.layers.{i}.",
            jax.tree.map(lambda x: x[i], vis["global_transformer"]),
            gated=True,
        )

    def put_mlp(pre, mlp):
        gate_up = np32(mlp["gate_up"])  # (H, 2, I)
        sd[pre + "mlp.gate_proj.weight"] = gate_up[:, 0, :].T
        sd[pre + "mlp.up_proj.weight"] = gate_up[:, 1, :].T
        sd[pre + "mlp.down_proj.weight"] = np32(mlp["down"]["kernel"]).T

    tp_ = "model.language_model."
    tc = config.text
    pattern = text_group_pattern(tc)
    if pattern is not None:
        text_layers = [
            text_layer_slice(params["layers"], i, pattern)[0]
            for i in range(tc.num_hidden_layers)
        ]
    else:
        text_layers = params["layers"]
    for i, p in enumerate(text_layers):
        pre = f"{tp_}layers.{i}."
        if i in tc.cross_attention_layers:
            put_ln(pre + "input_layernorm", p["input_layernorm"])
            for k in ("q", "k", "v", "o"):
                put_lin(pre + f"cross_attn.{k}_proj", p["cross_attn"][k])
            put_ln(pre + "cross_attn.q_norm", p["cross_attn"]["q_norm"])
            put_ln(pre + "cross_attn.k_norm", p["cross_attn"]["k_norm"])
            sd[pre + "cross_attn_attn_gate"] = np32(
                p["cross_attn_attn_gate"]
            ).reshape(1)
            sd[pre + "cross_attn_mlp_gate"] = np32(
                p["cross_attn_mlp_gate"]
            ).reshape(1)
            put_ln(pre + "post_attention_layernorm", p["post_attention_layernorm"])
            put_mlp(pre, p["mlp"])
        else:
            put_ln(pre + "input_layernorm", p["attn_norm"])
            qkv = p["attn"]["qkv"]
            sd[pre + "self_attn.q_proj.weight"] = np32(qkv["q_kernel"]).T
            sd[pre + "self_attn.k_proj.weight"] = np32(qkv["k_kernel"]).T
            sd[pre + "self_attn.v_proj.weight"] = np32(qkv["v_kernel"]).T
            put_lin(pre + "self_attn.o_proj", p["attn"]["o"])
            put_ln(pre + "post_attention_layernorm", p["mlp_norm"])
            put_mlp(pre, p["mlp"])

    put_lin("model.multi_modal_projector", params["multi_modal_projector"])
    sd[tp_ + "embed_tokens.weight"] = np32(params["embed"]["embedding"])
    put_ln(tp_ + "norm", params["final_norm"])
    put_lin("lm_head", params["lm_head"])
    return sd
