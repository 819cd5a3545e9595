"""Mllama (Llama-3.2 Vision) generation: KV-cache decode with static
cross-attention states.

The reference has no vision inference stack to port; the design follows its
text decode architecture (model_base.py:52 cache decoder) extended the way
Mllama requires: the vision encoder + projector run ONCE per request, each
cross-attention layer's K/V over the vision tokens are precomputed once
(they never grow during decoding — HF caches them the same way,
modeling_mllama.py:429-447), and the token-by-token loop only updates the
self-attention layers' rolling KV cache.

Reuse over re-implementation: self-attention cache layers execute through
:meth:`..inference.model.LlamaDecode._decode_layer` (the same scatter-write +
block-causal cache attention + sharding constraints the text engine uses),
and cross layers through the *model's own*
:class:`..models.mllama.CrossAttentionDecoderLayer` with precomputed K/V —
so decode can never drift numerically from the training forward.

Greedy semantics match HF ``MllamaForConditionalGeneration.generate``
incl. EOS stopping (verified in tests/test_mllama_decode.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode
from neuronx_distributed_llama3_2_tpu.models.llama import (
    RMSNorm,
    precompute_rope,
)
from neuronx_distributed_llama3_2_tpu.models.mllama import (
    CrossAttentionDecoderLayer,
    MllamaConfig,
    MllamaForConditionalGeneration,
    TextCrossAttention,
    prepare_cross_attention_mask,
    text_group_pattern,
    text_layer_slice,
)

Params = Dict[str, Any]


def _layer_at(layers, i: int, t):
    """Per-layer param tree for absolute layer ``i`` under either text
    layout (grouped scan stacks or the irregular-pattern list)."""
    pattern = text_group_pattern(t)
    if pattern is not None:
        return text_layer_slice(layers, i, pattern)
    return layers[i], i in t.cross_attention_layers


class MllamaCache(NamedTuple):
    """Self-attention rolling cache (per self layer) + static cross K/V
    (per cross layer, precomputed from the vision tokens)."""

    k: List[jax.Array]        # per self-layer (B, S_max, NKV, D)
    v: List[jax.Array]
    cross_k: List[jax.Array]  # per cross-layer (B, S_vis, NKV, D), k-normed
    cross_v: List[jax.Array]


class MllamaDecoder:
    """Greedy generation for the vision model (single sequence, batch 1 —
    the logit-parity gate path; batching rides the same programs)."""

    def __init__(self, config: MllamaConfig, params: Params, max_seq_len: int = 512):
        self.config = config
        self.params = params
        self.max_seq_len = max_seq_len
        self.model = MllamaForConditionalGeneration(config)
        # the text-engine decode layer, reused for the self-attn cache path
        self._decode = LlamaDecode(config.text.self_attn_layer_config())
        self._self_layers = [
            i
            for i in range(config.text.num_hidden_layers)
            if i not in config.text.cross_attention_layers
        ]
        self._fwd = jax.jit(self.forward)
        self._precompute = jax.jit(self._precompute_cross_kv_impl)

    def _live_params(self, params: Params) -> Params:
        """int8/fp8 trees stay resident; every program dequantizes in-jit so
        XLA fuses the cast into consumers — the shared serving discipline
        (quantization.live_params, checked per CALL on the tree passed, not
        one captured at construction). The vision subtree dequantizes to its
        own dtype; everything else to the text dtype."""
        from neuronx_distributed_llama3_2_tpu.quantization import live_params

        out = dict(live_params(
            {k: v for k, v in params.items() if k != "vision_model"},
            self.config.text.dtype,
        ))
        out["vision_model"] = live_params(
            params["vision_model"], self.config.vision.dtype
        )
        return out

    # -- one-time per request ---------------------------------------------

    def precompute_cross_kv(
        self, pixel_values, aspect_ratio_ids, aspect_ratio_mask
    ) -> Tuple[jax.Array, List[jax.Array], List[jax.Array]]:
        """(vision_tokens, cross_k per layer, cross_v per layer)."""
        return self._precompute(
            self.params, pixel_values, aspect_ratio_ids, aspect_ratio_mask
        )

    def _precompute_cross_kv_impl(
        self, params, pixel_values, aspect_ratio_ids, aspect_ratio_mask
    ):
        t = self.config.text
        params = self._live_params(params)
        vision_tokens = self.model.encode_images(
            params, pixel_values, aspect_ratio_ids, aspect_ratio_mask
        )
        xattn = TextCrossAttention(t)
        ks, vs = [], []
        for i in self.config.text.cross_attention_layers:
            lp, is_cross = _layer_at(params["layers"], i, t)
            assert is_cross
            k, v = xattn.project_kv(lp["cross_attn"], vision_tokens)
            ks.append(k)
            vs.append(v)
        return vision_tokens, ks, vs

    # -- block forward -----------------------------------------------------

    def forward(
        self,
        params: Params,
        cache: MllamaCache,
        tokens: jax.Array,     # (B, T)
        positions: jax.Array,  # (B,)
        bias,                  # cross-attn additive bias for this block
        full_row,
    ) -> Tuple[jax.Array, MllamaCache]:
        """Block-causal forward over the self-attn cache; cross layers use
        the static precomputed K/V. Returns (logits (B, T, V), cache)."""
        t = self.config.text
        b, tlen = tokens.shape
        params = self._live_params(params)
        x = self.model._embed()(params["embed"], tokens)
        pos_block = positions[:, None] + jnp.arange(tlen, dtype=jnp.int32)[None, :]
        sin, cos = precompute_rope(
            t.head_dim, self.max_seq_len, t.rope_theta, t.rope_scaling
        )
        slots = jnp.arange(b, dtype=jnp.int32)

        xlayer = CrossAttentionDecoderLayer(t)
        new_k = list(cache.k)
        new_v = list(cache.v)
        si = 0  # index into self-layer caches
        ci = 0  # index into cross-layer K/V
        for i in range(t.num_hidden_layers):
            lp, _ = _layer_at(params["layers"], i, t)
            if i in t.cross_attention_layers:
                x = xlayer(
                    lp, x, None, bias, full_row,
                    kv=(cache.cross_k[ci], cache.cross_v[ci]),
                )
                ci += 1
            else:
                # the shared layer takes a stack of layers and an index into
                # it: this cache is a list, so a stack of one and index 0
                x, kc, vc = self._decode._decode_layer(
                    lp, x, new_k[si][None], new_v[si][None], 0, sin, cos,
                    pos_block, positions, slots, context_encode=False,
                )
                new_k[si], new_v[si] = kc[0], vc[0]
                si += 1

        x = RMSNorm(t.hidden_size, t.rms_norm_eps, t.dtype)(
            params["final_norm"], x
        )
        logits = self.model._lm_head()(params["lm_head"], x)
        return logits, MllamaCache(new_k, new_v, cache.cross_k, cache.cross_v)

    # -- generation --------------------------------------------------------

    def generate(
        self,
        prompt: Sequence[int],
        pixel_values,
        aspect_ratio_ids,
        aspect_ratio_mask,
        cross_attention_mask,  # (1, len(prompt), M, T)
        max_new_tokens: int = 32,
        eos_token_id: int = -1,
    ) -> List[int]:
        """Greedy continuation; stops at ``eos_token_id`` (pass -1 to
        disable, e.g. for fixed-length benchmarking)."""
        t = self.config.text
        c_vis = self.config.vision
        if max_new_tokens < 1:
            return []
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        _, cross_k, cross_v = self.precompute_cross_kv(
            pixel_values, aspect_ratio_ids, aspect_ratio_mask
        )
        nkv, hd = t.num_kv_heads, t.head_dim
        cache = MllamaCache(
            k=[
                jnp.zeros((1, self.max_seq_len, nkv, hd), t.dtype)
                for _ in self._self_layers
            ],
            v=[
                jnp.zeros((1, self.max_seq_len, nkv, hd), t.dtype)
                for _ in self._self_layers
            ],
            cross_k=cross_k,
            cross_v=cross_v,
        )

        xmask = np.asarray(cross_attention_mask)
        bias, full_row = prepare_cross_attention_mask(
            jnp.asarray(xmask), c_vis.num_patches
        )
        toks = jnp.asarray([list(prompt)], jnp.int32)
        logits, cache = self._fwd(
            self.params, cache, toks, jnp.zeros((1,), jnp.int32), bias, full_row
        )
        out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]

        # generated tokens inherit the last prompt row's tile visibility
        # (HF extends cross_attention_mask the same way in generate)
        step_mask = xmask[:, -1:, :, :]
        step_bias, step_full = prepare_cross_attention_mask(
            jnp.asarray(step_mask), c_vis.num_patches
        )
        pos = len(prompt)
        while len(out) < max_new_tokens and out[-1] != eos_token_id:
            logits, cache = self._fwd(
                self.params, cache,
                jnp.asarray([[out[-1]]], jnp.int32),
                jnp.asarray([pos], jnp.int32),
                step_bias, step_full,
            )
            out.append(int(jnp.argmax(logits[0, 0])))
            pos += 1
        return out
