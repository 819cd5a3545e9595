"""Brumby model family (``manifestai/Brumby-14B-Base``, HF ``model_type:
brumby``), TPU-native: a dense Llama/Qwen3-shaped decoder in which **every
layer's attention is power retention** (arXiv:2507.04239) — a layer's memory
of the past is one fixed-size state per kv head, not rows per token.

The layer (``h`` the RMS-normed input, ``d`` the head width, degree 2):

- ``q = W_q h``, ``k = W_k h``, ``v = W_v h`` (GQA: query head ``n`` reads kv
  head ``n // group``); RMSNorm with a learned scale over each head of q and
  of k; rotary (rotate-half) on q and k; a gate ``log g_t = log σ(W_g h_t)``,
  one scalar a kv head a token. No bias anywhere.
- *Attention form* (what the benchmark's plain reference computes):
  ``A_ij = (q_i·k_j / √d)² · exp(Σ_{m=j+1..i} log g_m)`` for ``j ≤ i``;
  ``y_i = Σ_j A_ij v_j / (Σ_j A_ij + ε)``. Every weight is ≥ 0: no softmax and
  no running maximum.
- *Recurrent form* (:func:`retention_step`, decode): ``S_t = g_t S_{t−1} +
  φ(k_t) v_tᵀ``, ``z_t = g_t z_{t−1} + φ(k_t)``, ``y_t = φ(q_t)ᵀ S_t /
  (φ(q_t)ᵀ z_t + ε)`` with ``φ(a)·φ(b) = (a·b)²`` (:func:`power_features`).
- *Chunked form* (:func:`retention_chunks`, prefill and training): inside a
  chunk the attention form over the chunk's own rows plus ``φ(q_i)ᵀ S_in``
  decayed to row ``i``; ``S_out`` from ``S_in`` and the chunk's rows. Chunk
  boundaries change no result.

``φ`` here is the symmetric square tiled by blocks of :data:`PHI_BLOCK`
values: of the ``(d/16)²`` blocks of the outer product ``a aᵀ`` the upper
triangle is kept, the off-diagonal blocks times √2. At d = 128 that is 36
blocks of 256 = 9,216 features (the exact symmetric square has 8,256, the
plain outer product 16,384); it is a broadcast and a multiply, with no gather.

The training-side model (:class:`BrumbyForCausalLM`) makes the weights and
runs the chunked form from the zero state; the serving engines run
:class:`..inference.model.RetentionDecode` over a
:class:`..inference.model.StateCache`. Retention under ``tp > 1`` shards by kv
head like :class:`..llama.LlamaAttention` (specs only: not run on a chip).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    LlamaMLP,
    _head_axis,
    params_from_hf,
    params_to_hf,
)
from neuronx_distributed_llama3_2_tpu.parallel.layers import default_kernel_init

Params = Dict[str, Any]

# φ keeps the upper triangle of the outer product's blocks of this many values
PHI_BLOCK = 16
# the chunked form runs over chunks of at most this many rows: its largest
# temporaries are φ of one chunk's queries and a (chunk, chunk) weight matrix
# a head, whatever the sequence length. A 512-row bucket a layer at the
# published widths on the v5e: 2.91 ms and 456 MB of temporaries at 512,
# 2.76 / 234 at 256, 2.39 / 26 at 128 (PERF.md section 6, PR 36). The state
# goes from one chunk to the next in the dtype it came in (the pool's): a
# float32 pool accumulates in float32 throughout, a lower one rounds here too
RETENTION_CHUNK = 128
# what a serving state accumulates in: a recurrence sums thousands of updates
# (published hybrid configs state the same, ``mamba_ssm_dtype: float32``)
STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class BrumbyConfig(LlamaConfig):
    """LlamaConfig with the family's defaults. The degree (2), the gate, the
    normaliser and the per-head QK-norm are the model, not fields."""

    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    rope_theta: float = 1e6
    # added to the sum of weights under every output
    retention_eps: float = 1e-6

    @property
    def feature_width(self) -> int:
        """Width of φ of one head (:func:`power_features`)."""
        return feature_width(self.head_dim)

    def state_bytes_per_layer(self) -> int:
        """Bytes of one sequence's state in one layer: ``S`` (kv heads, φ,
        head) and the normaliser ``z`` (kv heads, φ)."""
        per_head = self.feature_width * (self.head_dim + 1)
        return self.num_kv_heads * per_head * jnp.dtype(STATE_DTYPE).itemsize


BRUMBY_CONFIGS: Dict[str, BrumbyConfig] = {
    # manifestai/Brumby-14B-Base config.json values (Qwen3-14B's widths)
    "brumby-14b": BrumbyConfig(
        vocab_size=151936, hidden_size=5120, intermediate_size=17408,
        num_layers=40, num_heads=40, num_kv_heads=8, head_dim=128,
        max_seq_len=32768,
    ),
    # two blocks of φ a head (3 of the 4 kept), a GQA group of 2
    "tiny-brumby": BrumbyConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32, remat="none",
    ),
}


# ---------------------------------------------------------------------------
# the feature map and the three forms
# ---------------------------------------------------------------------------

def feature_width(head_dim: int) -> int:
    if head_dim % PHI_BLOCK:
        raise ValueError(f"head_dim must be a multiple of {PHI_BLOCK}")
    blocks = head_dim // PHI_BLOCK
    return blocks * (blocks + 1) // 2 * PHI_BLOCK * PHI_BLOCK


def power_features(a: jax.Array) -> jax.Array:
    """φ of a (..., d) -> (..., feature_width(d)) with ``φ(a)·φ(b) = (a·b)²``:
    block row ``i`` of ``a aᵀ`` from its diagonal block on, the blocks right
    of the diagonal times √2 (they stand for their mirror images too)."""
    d = a.shape[-1]
    pieces = []
    for start in range(0, d, PHI_BLOCK):
        right = a[..., start:]
        weight = jnp.where(jnp.arange(d - start) < PHI_BLOCK, 1.0, math.sqrt(2.0))
        right = right * weight.astype(a.dtype)
        piece = a[..., start:start + PHI_BLOCK, None] * right[..., None, :]
        pieces.append(piece.reshape(a.shape[:-1] + (PHI_BLOCK * (d - start),)))
    return jnp.concatenate(pieces, axis=-1)


def _one_chunk(state, z, q, k, v, log_g, eps):
    """The chunked form over one chunk of one sequence. state (K, D, dv),
    z (K, D) float; q (t, K, G, d) already scaled by d^-1/2; k (t, K, d),
    v (t, K, dv), log_g (t, K) float32. Returns (y (t, K, G, dv) float32,
    state, z)."""
    t = q.shape[0]
    f32 = jnp.float32
    with jax.named_scope("expand"):
        phi_q, phi_k = power_features(q), power_features(k)
    with jax.named_scope("chunk"):
        decay = jnp.cumsum(log_g, axis=0)                              # (t, K)
        # in-chunk: (q_i·k_j)² · exp(decay_i − decay_j), j ≤ i — never above 1
        scores = jnp.square(jnp.einsum("ikgd,jkd->kgij", q, k, preferred_element_type=f32))
        gap = decay.T[:, :, None] - decay.T[:, None, :]                # (K, i, j)
        causal = lax.iota(jnp.int32, t)[:, None] >= lax.iota(jnp.int32, t)[None, :]
        weights = scores * jnp.exp(jnp.where(causal, gap, -jnp.inf))[:, None]
        num = jnp.einsum("kgij,jkv->ikgv", weights.astype(v.dtype), v, preferred_element_type=f32)
        den = jnp.sum(weights, axis=-1).transpose(2, 0, 1)             # (t, K, G)
        # what the state carries in, decayed to row i
        into = jnp.exp(decay)[:, :, None]                              # (t, K, 1)
        num = num + into[..., None] * jnp.einsum(
            "ikgD,kDv->ikgv", phi_q, state.astype(phi_q.dtype), preferred_element_type=f32)
        den = den + into * jnp.einsum(
            "ikgD,kD->ikg", phi_q, z.astype(phi_q.dtype), preferred_element_type=f32)
        # the state after the chunk's last row
        out = jnp.exp(decay[-1][None, :] - decay)                      # (t, K)
        weighted = phi_k * out[..., None].astype(phi_k.dtype)
        total = jnp.exp(decay[-1])
        state = (total[:, None, None] * state.astype(f32) + jnp.einsum(
            "jkD,jkv->kDv", weighted, v, preferred_element_type=f32)).astype(state.dtype)
        z = (total[:, None] * z.astype(f32) + jnp.sum(weighted.astype(f32), axis=0)).astype(z.dtype)
        return num / (den[..., None] + eps), state, z


def retention_chunks(state, z, q, k, v, log_g, live, eps):
    """The chunked form over one sequence's ``t`` rows, :data:`RETENTION_CHUNK`
    at a time, from ``(state, z)``. Rows at or past ``live`` leave the state
    untouched (``log g = 0``, ``φ(k) = 0``) and their outputs mean nothing: a
    state, unlike a row of keys, cannot be masked out later. q (t, K, G, d)
    unscaled. Returns (y (t, K, G, dv) in q's dtype, state, z)."""
    t, d = q.shape[0], q.shape[-1]
    alive = lax.iota(jnp.int32, t) < live
    k = jnp.where(alive[:, None, None], k, 0)
    log_g = jnp.where(alive[:, None], log_g, 0.0)
    q = q * jnp.asarray(d ** -0.5, q.dtype)
    if t <= RETENTION_CHUNK:
        y, state, z = _one_chunk(state, z, q, k, v, log_g, eps)
        return y.astype(q.dtype), state, z
    chunks = -(-t // RETENTION_CHUNK)
    pad = chunks * RETENTION_CHUNK - t          # dead rows: zero keys, log g = 0

    def split(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((chunks, RETENTION_CHUNK) + a.shape[1:])

    def body(carry, rows):
        y, s_new, z_new = _one_chunk(*carry, *rows, eps)
        return (s_new, z_new), y.astype(q.dtype)

    (state, z), y = lax.scan(body, (state, z), tuple(map(split, (q, k, v, log_g))))
    return y.reshape((chunks * RETENTION_CHUNK,) + y.shape[2:])[:t], state, z


def retention_step(state, z, q, k, v, log_g, eps):
    """The recurrent form: one token of one sequence. state (K, D, dv),
    z (K, D); q (K, G, d) unscaled, k (K, d), v (K, dv), log_g (K,) float32.
    The output reads the *old* state — ``φ(q)ᵀ S_t = g φ(q)ᵀ S_{t−1} +
    (q·k)² v`` — so the read and the update are two independent passes over
    it. Returns (y (K, G, dv) in q's dtype, state, z)."""
    f32 = jnp.float32
    d = q.shape[-1]
    qs = q * jnp.asarray(d ** -0.5, q.dtype)
    with jax.named_scope("expand"):
        phi_q, phi_k = power_features(qs).astype(f32), power_features(k).astype(f32)
    with jax.named_scope("step"):
        g = jnp.exp(log_g)
        own = jnp.square(jnp.einsum("kgd,kd->kg", qs, k, preferred_element_type=f32))
        num = g[:, None, None] * jnp.einsum("kgD,kDv->kgv", phi_q, state.astype(f32)) \
            + own[..., None] * v.astype(f32)[:, None, :]
        den = g[:, None] * jnp.einsum("kgD,kD->kg", phi_q, z.astype(f32)) + own
        state = (g[:, None, None] * state.astype(f32)
                 + phi_k[:, :, None] * v.astype(f32)[:, None, :]).astype(state.dtype)
        z = (g[:, None] * z.astype(f32) + phi_k).astype(z.dtype)
        return (num / (den[..., None] + eps)).astype(q.dtype), state, z


# ---------------------------------------------------------------------------
# the block and the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetentionAttention(LlamaAttention):
    """:class:`..llama.LlamaAttention`'s projections, rotary and output with
    retention between them: its scopes (``attn/qkv``, ``qk_norm``, ``rope``,
    ``o_proj``) plus ``attn/gate`` and ``attn/retention/{expand,chunk,step}``."""

    config: BrumbyConfig

    def init(self, key: jax.Array) -> Params:
        c = self.config
        params = super().init(key)
        for name in ("q_norm", "k_norm"):
            params[name] = {"scale": jnp.ones((c.head_dim,), jnp.float32)}
        params["gate"] = {"kernel": default_kernel_init(
            jax.random.fold_in(key, 1), (c.hidden_size, c.num_kv_heads), c.dtype)}
        return params

    def specs(self) -> Params:
        specs = super().specs()
        specs["q_norm"] = specs["k_norm"] = {"scale": P(None)}
        specs["gate"] = {"kernel": P(None, _head_axis(self.config.num_kv_heads))}
        return specs

    @jax.named_scope("qk_norm")
    def _qk_norm(self, params: Params, q: jax.Array, k: jax.Array):
        """RMSNorm of every head of q and of k on its own (the scale is one
        head wide, shared by the heads), fp32 accumulation: the per-head form
        beside OLMoE's joint one, under the same scope."""
        eps = self.config.rms_norm_eps

        def norm(x, scale):
            h = x.astype(jnp.float32)
            var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
            return (h * lax.rsqrt(var + eps) * scale).astype(x.dtype)

        return norm(q, params["q_norm"]["scale"]), norm(k, params["k_norm"]["scale"])

    def project(self, params: Params, h: jax.Array, sin, cos, pos_block):
        """h (b, t, H) -> q (b, t, K, G, d) and k (b, t, K, d) normed and
        rotated, v (b, t, K, d), log g (b, t, K) float32. ``sin``/``cos`` are
        tables indexed by ``pos_block`` (b, t)."""
        c = self.config
        b, t, _ = h.shape
        with jax.named_scope("qkv"):
            q, k, v = self._qkv()(params["qkv"], h)
            q = q.reshape(b, t, c.num_heads, c.head_dim)
            k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
            v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
        q, k = self._qk_norm(params, q, k)
        with jax.named_scope("rope"):
            q, k = self._apply_rope(q, k, sin, cos, pos_block)
        with jax.named_scope("gate"):
            log_g = jax.nn.log_sigmoid(
                jnp.einsum("bth,hk->btk", h, params["gate"]["kernel"],
                           preferred_element_type=jnp.float32))
        q = q.reshape(b, t, c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim)
        return q, k, v, log_g

    def output(self, params: Params, y: jax.Array) -> jax.Array:
        b, t = y.shape[:2]
        with jax.named_scope("o_proj"):
            return self._o()(params["o"], y.reshape(b, t, -1))

    @jax.named_scope("attn")
    def __call__(self, params, x, sin, cos, positions):
        """The whole sequence from the zero state, in chunks (training)."""
        c = self.config
        q, k, v, log_g = self.project(params, x, sin, cos, positions)
        dims = (c.num_kv_heads, c.feature_width)
        state = jnp.zeros(dims + (c.head_dim,), jnp.float32)
        z = jnp.zeros(dims, jnp.float32)
        with jax.named_scope("retention"):
            y = jax.vmap(
                lambda q, k, v, g: retention_chunks(
                    state, z, q, k, v, g, q.shape[0], c.retention_eps)[0]
            )(q, k, v, log_g)
        return self.output(params, y)


@dataclasses.dataclass(frozen=True)
class BrumbyDecoderLayer(LlamaDecoderLayer):
    config: BrumbyConfig

    def init(self, key: jax.Array) -> Params:
        ka, _ = jax.random.split(key)
        return {**super().init(key), "attn": RetentionAttention(self.config).init(ka)}

    def specs(self) -> Params:
        return {**super().specs(), "attn": RetentionAttention(self.config).specs()}

    def __call__(self, params, x, sin, cos, positions):
        h = self._norm()(params["attn_norm"], x)
        x = x + RetentionAttention(self.config)(params["attn"], h, sin, cos, positions)
        h = self._norm()(params["mlp_norm"], x)
        return x + LlamaMLP(self.config)(params["mlp"], h)


@dataclasses.dataclass(frozen=True)
class BrumbyForCausalLM(LlamaForCausalLM):
    """The Llama causal LM with retention layers: embed, head, final norm,
    layer scan, remat and the loss tail are the parent's."""

    config: BrumbyConfig

    def _layer(self) -> BrumbyDecoderLayer:
        return BrumbyDecoderLayer(self.config)


# ---------------------------------------------------------------------------
# HF checkpoint names
# ---------------------------------------------------------------------------

# Llama's names plus Qwen3's per-head ``q_norm`` / ``k_norm``; ``g_proj`` for
# the gate is this repo's reading (the catalog publishes no tensor names)
_EXTRA_HF = (
    (("q_norm", "scale"), "model.layers.{}.self_attn.q_norm.weight", False),
    (("k_norm", "scale"), "model.layers.{}.self_attn.k_norm.weight", False),
    (("gate", "kernel"), "model.layers.{}.self_attn.g_proj.weight", True),
)


def params_from_hf_brumby(state_dict: Dict[str, Any], config: BrumbyConfig) -> Params:
    """HF names -> the stacked pytree (torch Linear is (out, in); here (in, out))."""
    import numpy as np

    def t(name):
        w = state_dict[name]
        if hasattr(w, "detach"):
            w = w.detach().cpu().numpy()
        return np.asarray(w, dtype=np.float32)

    params = params_from_hf(state_dict, config)
    for (group, leaf), fmt, linear in _EXTRA_HF:
        stacked = np.stack([
            t(fmt.format(i)).T if linear else t(fmt.format(i)) for i in range(config.num_layers)
        ])
        params["layers"]["attn"][group] = {
            leaf: jnp.asarray(stacked, config.dtype if linear else jnp.float32)}
    return params


def params_to_hf_brumby(params: Params, config: BrumbyConfig) -> Dict[str, Any]:
    """Inverse of :func:`params_from_hf_brumby`."""
    import numpy as np

    out = params_to_hf(params, config)
    for (group, leaf), fmt, linear in _EXTRA_HF:
        stacked = np.asarray(params["layers"]["attn"][group][leaf], np.float32)
        for i in range(config.num_layers):
            out[fmt.format(i)] = stacked[i].T if linear else stacked[i]
    return out
