"""Sarvam MLA-MoE model family (``sarvamai/sarvam-105b``, HF ``model_type:
sarvam_mla``), TPU-native: the DeepSeek-V2/V3 block shape — multi-head latent
attention, a sparse feed-forward with a shared expert behind a few leading
dense layers, YaRN rotary tables.

What sets it apart from every other family here, each a fact of the *model*
config that the blocks read:

- **Latent attention (MLA).** A token's keys and values are one
  ``kv_lora_rank``-wide latent ``c`` (RMS-normed, learned scale) and one
  rotary key ``k_r`` of ``qk_rope_head_dim`` shared by all heads; per-head keys
  and values are up-projections of ``c``. What a cache holds is the row
  ``[c ‖ k_r]`` after the norm and the rotation — nothing by head. Attention
  has two forms that give the same numbers (:func:`latent_attention`): the
  *expanded* form up-projects the rows to per-head ``k``/``v`` (prefill), the
  *absorbed* form folds ``W_UK`` into the query and ``W_UV`` into the output,
  which makes it multi-query attention over the rows themselves (decode).
- **The router** (``MoEConfig.routing = "sigmoid_bias"``): sigmoid scores,
  the top-k of score + a learned selection bias, gates from the unbiased
  scores renormalised and scaled by ``routed_scaling_factor``
  (:func:`..moe.routing.sigmoid_bias_routing`); **one shared expert** beside
  the routed ones (``MoEConfig.shared_intermediate_size``).
- **Leading dense layers** (``first_k_dense``): the first layers carry a
  plain SwiGLU MLP of ``intermediate_size``; their weights are a stack of
  their own (``params["dense_layers"]``) ahead of the expert layers'
  (``params["layers"]``).
- **A held share of the experts** (``experts_held`` / ``first_held_expert``):
  one rank of an expert-parallel deployment run alone routes over all
  ``num_experts`` and computes the experts it holds.
- **YaRN** (``rope_scaling.type = deepseek_yarn``): :func:`yarn_rope`, and the
  softmax scale that goes with it (:meth:`SarvamConfig.softmax_scale`).
  Rotary dimensions pair as *halves* (rotate-half, as everywhere in this
  repo); a published checkpoint's neighbour-paired columns would be permuted
  on load. No HF name map is given: the catalog publishes no tensor names.
- **The query** is one matrix here (``q_lora_rank = None``: sarvam-105b
  publishes none). A family whose query goes through a latent of its own
  (``W_DQ``, an RMSNorm with a learned scale, ``W_UQ``: :mod:`.xing`) sets
  ``q_lora_rank`` and shares everything else in :class:`LatentAttention`.

The training-side model (:class:`SarvamForCausalLM`) makes the weights and
runs the expanded form; the paged serving engines run
:class:`..inference.model.SarvamDecode`. Tensor parallelism of the latent
block and training at scale are not worked out (weights are replicated by
their specs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaForCausalLM,
    LlamaMLP,
    RMSNorm,
    apply_rope,
    make_norm,
)
from neuronx_distributed_llama3_2_tpu.models.mixtral import MixtralConfig
from neuronx_distributed_llama3_2_tpu.moe.loss import load_balancing_loss
from neuronx_distributed_llama3_2_tpu.moe.model import MoE, MoEConfig
from neuronx_distributed_llama3_2_tpu.parallel.layers import default_kernel_init

Params = Dict[str, Any]

# prefill attention runs over query blocks of at most this many rows, so that
# no program holds a (heads, S, S) score tensor: its largest is
# (heads, QUERY_BLOCK, S) in float32
QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class SarvamConfig(MixtralConfig):
    """The published keys of ``sarvam_mla`` on top of the shared Llama/MoE
    fields: ``intermediate_size`` is the dense layers' width, ``head_dim`` the
    query/key head (``qk_nope_head_dim + qk_rope_head_dim``)."""

    kv_lora_rank: int = 512
    # None: the query is one matrix (sarvam); a width: it goes through a
    # normed latent of that width (W_DQ, RMSNorm, W_UQ — models/xing.py)
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # deepseek_yarn: (factor, original_max_position, beta_fast, beta_slow,
    # mscale, mscale_all_dim); None = plain rotary tables
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    first_k_dense: int = 1
    routed_scaling_factor: float = 2.5
    experts_held: Optional[int] = None
    first_held_expert: int = 0
    routing: str = "sigmoid_bias"
    num_experts: int = 128
    top_k: int = 8
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.qk_nope_head_dim + self.qk_rope_head_dim
            )
        super().__post_init__()
        if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("head_dim is qk_nope_head_dim + qk_rope_head_dim")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("need 0 <= first_k_dense < num_layers")

    @property
    def cache_row_width(self) -> int:
        """Values a token leaves in the cache, a layer: ``[c ‖ k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def softmax_scale(self) -> float:
        """``head_dim^-1/2``, times YaRN's ``m(mscale_all_dim)²``."""
        scale = self.head_dim ** -0.5
        if self.yarn is not None:
            scale *= yarn_mscale(self.yarn[0], self.yarn[5]) ** 2
        return scale

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            num_experts=self.num_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            routing=self.routing,
            normalize_top_k=self.normalize_top_k,
            routed_scale=self.routed_scaling_factor,
            shared_intermediate_size=self.num_shared_experts * self.moe_intermediate_size,
            experts_held=self.experts_held,
            first_held=self.first_held_expert,
            dtype=self.dtype,
        )


SARVAM_CONFIGS: Dict[str, SarvamConfig] = {
    # sarvamai/sarvam-105b config.json values
    "sarvam-105b": SarvamConfig(
        vocab_size=262144, hidden_size=4096, intermediate_size=16384,
        num_layers=32, num_heads=64, num_kv_heads=64, max_seq_len=131072,
        rope_theta=10000.0, yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0),
    ),
    # 1 dense + 2 expert layers, 8 experts top-2 + shared: T tokens sit
    # below, at and above T * k = E as tiny-olmoe's do
    "tiny-sarvam": SarvamConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=4, num_kv_heads=4, max_seq_len=128,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, num_experts=8, top_k=2,
        rope_theta=10000.0, yarn=(4.0, 32, 32.0, 1.0, 1.0, 1.0),
        dtype=jnp.float32, remat="none",
    ),
}


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(a) = 0.1 · a · ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope(
    rotary_dim: int, max_seq_len: int, theta: float,
    yarn: Optional[Tuple[float, int, float, float, float, float]],
) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) tables (max_seq_len, rotary_dim), fp32, rotate-half layout,
    under ``deepseek_yarn``: each frequency is blended between ``θ_i`` and
    ``θ_i / factor`` by a linear ramp over the dimensions between the one that
    turns ``beta_fast`` times in the original context (floor) and the one that
    turns ``beta_slow`` times (ceil); the tables are scaled by
    ``m(mscale) / m(mscale_all_dim)``."""
    exponent = jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim
    inv_freq = 1.0 / (theta ** exponent)
    table_scale = 1.0
    if yarn is not None:
        factor, original, beta_fast, beta_slow, mscale, mscale_all_dim = yarn

        def turns_dim(turns: float) -> float:
            return rotary_dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(turns_dim(beta_fast)), 0)
        high = min(math.ceil(turns_dim(beta_slow)), rotary_dim - 1)
        ramp = jnp.clip(
            (jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
            0.0, 1.0,
        )
        inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
        table_scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    freqs = jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb) * table_scale, jnp.cos(emb) * table_scale


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def absorbed_is_cheaper(config: SarvamConfig, t: int) -> bool:
    """Whether ``t`` queries against cached rows cost fewer FLOPs absorbed
    than expanded. A (query, row, head) triple costs ``2·(2r + d_r)`` absorbed
    (scores over the 576-wide row, values over its 512) and
    ``2·(d_n + d_r + d_v)`` expanded, which also up-projects every row once:
    ``2·r·(d_n + d_v) / t`` a triple. At the published widths the forms cross
    at t ≈ 171: decode and a 128-row chunk run absorbed, a 512-row chunk
    expanded."""
    c = config
    absorbed = 2 * c.kv_lora_rank + c.qk_rope_head_dim
    expanded = c.head_dim + c.v_head_dim + c.kv_lora_rank * (c.qk_nope_head_dim + c.v_head_dim) / t
    return absorbed < expanded


def _blocked_softmax_attention(q, keys, values, pos_block, scale, einsums):
    """softmax(scale · q·keys, rows ``j <= pos_block``) · values over query
    blocks of at most QUERY_BLOCK rows. q (b, t, N, ·); ``einsums`` are the
    score and the value contraction (per-head or multi-query keys)."""
    b, t = q.shape[:2]
    s = keys.shape[1]
    j = lax.iota(jnp.int32, s)[None, None, None, :]

    def attend(qb, pb):
        scores = (jnp.einsum(einsums[0], qb, keys) * scale).astype(jnp.float32)
        scores = jnp.where(j <= pb[:, None, :, None], scores, jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum(einsums[1], probs, values)

    if t <= QUERY_BLOCK:
        return attend(q, pos_block)
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(pos_block, ((0, 0), (0, pad)))
    qp = qp.reshape(b, blocks, QUERY_BLOCK, *q.shape[2:]).swapaxes(0, 1)
    pp = pp.reshape(b, blocks, QUERY_BLOCK).swapaxes(0, 1)
    out = lax.map(lambda xs: attend(*xs), (qp, pp))      # (blocks, b, QB, N, ·)
    return out.swapaxes(0, 1).reshape(b, blocks * QUERY_BLOCK, *out.shape[3:])[:, :t]


@jax.named_scope("absorb")
def absorb_query(config: SarvamConfig, kv_b: jax.Array, q: jax.Array) -> jax.Array:
    """``W_UK`` folded into q (b, t, N, d_n + d_r): ``[q·W_UK ‖ q_rope]``
    (b, t, N, r + d_r), which scores a cache row whole."""
    dn = config.qk_nope_head_dim
    q_lat = jnp.einsum("btnd,rnd->btnr", q[..., :dn], kv_b[..., :dn])
    return jnp.concatenate([q_lat, q[..., dn:]], axis=-1)


@jax.named_scope("absorb")
def absorb_output(config: SarvamConfig, kv_b: jax.Array, o_lat: jax.Array) -> jax.Array:
    """``W_UV`` over o_lat (b, t, N, r), the probabilities' sum of the rows'
    latents: the block's (b, t, N, d_v)."""
    return jnp.einsum("btnr,rnd->btnd", o_lat, kv_b[..., config.qk_nope_head_dim:])


def latent_attention(
    config: SarvamConfig, kv_b: jax.Array, q: jax.Array, rows: jax.Array,
    pos_block: jax.Array, *, absorbed: bool,
) -> jax.Array:
    """Attention of q (b, t, N, d_n + d_r) — its rotary part already rotated
    — over cache rows (b, S, r + d_r) = ``[c ‖ k_r]``, query ``i`` seeing the
    rows ``j <= pos_block[·, i]``; ``kv_b`` (r, N, d_n + d_v) is ``W_UKV``.
    Returns (b, t, N, d_v). Softmax in float32, as ``core_attention``. The
    scopes: ``latent_up`` (``W_UKV`` over the rows), ``absorb`` (``W_UK`` and
    ``W_UV`` folded into q and out), ``sdpa``."""
    c = config
    r, dn = c.kv_lora_rank, c.qk_nope_head_dim
    scale = c.softmax_scale()
    rows = rows.astype(q.dtype)
    if absorbed:
        q_abs = absorb_query(c, kv_b, q)
        with jax.named_scope("sdpa"):
            o_lat = _blocked_softmax_attention(
                q_abs, rows, rows[..., :r], pos_block, scale,
                ("btnd,bsd->bnts", "bnts,bsr->btnr"),
            )
        return absorb_output(c, kv_b, o_lat)
    with jax.named_scope("latent_up"):
        kv = jnp.einsum("bsr,rnd->bsnd", rows[..., :r], kv_b)           # (b,S,N,d_n+d_v)
        k_rope = jnp.broadcast_to(
            rows[:, :, None, r:], rows.shape[:2] + (c.num_heads, c.qk_rope_head_dim)
        )
        keys = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)         # (b,S,N,d_n+d_r)
    with jax.named_scope("sdpa"):
        return _blocked_softmax_attention(
            q, keys, kv[..., dn:], pos_block, scale,
            ("btnd,bsnd->bnts", "bnts,bsnd->btnd"),
        )


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The MLA block beside :class:`..llama.LlamaAttention`: same scopes
    (``attn/qkv``, ``rope``, ``sdpa``, ``o_proj``) plus ``latent_down``,
    ``latent_up`` and ``absorb``. The query is one projection where the
    config has no ``q_lora_rank`` (sarvam: ``params["q"]``); with one (xing)
    it is ``q_b · RMSNorm(q_a · h)`` — the down-projection and its norm under
    ``q_latent``, the up-projection under ``qkv`` — and both the expanded and
    the absorbed form take that query as they take sarvam's."""

    config: SarvamConfig

    def init(self, key: jax.Array) -> Params:
        c = self.config
        kq, ka, kb, ko = jax.random.split(key, 4)
        n, r = c.num_heads, c.kv_lora_rank
        return {
            **self._init_query(kq),
            "kv_a": {"kernel": default_kernel_init(ka, (c.hidden_size, c.cache_row_width), c.dtype)},
            "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
            "kv_b": {"kernel": default_kernel_init(
                kb, (r, n, c.qk_nope_head_dim + c.v_head_dim), c.dtype)},
            "o": {"kernel": default_kernel_init(ko, (n * c.v_head_dim, c.hidden_size), c.dtype)},
        }

    def _init_query(self, key: jax.Array) -> Params:
        c = self.config
        width = c.num_heads * c.head_dim
        if c.q_lora_rank is None:
            return {"q": {"kernel": default_kernel_init(key, (c.hidden_size, width), c.dtype)}}
        ka, kb = jax.random.split(key)
        return {
            "q_a": {"kernel": default_kernel_init(ka, (c.hidden_size, c.q_lora_rank), c.dtype)},
            "q_norm": {"scale": jnp.ones((c.q_lora_rank,), jnp.float32)},
            "q_b": {"kernel": default_kernel_init(kb, (c.q_lora_rank, width), c.dtype)},
        }

    def specs(self) -> Params:
        query = (
            {"q": {"kernel": P(None, None)}} if self.config.q_lora_rank is None
            else {"q_a": {"kernel": P(None, None)}, "q_norm": {"scale": P(None)},
                  "q_b": {"kernel": P(None, None)}}
        )
        return {
            **query, "kv_a": {"kernel": P(None, None)},
            "kv_norm": {"scale": P(None)}, "kv_b": {"kernel": P(None, None, None)},
            "o": {"kernel": P(None, None)},
        }

    def project(self, params: Params, h: jax.Array, sin, cos, pos_block):
        """The block's projections of h (b, t, H): q (b, t, N, d_n + d_r)
        with its rotary part rotated, and the cache rows (b, t, r + d_r) =
        ``[RMSNorm(c) ‖ RoPE(k_r)]``."""
        c = self.config
        b, t, _ = h.shape
        r, dn = c.kv_lora_rank, c.qk_nope_head_dim
        if c.q_lora_rank is not None:
            with jax.named_scope("q_latent"):
                h_q = RMSNorm(c.q_lora_rank, c.rms_norm_eps, c.dtype)(
                    params["q_norm"], h @ params["q_a"]["kernel"])
            q_kernel = params["q_b"]["kernel"]
        else:
            h_q, q_kernel = h, params["q"]["kernel"]
        with jax.named_scope("qkv"):
            q = (h_q @ q_kernel).reshape(b, t, c.num_heads, c.head_dim)
        with jax.named_scope("latent_down"):
            ckr = h @ params["kv_a"]["kernel"]
            latent = RMSNorm(r, c.rms_norm_eps, c.dtype)(params["kv_norm"], ckr[..., :r])
        with jax.named_scope("rope"):
            q = jnp.concatenate(
                [q[..., :dn], apply_rope(q[..., dn:], sin, cos, pos_block)], axis=-1
            )
            k_rope = apply_rope(ckr[..., None, r:], sin, cos, pos_block)[:, :, 0]
        return q, jnp.concatenate([latent, k_rope.astype(latent.dtype)], axis=-1)

    def output(self, params: Params, att: jax.Array) -> jax.Array:
        b, t = att.shape[:2]
        with jax.named_scope("o_proj"):
            return att.reshape(b, t, -1) @ params["o"]["kernel"]

    @jax.named_scope("attn")
    def __call__(self, params, x, sin, cos, positions, *, absorbed: bool = False):
        q, rows = self.project(params, x, sin, cos, positions)
        att = latent_attention(
            self.config, params["kv_b"]["kernel"], q, rows, positions, absorbed=absorbed
        )
        return self.output(params, att)


# ---------------------------------------------------------------------------
# layers and the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SarvamDecoderLayer:
    """Pre-norm block: latent attention, then the dense SwiGLU MLP
    (``sparse=False``, a leading layer) or the expert block."""

    config: SarvamConfig
    sparse: bool = True

    def _ffn(self):
        return MoE(self.config.moe_config()) if self.sparse else LlamaMLP(self.config)

    def _name(self) -> str:
        return "moe" if self.sparse else "mlp"

    def init(self, key: jax.Array) -> Params:
        ka, km = jax.random.split(key)
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.init(key),
            "attn": LatentAttention(self.config).init(ka),
            "mlp_norm": norm.init(key),
            self._name(): self._ffn().init(km),
        }

    def specs(self) -> Params:
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.specs(),
            "attn": LatentAttention(self.config).specs(),
            "mlp_norm": norm.specs(),
            self._name(): self._ffn().specs(),
        }

    def attention(self, params, x, sin, cos, positions):
        """The attention sub-layer ``F(x)``, its norm inside."""
        c = self.config
        return LatentAttention(c)(
            params["attn"], make_norm(c)(params["attn_norm"], x), sin, cos, positions)

    def feed_forward(self, params, x):
        """The feed-forward sub-layer ``F(x)``, its norm inside: (y, aux)."""
        c = self.config
        h = make_norm(c)(params["mlp_norm"], x)
        if not self.sparse:
            return LlamaMLP(c)(params["mlp"], h), jnp.zeros((), jnp.float32)
        y, router_logits, idx = self._ffn()(params["moe"], h)
        return y, load_balancing_loss(router_logits, idx, c.num_experts)

    def __call__(self, params, x, sin, cos, positions):
        """Returns (x, aux): aux is the layer's load-balancing loss, 0 for a
        dense layer."""
        x = x + self.attention(params, x, sin, cos, positions)
        y, aux = self.feed_forward(params, x)
        return x + y, aux


@dataclasses.dataclass(frozen=True)
class SarvamForCausalLM:
    """Same protocol as :class:`..mixtral.MixtralForCausalLM`
    (init/specs/__call__/loss); ``params["dense_layers"]`` stacks the
    ``first_k_dense`` leading layers, ``params["layers"]`` the expert layers."""

    config: SarvamConfig

    def _llama(self) -> LlamaForCausalLM:
        return LlamaForCausalLM(self.config)     # embed / head / final norm / loss tail

    def _layer(self, sparse: bool):
        return SarvamDecoderLayer(self.config, sparse=sparse)

    def _stacks(self):
        c = self.config
        return (
            ("dense_layers", self._layer(False), c.first_k_dense),
            ("layers", self._layer(True), c.num_layers - c.first_k_dense),
        )

    def _enter(self, x: jax.Array) -> jax.Array:
        """What the layer stacks carry, from the embedding (b, s, H)."""
        return x

    def _leave(self, x: jax.Array) -> jax.Array:
        """What the final norm reads, from the stacks' carry."""
        return x

    def _embed(self):
        return self._llama()._embed()

    def _norm(self):
        return self._llama()._norm()

    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        return self._llama()._logits(params, hidden)

    def _rope(self, s: int):
        c = self.config
        return yarn_rope(c.qk_rope_head_dim, s, c.rope_theta, c.yarn)

    def init(self, key: jax.Array) -> Params:
        c = self.config
        ke, kl, kh = jax.random.split(key, 3)
        params = {"embed": self._embed().init(ke), "final_norm": self._norm().init(kh)}
        for i, (name, layer, count) in enumerate(self._stacks()):
            if count:
                keys = jax.random.split(jax.random.fold_in(kl, i), count)
                params[name] = jax.vmap(layer.init)(keys)
        if not c.tie_word_embeddings:
            params["lm_head"] = self._llama()._lm_head().init(kh)
        return params

    def specs(self) -> Params:
        specs = {"embed": self._embed().specs(), "final_norm": self._norm().specs()}
        for name, layer, count in self._stacks():
            if count:
                specs[name] = jax.tree.map(
                    lambda s: P(None, *s), layer.specs(), is_leaf=lambda s: isinstance(s, P)
                )
        if not self.config.tie_word_embeddings:
            specs["lm_head"] = self._llama()._lm_head().specs()
        return specs

    def _backbone(self, params: Params, input_ids: jax.Array):
        """Embed + the two layer stacks + final norm: (hidden, mean aux loss
        of the expert layers)."""
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        sin, cos = self._rope(s)
        x = self._enter(self._embed()(params["embed"], input_ids))
        aux = jnp.zeros((), jnp.float32)
        for name, layer, count in self._stacks():
            if count:
                x, auxes = lax.scan(
                    lambda x, lp, layer=layer: layer(lp, x, sin, cos, positions), x, params[name]
                )
                if layer.sparse:
                    aux = jnp.mean(auxes)
        return self._norm()(params["final_norm"], self._leave(x)), aux

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        return self._logits(params, self._backbone(params, input_ids)[0])

    def loss_from_hidden(self, params, hidden, labels):
        return self._llama().loss_from_hidden(params, hidden, labels)

    def loss(self, params: Params, input_ids: jax.Array, labels: jax.Array) -> jax.Array:
        hidden, aux = self._backbone(params, input_ids)
        return self.loss_from_hidden(params, hidden, labels) + self.config.router_aux_loss_coef * aux
