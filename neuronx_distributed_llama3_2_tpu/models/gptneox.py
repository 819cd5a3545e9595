"""GPT-NeoX family (GPT-NeoX-20B, Pythia, CodeGen), TPU-native.

Counterpart of the reference's GPT-NeoX 6.9B/20B and CodeGen2.5 7B training
examples (SURVEY.md §2.8 "other training examples": examples/training/
gpt_neox_* and codegen25 pretraining, ~4K LoC of per-model copies). Instead of
per-model forks, one block family covers the whole parallel-residual lineage
via config:

- ``parallel_residual``: x + attn(ln1(x)) + mlp(ln2(x)) (GPT-NeoX
  ``use_parallel_residual``; sequential Pythia-style otherwise)
- ``shared_layernorm``: CodeGen/GPT-J single ln per block (mlp reads ln1's
  output)
- ``rotary_pct`` / ``rotary_interleaved``: partial-rotary on the first
  ``head_dim·pct`` dims; NeoX uses the rotate-half convention, CodeGen the
  GPT-J interleaved (rotate-every-two) convention
- biases on qkv / attn-out / mlp / lm-head per family

Everything else (TP/SP sharding, flash attention, context parallelism, remat,
scan-over-layers, vocab-parallel CE, trainer/checkpoint/pipeline protocols)
is inherited from the Llama machinery.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    LlamaForCausalLM,
    apply_rope,
    make_norm,
    precompute_rope,
)
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig(LlamaConfig):
    """LlamaConfig + parallel-residual-family knobs (HF GPTNeoXConfig /
    CodeGenConfig fields)."""

    norm_type: str = "layernorm"
    norm_bias: bool = True
    tie_word_embeddings: bool = False
    rotary_pct: float = 0.25
    rotary_interleaved: bool = False  # True = GPT-J/CodeGen convention
    parallel_residual: bool = True
    shared_layernorm: bool = False  # True = CodeGen single ln per block
    activation: str = "gelu"  # "gelu" (exact) | "gelu_new" (tanh approx)
    qkv_bias: bool = True
    attn_out_bias: bool = True
    mlp_bias: bool = True
    lm_head_bias: bool = False

    @property
    def rotary_dims(self) -> int:
        d = int(self.head_dim * self.rotary_pct)
        return d - d % 2

    def __post_init__(self):
        super().__post_init__()
        if self.activation not in ("gelu", "gelu_new"):
            raise ValueError(
                f"activation must be gelu|gelu_new, got {self.activation!r}"
            )
        if self.shared_layernorm and not self.parallel_residual:
            raise ValueError(
                "shared_layernorm=True requires parallel_residual=True: the "
                "sequential-residual path needs a post-attention norm "
                "(mlp_norm) that a shared-ln block does not have"
            )
        if self.rope_scaling is not None:
            raise ValueError(
                "rope_scaling is not supported for the GPT-NeoX/CodeGen "
                "family (partial rotary uses plain inverse-frequency tables)"
            )


GPTNEOX_CONFIGS: Dict[str, GPTNeoXConfig] = {
    # EleutherAI/gpt-neox-20b config.json
    "gpt-neox-20b": GPTNeoXConfig(
        vocab_size=50432, hidden_size=6144, intermediate_size=24576,
        num_layers=44, num_heads=64, num_kv_heads=64, head_dim=96,
        max_seq_len=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        rotary_pct=0.25,
    ),
    # EleutherAI/pythia-6.9b config.json
    "pythia-6.9b": GPTNeoXConfig(
        vocab_size=50432, hidden_size=4096, intermediate_size=16384,
        num_layers=32, num_heads=32, num_kv_heads=32, head_dim=128,
        max_seq_len=2048, rope_theta=10000.0, rotary_pct=0.25,
    ),
    # Salesforce/codegen25-7b config.json (CodeGen architecture)
    "codegen25-7b": GPTNeoXConfig(
        vocab_size=51200, hidden_size=4096, intermediate_size=16384,
        num_layers=32, num_heads=32, num_kv_heads=32, head_dim=128,
        max_seq_len=2048, rope_theta=10000.0,
        rotary_pct=64 / 128, rotary_interleaved=True,
        shared_layernorm=True, activation="gelu_new",
        qkv_bias=False, attn_out_bias=False, lm_head_bias=True,
    ),
    "tiny-neox": GPTNeoXConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=8, num_kv_heads=8, head_dim=8,
        max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32,
        remat="none", rotary_pct=0.25,
    ),
    "tiny-codegen": GPTNeoXConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=8, num_kv_heads=8, head_dim=8,
        max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32,
        remat="none", rotary_pct=0.5, rotary_interleaved=True,
        shared_layernorm=True, activation="gelu_new",
        qkv_bias=False, attn_out_bias=False, lm_head_bias=True,
    ),
}


def apply_rope_interleaved(
    x: jax.Array, sin: jax.Array, cos: jax.Array, positions: jax.Array
) -> jax.Array:
    """GPT-J/CodeGen rotary: sin/cos interleave every two lanes
    (reference-of-record: HF ``rotate_every_two`` + repeat_interleave(2)).
    ``sin``/``cos`` are the (S, D) rotate-half tables — the first D/2
    columns hold the per-frequency values, so take those and interleave."""
    d = x.shape[-1]
    half = sin[:, : d // 2]  # (S, D/2) frequency-major
    halfc = cos[:, : d // 2]
    sin_i = jnp.repeat(half, 2, axis=-1)  # (S, D) interleaved
    cos_i = jnp.repeat(halfc, 2, axis=-1)
    sin_i = jnp.take(sin_i, positions, axis=0)[:, :, None, :]
    cos_i = jnp.take(cos_i, positions, axis=0)[:, :, None, :]
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    rotated = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)
    out = x.astype(jnp.float32) * cos_i + rotated.astype(jnp.float32) * sin_i
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class GPTNeoXAttention(LlamaAttention):
    """Llama attention machinery (fused TP QKV, flash/CP dispatch, remat
    names) with partial rotary and per-family biases."""

    config: GPTNeoXConfig

    def _qkv(self):
        base = super()._qkv()
        return dataclasses.replace(base, use_bias=self.config.qkv_bias)

    def _o(self):
        base = super()._o()
        return dataclasses.replace(base, use_bias=self.config.attn_out_bias)

    def _apply_rope(self, q, k, sin, cos, positions):
        c = self.config
        rot = c.rotary_dims
        fn = apply_rope_interleaved if c.rotary_interleaved else apply_rope
        q_rot = fn(q[..., :rot], sin, cos, positions)
        k_rot = fn(k[..., :rot], sin, cos, positions)
        q = jnp.concatenate([q_rot, q[..., rot:]], axis=-1)
        k = jnp.concatenate([k_rot, k[..., rot:]], axis=-1)
        return q, k


@dataclasses.dataclass(frozen=True)
class GPTNeoXMLP:
    """h → I → h with gelu and optional biases (HF GPTNeoXMLP / CodeGenMLP)."""

    config: GPTNeoXConfig
    # trace layout depends on global parallel state (shardlint SL002); safe
    # because initialize/destroy_model_parallel clear the jit cache
    __layout_deps__ = ("sequence_parallel_enabled",)

    def _up(self) -> ColumnParallelLinear:
        c = self.config
        return ColumnParallelLinear(
            in_features=c.hidden_size, out_features=c.intermediate_size,
            use_bias=c.mlp_bias, dtype=c.dtype,
        )

    def _down(self) -> RowParallelLinear:
        c = self.config
        from neuronx_distributed_llama3_2_tpu.parallel import (
            state as parallel_state,
        )

        return RowParallelLinear(
            in_features=c.intermediate_size, out_features=c.hidden_size,
            use_bias=c.mlp_bias,
            sequence_parallel=parallel_state.sequence_parallel_enabled(),
            dtype=c.dtype,
        )

    def init(self, key: jax.Array) -> Params:
        ku, kd = jax.random.split(key)
        return {"up": self._up().init(ku), "down": self._down().init(kd)}

    def specs(self) -> Params:
        return {"up": self._up().specs(), "down": self._down().specs()}

    @jax.named_scope("mlp")
    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        h = self._up()(params["up"], x)
        h = jax.nn.gelu(
            h.astype(jnp.float32),
            approximate=self.config.activation == "gelu_new",
        ).astype(self.config.dtype)
        return self._down()(params["down"], h)


@dataclasses.dataclass(frozen=True)
class GPTNeoXDecoderLayer:
    config: GPTNeoXConfig

    def _norm(self):
        return make_norm(self.config)

    def init(self, key: jax.Array) -> Params:
        ka, km = jax.random.split(key)
        p = {
            "attn_norm": self._norm().init(key),
            "attn": GPTNeoXAttention(self.config).init(ka),
            "mlp": GPTNeoXMLP(self.config).init(km),
        }
        if not self.config.shared_layernorm:
            p["mlp_norm"] = self._norm().init(key)
        return p

    def specs(self) -> Params:
        s = {
            "attn_norm": self._norm().specs(),
            "attn": GPTNeoXAttention(self.config).specs(),
            "mlp": GPTNeoXMLP(self.config).specs(),
        }
        if not self.config.shared_layernorm:
            s["mlp_norm"] = self._norm().specs()
        return s

    def __call__(self, params, x, sin, cos, positions):
        c = self.config
        norm = self._norm()
        h1 = norm(params["attn_norm"], x)
        attn_out = GPTNeoXAttention(c)(params["attn"], h1, sin, cos, positions)
        mlp = GPTNeoXMLP(c)
        if c.parallel_residual:
            h2 = h1 if c.shared_layernorm else norm(params["mlp_norm"], x)
            return x + attn_out + mlp(params["mlp"], h2)
        x = x + attn_out
        h2 = norm(params["mlp_norm"], x)
        return x + mlp(params["mlp"], h2)


@dataclasses.dataclass(frozen=True)
class GPTNeoXForCausalLM(LlamaForCausalLM):
    """Same model protocol as LlamaForCausalLM (init/specs/__call__/loss),
    so the trainer, ZeRO-1, checkpointing and pipeline wrappers work
    unchanged."""

    config: GPTNeoXConfig

    def _layer(self):
        return GPTNeoXDecoderLayer(self.config)

    def _lm_head(self) -> ColumnParallelLinear:
        base = super()._lm_head()
        return dataclasses.replace(base, use_bias=self.config.lm_head_bias)

    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        if self.config.lm_head_bias:
            with jax.named_scope("lm_head"):
                return self._lm_head()(params["lm_head"], hidden)
        return super()._logits(params, hidden)

    def _rope(self, s: int):
        c = self.config
        return precompute_rope(c.rotary_dims, s, c.rope_theta, None)


# ---------------------------------------------------------------------------
# HF converters
# ---------------------------------------------------------------------------

def _np(w) -> np.ndarray:
    if hasattr(w, "detach"):
        w = w.detach().cpu().numpy()
    return np.asarray(w, dtype=np.float32)


def params_from_hf_neox(state_dict: Dict[str, Any], config: GPTNeoXConfig) -> Params:
    """HF GPT-NeoX → stacked pytree. HF fuses QKV per head: ``view(...,
    heads, 3·head_dim)`` then chunk, so head n's q rows are
    ``n·3d .. n·3d+d`` (likewise k, v)."""
    c = config
    L, n, hd = c.num_layers, c.num_heads, c.head_dim

    def qkv_rows(comp: int) -> np.ndarray:
        # row indices of component comp (0=q,1=k,2=v), head-major
        return (
            np.arange(n)[:, None] * 3 * hd + comp * hd + np.arange(hd)[None, :]
        ).reshape(-1)

    qs, ks, vs, qb, kb, vb = [], [], [], [], [], []
    os_, ob, n1w, n1b, n2w, n2b, upw, upb, dnw, dnb = ([] for _ in range(10))
    for i in range(L):
        pre = f"gpt_neox.layers.{i}"
        w = _np(state_dict[f"{pre}.attention.query_key_value.weight"])
        b = _np(state_dict[f"{pre}.attention.query_key_value.bias"])
        qs.append(w[qkv_rows(0)].T)
        ks.append(w[qkv_rows(1)].T)
        vs.append(w[qkv_rows(2)].T)
        qb.append(b[qkv_rows(0)])
        kb.append(b[qkv_rows(1)])
        vb.append(b[qkv_rows(2)])
        os_.append(_np(state_dict[f"{pre}.attention.dense.weight"]).T)
        ob.append(_np(state_dict[f"{pre}.attention.dense.bias"]))
        n1w.append(_np(state_dict[f"{pre}.input_layernorm.weight"]))
        n1b.append(_np(state_dict[f"{pre}.input_layernorm.bias"]))
        n2w.append(_np(state_dict[f"{pre}.post_attention_layernorm.weight"]))
        n2b.append(_np(state_dict[f"{pre}.post_attention_layernorm.bias"]))
        upw.append(_np(state_dict[f"{pre}.mlp.dense_h_to_4h.weight"]).T)
        upb.append(_np(state_dict[f"{pre}.mlp.dense_h_to_4h.bias"]))
        dnw.append(_np(state_dict[f"{pre}.mlp.dense_4h_to_h.weight"]).T)
        dnb.append(_np(state_dict[f"{pre}.mlp.dense_4h_to_h.bias"]))

    dt = c.dtype
    f32 = jnp.float32
    st = lambda xs, dtype=None: jnp.asarray(np.stack(xs), dtype or dt)  # noqa: E731
    return {
        "embed": {"embedding": jnp.asarray(_np(state_dict["gpt_neox.embed_in.weight"]), dt)},
        "layers": {
            "attn_norm": {"scale": st(n1w, f32), "bias": st(n1b, f32)},
            "attn": {
                "qkv": {
                    "q_kernel": st(qs), "k_kernel": st(ks), "v_kernel": st(vs),
                    "q_bias": st(qb), "k_bias": st(kb), "v_bias": st(vb),
                },
                "o": {"kernel": st(os_), "bias": st(ob)},
            },
            "mlp_norm": {"scale": st(n2w, f32), "bias": st(n2b, f32)},
            "mlp": {
                "up": {"kernel": st(upw), "bias": st(upb)},
                "down": {"kernel": st(dnw), "bias": st(dnb)},
            },
        },
        "final_norm": {
            "scale": jnp.asarray(_np(state_dict["gpt_neox.final_layer_norm.weight"]), f32),
            "bias": jnp.asarray(_np(state_dict["gpt_neox.final_layer_norm.bias"]), f32),
        },
        "lm_head": {"kernel": jnp.asarray(_np(state_dict["embed_out.weight"]).T, dt)},
    }


def params_from_hf_codegen(
    state_dict: Dict[str, Any], config: GPTNeoXConfig, mp_num: int = 4
) -> Params:
    """HF CodeGen → stacked pytree. CodeGen's fused qkv_proj uses a
    TPU-v4-era blocked layout: output split into ``mp_num`` blocks, each
    holding [query; value; key] (in that order) for ``heads/mp_num`` heads —
    rows are mapped back to head-major q/k/v here."""
    c = config
    L, n, hd = c.num_layers, c.num_heads, c.head_dim
    h3 = 3 * n * hd
    local = n * hd // mp_num

    idx = np.arange(h3).reshape(mp_num, 3 * local)
    # HF split order is (query, value, key), neuron_modeling-independent
    q_i, v_i, k_i = np.split(idx, 3, axis=1)

    def rows(block: np.ndarray) -> np.ndarray:
        # (mp, local) -> (mp, n/mp, hd) -> head-major flat rows
        return block.reshape(mp_num, n // mp_num, hd).reshape(-1)

    qs, ks, vs, os_, n1w, n1b, upw, upb, dnw, dnb = ([] for _ in range(10))
    for i in range(L):
        pre = f"transformer.h.{i}"
        w = _np(state_dict[f"{pre}.attn.qkv_proj.weight"])
        qs.append(w[rows(q_i)].T)
        ks.append(w[rows(k_i)].T)
        vs.append(w[rows(v_i)].T)
        os_.append(_np(state_dict[f"{pre}.attn.out_proj.weight"]).T)
        n1w.append(_np(state_dict[f"{pre}.ln_1.weight"]))
        n1b.append(_np(state_dict[f"{pre}.ln_1.bias"]))
        upw.append(_np(state_dict[f"{pre}.mlp.fc_in.weight"]).T)
        upb.append(_np(state_dict[f"{pre}.mlp.fc_in.bias"]))
        dnw.append(_np(state_dict[f"{pre}.mlp.fc_out.weight"]).T)
        dnb.append(_np(state_dict[f"{pre}.mlp.fc_out.bias"]))

    dt = c.dtype
    f32 = jnp.float32
    st = lambda xs, dtype=None: jnp.asarray(np.stack(xs), dtype or dt)  # noqa: E731
    return {
        "embed": {
            "embedding": jnp.asarray(_np(state_dict["transformer.wte.weight"]), dt)
        },
        "layers": {
            "attn_norm": {"scale": st(n1w, f32), "bias": st(n1b, f32)},
            "attn": {
                "qkv": {"q_kernel": st(qs), "k_kernel": st(ks), "v_kernel": st(vs)},
                "o": {"kernel": st(os_)},
            },
            "mlp": {
                "up": {"kernel": st(upw), "bias": st(upb)},
                "down": {"kernel": st(dnw), "bias": st(dnb)},
            },
        },
        "final_norm": {
            "scale": jnp.asarray(_np(state_dict["transformer.ln_f.weight"]), f32),
            "bias": jnp.asarray(_np(state_dict["transformer.ln_f.bias"]), f32),
        },
        "lm_head": {
            "kernel": jnp.asarray(_np(state_dict["lm_head.weight"]).T, dt),
            "bias": jnp.asarray(_np(state_dict["lm_head.bias"]), dt),
        },
    }


def params_to_hf_neox(params: Params, config: GPTNeoXConfig) -> Dict[str, Any]:
    """Inverse of :func:`params_from_hf_neox`: stacked pytree → HF GPT-NeoX
    ``state_dict``, re-fusing q/k/v into HF's per-head-interleaved
    ``query_key_value`` rows (head n holds rows [q; k; v] of its head_dim).
    Native→HF direction of the reference's family-generic converter
    (scripts/checkpoint_converter.py:685)."""
    c = config
    L, n, hd = c.num_layers, c.num_heads, c.head_dim

    def np32(x):
        return np.asarray(x, dtype=np.float32)

    lyr = params["layers"]
    q_k = np32(lyr["attn"]["qkv"]["q_kernel"])  # (L, H, n·hd)
    k_k = np32(lyr["attn"]["qkv"]["k_kernel"])
    v_k = np32(lyr["attn"]["qkv"]["v_kernel"])
    q_b = np32(lyr["attn"]["qkv"]["q_bias"])
    k_b = np32(lyr["attn"]["qkv"]["k_bias"])
    v_b = np32(lyr["attn"]["qkv"]["v_bias"])
    o_k = np32(lyr["attn"]["o"]["kernel"])
    o_b = np32(lyr["attn"]["o"]["bias"])
    n1w, n1b = np32(lyr["attn_norm"]["scale"]), np32(lyr["attn_norm"]["bias"])
    n2w, n2b = np32(lyr["mlp_norm"]["scale"]), np32(lyr["mlp_norm"]["bias"])
    upw, upb = np32(lyr["mlp"]["up"]["kernel"]), np32(lyr["mlp"]["up"]["bias"])
    dnw, dnb = np32(lyr["mlp"]["down"]["kernel"]), np32(lyr["mlp"]["down"]["bias"])

    sd: Dict[str, Any] = {
        "gpt_neox.embed_in.weight": np32(params["embed"]["embedding"]),
        "gpt_neox.final_layer_norm.weight": np32(params["final_norm"]["scale"]),
        "gpt_neox.final_layer_norm.bias": np32(params["final_norm"]["bias"]),
        "embed_out.weight": np32(params["lm_head"]["kernel"]).T,
    }
    for i in range(L):
        pre = f"gpt_neox.layers.{i}."
        # head-major (n, hd, H) per component → interleave to (n, 3, hd, H)
        q = q_k[i].T.reshape(n, hd, -1)
        k = k_k[i].T.reshape(n, hd, -1)
        v = v_k[i].T.reshape(n, hd, -1)
        w = np.stack([q, k, v], axis=1).reshape(3 * n * hd, -1)
        b = np.stack(
            [q_b[i].reshape(n, hd), k_b[i].reshape(n, hd), v_b[i].reshape(n, hd)],
            axis=1,
        ).reshape(-1)
        sd[pre + "attention.query_key_value.weight"] = w
        sd[pre + "attention.query_key_value.bias"] = b
        sd[pre + "attention.dense.weight"] = o_k[i].T
        sd[pre + "attention.dense.bias"] = o_b[i]
        sd[pre + "input_layernorm.weight"] = n1w[i]
        sd[pre + "input_layernorm.bias"] = n1b[i]
        sd[pre + "post_attention_layernorm.weight"] = n2w[i]
        sd[pre + "post_attention_layernorm.bias"] = n2b[i]
        sd[pre + "mlp.dense_h_to_4h.weight"] = upw[i].T
        sd[pre + "mlp.dense_h_to_4h.bias"] = upb[i]
        sd[pre + "mlp.dense_4h_to_h.weight"] = dnw[i].T
        sd[pre + "mlp.dense_4h_to_h.bias"] = dnb[i]
    return sd


def params_to_hf_codegen(
    params: Params, config: GPTNeoXConfig, mp_num: int = 4
) -> Dict[str, Any]:
    """Inverse of :func:`params_from_hf_codegen`: re-fuses q/k/v into
    CodeGen's mp_num-blocked [query; value; key] ``qkv_proj`` layout."""
    c = config
    L, n, hd = c.num_layers, c.num_heads, c.head_dim

    def np32(x):
        return np.asarray(x, dtype=np.float32)

    lyr = params["layers"]
    q_k = np32(lyr["attn"]["qkv"]["q_kernel"])
    k_k = np32(lyr["attn"]["qkv"]["k_kernel"])
    v_k = np32(lyr["attn"]["qkv"]["v_kernel"])
    o_k = np32(lyr["attn"]["o"]["kernel"])
    n1w, n1b = np32(lyr["attn_norm"]["scale"]), np32(lyr["attn_norm"]["bias"])
    upw, upb = np32(lyr["mlp"]["up"]["kernel"]), np32(lyr["mlp"]["up"]["bias"])
    dnw, dnb = np32(lyr["mlp"]["down"]["kernel"]), np32(lyr["mlp"]["down"]["bias"])

    sd: Dict[str, Any] = {
        "transformer.wte.weight": np32(params["embed"]["embedding"]),
        "transformer.ln_f.weight": np32(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np32(params["final_norm"]["bias"]),
        "lm_head.weight": np32(params["lm_head"]["kernel"]).T,
    }
    if "bias" in params["lm_head"]:
        sd["lm_head.bias"] = np32(params["lm_head"]["bias"])
    n_loc = n // mp_num
    for i in range(L):
        pre = f"transformer.h.{i}."
        # head-major (mp, n/mp·hd, H) blocks, fused per block as [q; v; k]
        q = q_k[i].T.reshape(mp_num, n_loc * hd, -1)
        k = k_k[i].T.reshape(mp_num, n_loc * hd, -1)
        v = v_k[i].T.reshape(mp_num, n_loc * hd, -1)
        w = np.concatenate([q, v, k], axis=1).reshape(3 * n * hd, -1)
        sd[pre + "attn.qkv_proj.weight"] = w
        sd[pre + "attn.out_proj.weight"] = o_k[i].T
        sd[pre + "ln_1.weight"] = n1w[i]
        sd[pre + "ln_1.bias"] = n1b[i]
        sd[pre + "mlp.fc_in.weight"] = upw[i].T
        sd[pre + "mlp.fc_in.bias"] = upb[i]
        sd[pre + "mlp.fc_out.weight"] = dnw[i].T
        sd[pre + "mlp.fc_out.bias"] = dnb[i]
    return sd
