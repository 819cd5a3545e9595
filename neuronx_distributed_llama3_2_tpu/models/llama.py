"""Llama-3 / Llama-3.2 model family, TPU-native.

TPU-first re-design of the reference's training model
(``examples/training/llama/modeling_llama_nxd.py``): fused gate_up MLP
(:152-212), GQA attention with fused QKV (:238), RoPE sin/cos shared across
layers (tp_zero1_llama_hf_pretrain.py:151-158), Megatron-SP activation layout
(:352-440, LlamaModel scatter/gather :578,:625), selective activation
checkpointing of the core attention (:214), vocab-parallel cross-entropy head
(:643). None of that file's per-rank weight slicing or hand-inserted
collectives survives: parameters are *global* arrays with PartitionSpecs and
XLA/GSPMD inserts the Megatron TP/SP collectives from sharding constraints.

Structural choices that are TPU-idiomatic rather than reference-translated:

- **Stacked layers + ``lax.scan``**: all decoder layers share one set of
  weight arrays with a leading layer dim. One compiled layer body instead of
  ``num_layers`` unrolled copies (compile time, HBM working set); also gives
  pipeline partitioning natural layer-range slices.
- **Remat via ``jax.checkpoint`` policies** on the scanned body — replaces the
  reference's ``activation_checkpoint_config`` ("full" / CoreAttention class
  selective, trainer/trainer.py:33 + modeling_llama_nxd.py:214).
- **GQA**: K/V heads are *not* replicated ``kv_size_multiplier`` times as in
  the reference (qkv_linear.py:454) — sharding constraints keep K/V either
  tp-sharded (tp ≤ kv_heads) or replicated (tp > kv_heads), and XLA handles
  gradient summation over replicas.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    BATCH_AXES,
    ColumnParallelLinear,
    GQAQKVColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
    constrain,
    default_kernel_init,
)
from neuronx_distributed_llama3_2_tpu.parallel.loss import parallel_cross_entropy
from neuronx_distributed_llama3_2_tpu.parallel.state import TP_AXIS

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Model hyperparameters (mirrors the fields of HF ``LlamaConfig`` the
    reference trains from, examples/training/llama/configs)."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_layers: int = 16
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None  # defaults to hidden // heads
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # HF "llama3" rope_scaling (mandatory for published Llama-3.2 weights):
    # (factor, low_freq_factor, high_freq_factor, original_max_position).
    # None = plain RoPE (Llama-3 8B/70B).
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    # compute dtype for activations/weights; fp32 master handling lives in the
    # optimizer (reference mixed_precision_config, trainer/trainer.py:33)
    dtype: Any = jnp.bfloat16
    # "none" | "full" | "selective" — reference activation_checkpoint_config
    remat: str = "selective"
    scan_layers: bool = True
    # use the Pallas flash-attention kernel for core attention (reference
    # nki_flash_attn_func opt-in, modeling_llama_nxd.py:410-417)
    use_flash_attention: bool = False
    # flash kernel tile sizes (perf knobs; defaults in kernels/)
    flash_block_q: Optional[int] = None
    flash_block_kv: Optional[int] = None
    # paged serving decode: read the KV pool through the block table with
    # the Pallas flash-decoding kernel (kernels/paged_attention_pallas)
    # instead of materializing a (b, kv_limit, NKV, D) gather; covers
    # T == 1 token-gen and linear fresh blocks up to paged_kernel_max_t
    # tokens (speculative verify, short suffix-prefill chunks), dense
    # gather remains the fallback
    use_paged_kernel: bool = False
    # largest fresh-block length routed through the paged kernel: the t
    # fresh tokens fold into the kernel's query-tile rows, so this bounds
    # the (t * group) tile height; tree-masked blocks and longer prefill
    # buckets keep the dense gather
    paged_kernel_max_t: int = 8
    # low-precision MXU q·k in the paged kernel (quantized pool only): the
    # int8/fp8 payload stays a dot operand (int8×int8→int32 accumulate /
    # fp8 preferred_element_type=f32) and the absmax scales multiply the
    # fp32 score outputs instead of dequant-widening before the dot; off,
    # the kernel widens to fp32 first (the graftcheck GC005 contract)
    quant_mxu: bool = False
    # chunk the LM head + CE over the sequence so full (B,S,V) logits never
    # materialize; None disables (loss-memory redesign, no reference analogue)
    loss_chunk_size: Optional[int] = None
    # "rmsnorm" (Llama/Mixtral) | "layernorm" (DBRX/GPT-NeoX family models,
    # reference NeuronDbrxBlock uses nn.LayerNorm(bias=False),
    # neuron_modeling_dbrx.py:216-217)
    norm_type: str = "rmsnorm"
    norm_bias: bool = False
    # clamp Q/K/V projections to [-clip_qkv, clip_qkv] (DBRX attn_config,
    # reference neuron_modeling_dbrx.py:171)
    clip_qkv: Optional[float] = None
    # OLMoE's QK-norm (arXiv:2409.02060; HF OlmoeAttention q_norm/k_norm): an
    # RMSNorm with a learned scale over the whole projected query and the
    # whole projected key — every head jointly — after the projection and
    # before the split's rotary embedding. What the cache holds is the
    # normed, rotated key.
    qk_norm: bool = False
    # cp ring sequence layout: "auto" (zigzag when divisible and the kernel
    # mode takes Pallas — balances causal work across the ring,
    # kernels/ring_attention_pallas), "contiguous", or "zigzag" (forced;
    # the CPU tests use it under the "reference" mode). The model
    # permutes hidden states once outside the layer stack; attention layers
    # must resolve the SAME value (kernels.ring_attention.resolve_cp_layout)
    cp_ring_layout: str = "auto"

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_heads)
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.remat not in ("none", "full", "selective", "hybrid", "kv", "dots"):
            raise ValueError(
                f"remat must be none/full/selective/hybrid/kv/dots, got {self.remat!r}"
            )
        if self.norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(
                f"norm_type must be rmsnorm|layernorm, got {self.norm_type!r}"
            )
        if self.qk_norm and self.clip_qkv is not None:
            # HF OlmoeAttention clamps *after* its q/k norms, this block
            # before them; no published config sets both
            raise ValueError("qk_norm with clip_qkv is not implemented")


# Published Llama-3.x architectures (HF config.json values).
LLAMA_CONFIGS: Dict[str, LlamaConfig] = {
    "llama3.2-1b": LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=500000.0, rope_scaling=(32.0, 1.0, 4.0, 8192),
        max_seq_len=131072, tie_word_embeddings=True,
    ),
    "llama3.2-3b": LlamaConfig(
        vocab_size=128256, hidden_size=3072, intermediate_size=8192,
        num_layers=28, num_heads=24, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, rope_scaling=(32.0, 1.0, 4.0, 8192),
        max_seq_len=131072, tie_word_embeddings=True,
    ),
    "llama3-8b": LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, tie_word_embeddings=False,
    ),
    "llama3-70b": LlamaConfig(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, tie_word_embeddings=False,
    ),
    # hardware-free test config (reference combinatorial_tests/config.json is
    # likewise a fixed 4-layer llama)
    "tiny": LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=8,
        max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32,
        remat="none",
    ),
}


# ---------------------------------------------------------------------------
# RMSNorm + RoPE
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RMSNorm:
    """RMS layer norm in fp32 accumulation (reference uses HF LlamaRMSNorm /
    CustomRMSNorm, examples/inference/llama3/custom_calls.py:5). Weight is
    replicated; under SP its gradient reduction over tp is handled by GSPMD
    (replaces the reference's sequence_parallel_enabled weight tagging,
    parallel_layers/layer_norm.py:17 + grads.py:313)."""

    dim: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def init(self, key: jax.Array) -> Params:
        del key
        return {"scale": jnp.ones((self.dim,), jnp.float32)}

    def specs(self) -> Params:
        return {"scale": P(None)}

    @jax.named_scope("norm")
    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        h = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        h = h * lax.rsqrt(var + self.eps)
        return (h * params["scale"]).astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class LayerNorm:
    """Mean-centered layer norm in fp32 accumulation, optional bias —
    the DBRX/GPT-NeoX-family norm (reference NeuronDbrxBlock
    neuron_modeling_dbrx.py:216-217 uses ``nn.LayerNorm(bias=False)``).
    Same param protocol as :class:`RMSNorm`."""

    dim: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    bias: bool = False

    def init(self, key: jax.Array) -> Params:
        del key
        p = {"scale": jnp.ones((self.dim,), jnp.float32)}
        if self.bias:
            p["bias"] = jnp.zeros((self.dim,), jnp.float32)
        return p

    def specs(self) -> Params:
        s = {"scale": P(None)}
        if self.bias:
            s["bias"] = P(None)
        return s

    @jax.named_scope("norm")
    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        h = x.astype(jnp.float32)
        mean = jnp.mean(h, axis=-1, keepdims=True)
        h = h - mean
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        h = h * lax.rsqrt(var + self.eps)
        h = h * params["scale"]
        if self.bias:
            h = h + params["bias"]
        return h.astype(self.dtype)


def make_norm(config: "LlamaConfig"):
    """Norm block per ``config.norm_type`` (one construction site for every
    model family sharing the Llama block machinery)."""
    if config.norm_type == "layernorm":
        return LayerNorm(
            config.hidden_size, config.rms_norm_eps, config.dtype,
            bias=config.norm_bias,
        )
    return RMSNorm(config.hidden_size, config.rms_norm_eps, config.dtype)


def precompute_rope(
    head_dim: int,
    max_seq_len: int,
    theta: float,
    rope_scaling: Optional[Tuple[float, float, float, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) tables of shape (max_seq_len, head_dim), fp32, shared by all
    layers (reference shares sin/cos across layers,
    tp_zero1_llama_hf_pretrain.py:151-158). ``rope_scaling`` applies HF's
    "llama3" long-context frequency scaling (factor, low_freq_factor,
    high_freq_factor, original_max_position)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if rope_scaling is not None:
        factor, low_f, high_f, orig_max = rope_scaling
        wavelen = 2 * jnp.pi / inv_freq
        smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
        smoothed = (1 - smooth) * inv_freq / factor + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen < orig_max / high_f,  # high freq: untouched
            inv_freq,
            jnp.where(
                wavelen > orig_max / low_f,  # low freq: fully scaled
                inv_freq / factor,
                smoothed,  # medium: interpolate
            ),
        )
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (S, D/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # (S, D) — HF layout
    return jnp.sin(emb), jnp.cos(emb)


def apply_rope(
    x: jax.Array, sin: jax.Array, cos: jax.Array, positions: jax.Array
) -> jax.Array:
    """Rotate (B, S, n, D) by position. HF rotate_half convention so HF
    checkpoints load without permutation."""
    sin = jnp.take(sin, positions, axis=0)[:, :, None, :]  # (B,S,1,D)
    cos = jnp.take(cos, positions, axis=0)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = x.astype(jnp.float32) * cos + rotated.astype(jnp.float32) * sin
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _warn_unsharded_heads(num: int, tp: int) -> None:
    from neuronx_distributed_llama3_2_tpu.utils.logger import get_logger

    get_logger().warning(
        "head count %d is not divisible by tp=%d: attention falls back to "
        "replicated head activations — a throughput/memory cliff, not an "
        "error. Pad heads with parallel.pad.pad_llama_params_for_tp or pick "
        "tp dividing the head count (reference pads, parallel_layers/pad.py:28).",
        num, tp,
    )


def _head_axis(num: int) -> Optional[str]:
    """Shard a head dimension over tp only when divisible (loud warning on
    the replication fallback — never silent, VERDICT guardrail #10)."""
    if not parallel_state.model_parallel_is_initialized():
        return None
    tp = parallel_state.get_tensor_model_parallel_size()
    if num % tp != 0:
        _warn_unsharded_heads(num, tp)
        return None
    return TP_AXIS


def core_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference CoreAttention (modeling_llama_nxd.py:214): softmax(QK^T/√d)V
    with causal mask, softmax in fp32. q (B,S,N,D); k/v (B,S,Nkv,D) with
    Nkv dividing N (GQA repeat happens here). ``bias`` is an fp32 additive
    mask broadcastable to (B, N, S, T) — e.g. a BERT padding mask. Kept as a
    separable function so remat policy can target it (reference selective
    checkpointing wraps exactly this module)."""
    b, s, n, d = q.shape
    nkv = k.shape[2]
    if nkv != n:
        rep = n // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    ha = _head_axis(n)
    scores = jnp.einsum("bsnd,btnd->bnst", q, k) * (d ** -0.5)
    scores = constrain(scores, P(BATCH_AXES, ha, None, None))
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if causal:
        st = lax.iota(jnp.int32, s)[:, None]
        tt = lax.iota(jnp.int32, k.shape[1])[None, :]
        scores = jnp.where(tt <= st, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnst,btnd->bsnd", probs, v)
    return constrain(out, P(BATCH_AXES, None, ha, None))


@dataclasses.dataclass(frozen=True)
class LlamaAttention:
    """GQA attention block (reference LlamaAttention
    modeling_llama_nxd.py:238): fused QKV column-parallel, RoPE, core
    attention, row-parallel output projection with SP reduce-scatter."""

    config: LlamaConfig
    # trace layout depends on global parallel state (shardlint SL002); valid
    # across re-init only because initialize/destroy_model_parallel clear
    # the jit cache (parallel/state.py)
    __layout_deps__ = (
        "get_context_parallel_size", "get_parallel_state",
        "model_parallel_is_initialized", "sequence_parallel_enabled",
    )

    def _qkv(self) -> GQAQKVColumnParallelLinear:
        c = self.config
        return GQAQKVColumnParallelLinear(
            hidden_size=c.hidden_size, num_heads=c.num_heads,
            num_kv_heads=c.num_kv_heads, head_dim=c.head_dim, dtype=c.dtype,
        )

    def _o(self) -> RowParallelLinear:
        c = self.config
        sp = parallel_state.sequence_parallel_enabled()
        return RowParallelLinear(
            in_features=c.num_heads * c.head_dim, out_features=c.hidden_size,
            sequence_parallel=sp, dtype=c.dtype,
        )

    def init(self, key: jax.Array) -> Params:
        c = self.config
        kq, ko = jax.random.split(key)
        params = {"qkv": self._qkv().init(kq), "o": self._o().init(ko)}
        if c.qk_norm:
            for name, heads in (("q_norm", c.num_heads), ("k_norm", c.num_kv_heads)):
                params[name] = {"scale": jnp.ones((heads * c.head_dim,), jnp.float32)}
        return params

    def specs(self) -> Params:
        specs = {"qkv": self._qkv().specs(), "o": self._o().specs()}
        if self.config.qk_norm:
            # replicated like every norm scale: 2 x hidden floats a layer
            specs["q_norm"] = specs["k_norm"] = {"scale": P(None)}
        return specs

    @jax.named_scope("qk_norm")
    def _qk_norm(self, params: Params, q: jax.Array, k: jax.Array):
        """RMSNorm of q (b, s, N, D) and k (b, s, NKV, D), each over all of
        its heads jointly, fp32 accumulation, with the learned scale. The
        mean runs over the head axis, which tensor parallelism shards: the
        block is global GSPMD math, so under tp > 1 the partitioner supplies
        the cross-shard sum (one scalar per token for q, one for k)."""
        eps = self.config.rms_norm_eps

        def norm(x, scale):
            h = x.astype(jnp.float32)
            var = jnp.mean(jnp.square(h), axis=(-2, -1), keepdims=True)
            h = h * lax.rsqrt(var + eps) * scale.reshape(x.shape[-2:])
            return h.astype(x.dtype)

        return norm(q, params["q_norm"]["scale"]), norm(k, params["k_norm"]["scale"])

    def _apply_rope(self, q, k, sin, cos, positions):
        """Full-head-dim rotate-half RoPE; partial-rotary families
        (GPT-NeoX/CodeGen) override. ``sin`` None: a block with no positional
        term at all (models/jamba.py, whose state-space layers carry the
        order) — q and k go on as they were projected, and no table exists."""
        if sin is None:
            return q, k
        return apply_rope(q, sin, cos, positions), apply_rope(k, sin, cos, positions)

    # the device-trace scopes of an attention block (serving/tracing.py
    # SCOPES): attn/qkv, attn/rope, attn/sdpa, attn/o_proj here;
    # attn/kv_write and attn/kv_read in the cached forward (inference/model.py)
    @jax.named_scope("attn")
    def __call__(
        self,
        params: Params,
        x: jax.Array,
        sin: jax.Array,
        cos: jax.Array,
        positions: jax.Array,
    ) -> jax.Array:
        c = self.config
        b = x.shape[0]
        qkv_layer = self._qkv()
        with jax.named_scope("qkv"):
            q, k, v = qkv_layer(params["qkv"], x)
            if c.clip_qkv is not None:
                q = jnp.clip(q, -c.clip_qkv, c.clip_qkv)
                k = jnp.clip(k, -c.clip_qkv, c.clip_qkv)
                v = jnp.clip(v, -c.clip_qkv, c.clip_qkv)
            s = q.shape[1]  # global seq len (post SP all-gather under GSPMD)
            q = q.reshape(b, s, c.num_heads, c.head_dim)
            k = k.reshape(b, s, c.num_kv_heads, c.head_dim)
            v = v.reshape(b, s, c.num_kv_heads, c.head_dim)
        if c.qk_norm:
            q, k = self._qk_norm(params, q, k)
        with jax.named_scope("rope"):
            q, k = self._apply_rope(q, k, sin, cos, positions)
        with jax.named_scope("sdpa"):
            attn = self._sdpa(qkv_layer, q, k, v)
        attn = attn.reshape(b, s, c.num_heads * c.head_dim)
        attn = checkpoint_name(attn, "attn_out")
        with jax.named_scope("o_proj"):
            return self._o()(params["o"], attn)

    def _sdpa(self, qkv_layer, q, k, v) -> jax.Array:
        """KV-head repeat, remat names, and the attention kernel the config
        and the mesh select; (B, S, N, D) in and out."""
        c = self.config
        b = q.shape[0]

        # tp > kv_heads: repeat KV heads to tp granularity so the attention
        # activations shard 1 head/device instead of full replication — the
        # GSPMD form of the reference's kv_size_multiplier replication
        # (qkv_linear.py:454); the repeat is on *activations*, so the single
        # stored kernel receives the summed gradient of all replicas
        # automatically (the reference needs KV replica-group all-reduces,
        # qkv_linear.py:250-256)
        m = qkv_layer.kv_repeat_factor()
        if m > 1:
            # mirror _activation_spec: keep the sequence dim on cp when
            # context parallelism is on (a None here would force an
            # all-gather of the full sequence right before ring attention)
            seq_axis = (
                parallel_state.CP_AXIS
                if parallel_state.model_parallel_is_initialized()
                and parallel_state.get_parallel_state().context_parallel_size > 1
                else None
            )
            k = jnp.repeat(k, m, axis=2)
            v = jnp.repeat(v, m, axis=2)
            k = constrain(k, P(BATCH_AXES, seq_axis, TP_AXIS, None))
            v = constrain(v, P(BATCH_AXES, seq_axis, TP_AXIS, None))

        # remat-saved activations are stored flattened to (B, S, N·D): with
        # head_dim < 128 the (…, N, D) layout pads D to the 128-lane tile and
        # doubles the HBM bill of every saved tensor (e.g. 2.0x on 1B's D=64)
        def save_flat(x, name):
            n, d = x.shape[2], x.shape[3]
            return checkpoint_name(
                x.reshape(b, x.shape[1], n * d), name
            ).reshape(b, x.shape[1], n, d)

        q = save_flat(q, "q_rope")
        k = save_flat(k, "kv_rope")
        v = save_flat(v, "kv_rope")
        cp = (
            parallel_state.get_context_parallel_size()
            if parallel_state.model_parallel_is_initialized()
            else 1
        )
        if cp > 1:
            # context parallelism: sequence stays cp-sharded; attention runs
            # as a k/v ring over the cp axis (kernels/ring_attention.py) —
            # the only op in the block that mixes sequence positions
            from neuronx_distributed_llama3_2_tpu.kernels.mode import (
                prefer_pallas,
            )
            from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import (
                active_cp_layout,
                ring_attention_sharded,
            )

            # the executor that permuted the hidden states declared the
            # layout via cp_layout(); reading it here (instead of
            # re-deriving) makes a layout/executor mismatch impossible.
            # zigzag ⇒ inputs are already permuted; contiguous ⇒ pallas
            # ring, or the jnp oracle in the "reference" kernel mode
            layout = active_cp_layout()
            if layout == "zigzag":
                impl = "zigzag"
            else:
                impl = "pallas" if prefer_pallas() else "jnp"
            attn = ring_attention_sharded(
                q, k, v,
                parallel_state.get_parallel_state().mesh,
                parallel_state.CP_AXIS,
                causal=True,
                impl=impl,
                pre_permuted=(layout == "zigzag"),
            )
        elif c.use_flash_attention:
            from neuronx_distributed_llama3_2_tpu.kernels.flash_attention import (
                DEFAULT_BLOCK_KV,
                DEFAULT_BLOCK_Q,
                flash_attention,
            )
            attn = flash_attention(
                q, k, v, causal=True,
                block_q=c.flash_block_q or DEFAULT_BLOCK_Q,
                block_kv=c.flash_block_kv or DEFAULT_BLOCK_KV,
            )
        else:
            attn = core_attention(q, k, v, causal=True)
        return attn


@dataclasses.dataclass(frozen=True)
class LlamaMLP:
    """SwiGLU MLP with fused gate_up projection (reference LlamaMLP
    modeling_llama_nxd.py:152-212 fuses gate+up in one ColumnParallel with
    stride=2). Here the fused kernel is (H, 2, I) — the extra unsharded axis
    separates gate/up so the split never crosses the tp-sharded I dim; XLA
    contracts it as a single (H, 2I) matmul on the MXU."""

    config: LlamaConfig
    # shardlint SL002 — see LlamaAttention
    __layout_deps__ = ("sequence_parallel_enabled",)

    def _down(self) -> RowParallelLinear:
        c = self.config
        sp = parallel_state.sequence_parallel_enabled()
        return RowParallelLinear(
            in_features=c.intermediate_size, out_features=c.hidden_size,
            sequence_parallel=sp, dtype=c.dtype,
        )

    def init(self, key: jax.Array) -> Params:
        c = self.config
        kg, kd = jax.random.split(key)
        return {
            "gate_up": default_kernel_init(
                kg, (c.hidden_size, 2, c.intermediate_size), c.dtype
            ),
            "down": self._down().init(kd),
        }

    def specs(self) -> Params:
        return {"gate_up": P(None, None, TP_AXIS), "down": self._down().specs()}

    @jax.named_scope("mlp")
    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        y = jnp.einsum("bsh,hti->bsti", x, params["gate_up"])
        y = constrain(y, P(BATCH_AXES, None, None, TP_AXIS))
        gate, up = y[:, :, 0, :], y[:, :, 1, :]
        h = jax.nn.silu(gate) * up
        h = constrain(h, P(BATCH_AXES, None, TP_AXIS))
        return self._down()(params["down"], h)


# ---------------------------------------------------------------------------
# Decoder layer / model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LlamaDecoderLayer:
    config: LlamaConfig

    def _norm(self) -> RMSNorm:
        c = self.config
        return make_norm(c)

    def init(self, key: jax.Array) -> Params:
        ka, km = jax.random.split(key)
        return {
            "attn_norm": self._norm().init(key),
            "attn": LlamaAttention(self.config).init(ka),
            "mlp_norm": self._norm().init(key),
            "mlp": LlamaMLP(self.config).init(km),
        }

    def specs(self) -> Params:
        return {
            "attn_norm": self._norm().specs(),
            "attn": LlamaAttention(self.config).specs(),
            "mlp_norm": self._norm().specs(),
            "mlp": LlamaMLP(self.config).specs(),
        }

    def __call__(self, params, x, sin, cos, positions):
        h = self._norm()(params["attn_norm"], x)
        x = x + LlamaAttention(self.config)(params["attn"], h, sin, cos, positions)
        h = self._norm()(params["mlp_norm"], x)
        x = x + LlamaMLP(self.config)(params["mlp"], h)
        return x


def _remat_policy(remat: str):
    if remat == "none":
        return None
    if remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    if remat == "hybrid":
        # save only H-wide tensors that are expensive to recompute (post-RoPE
        # q/k/v and the attention output); recompute norms and the 8x-wide
        # MLP intermediates. Best memory/recompute tradeoff for large-vocab
        # llama on 16G chips.
        return jax.checkpoint_policies.save_only_these_names(
            "q_rope", "kv_rope", "attn_out"
        )
    if remat == "kv":
        # like hybrid but q is also recomputed (one matmul + rope): 2/3 of
        # hybrid's activation footprint, buying batch on small-HBM chips
        return jax.checkpoint_policies.save_only_these_names(
            "kv_rope", "attn_out"
        )
    if remat == "dots":
        # save every matmul output, recompute only cheap elementwise/norm/
        # softmax work in the backward: near-zero FLOP overhead (vs "full"'s
        # 33% fwd recompute), at the cost of ~2·B·S·(H+I)·L bytes of residuals
        # — the fastest policy when the batch fits
        return jax.checkpoint_policies.dots_saveable
    # "selective": save the big matmul outputs and the flash kernel's output +
    # LSE (named in its forward rule; unlisted, flash_fwd runs again), recompute
    cp = jax.checkpoint_policies  # the rest: norms, rope, activations, softmax
    return cp.save_from_both_policies(cp.dots_with_no_batch_dims_saveable, cp.save_only_these_names("flash_out", "flash_lse"))


@dataclasses.dataclass(frozen=True)
class LlamaForCausalLM:
    """Full causal-LM (reference LlamaForCausalLM modeling_llama_nxd.py:643 +
    LlamaModel :507). ``__call__`` returns logits; ``loss`` fuses the
    vocab-parallel cross-entropy head so the full-vocab logits are never
    replicated (reference parallel_cross_entropy usage :643)."""

    config: LlamaConfig
    # shardlint SL002 — see LlamaAttention
    __layout_deps__ = (
        "get_context_parallel_size", "model_parallel_is_initialized",
        "sequence_parallel_enabled",
    )

    def _embed(self) -> ParallelEmbedding:
        c = self.config
        return ParallelEmbedding(c.vocab_size, c.hidden_size, dtype=c.dtype)

    def _lm_head(self) -> ColumnParallelLinear:
        c = self.config
        return ColumnParallelLinear(
            in_features=c.hidden_size, out_features=c.vocab_size, dtype=c.dtype
        )

    def _layer(self) -> LlamaDecoderLayer:
        return LlamaDecoderLayer(self.config)

    def _norm(self) -> RMSNorm:
        c = self.config
        return make_norm(c)

    def init(self, key: jax.Array) -> Params:
        c = self.config
        ke, kl, kh = jax.random.split(key, 3)
        layer_keys = jax.random.split(kl, c.num_layers)
        # stacked layer params: leading dim = layer
        layers = jax.vmap(self._layer().init)(layer_keys)
        params = {
            "embed": self._embed().init(ke),
            "layers": layers,
            "final_norm": self._norm().init(kh),
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = self._lm_head().init(kh)
        return params

    def specs(self) -> Params:
        c = self.config
        layer_specs = jax.tree.map(
            lambda s: P(None, *s), self._layer().specs(),
            is_leaf=lambda s: isinstance(s, P),
        )
        specs = {
            "embed": self._embed().specs(),
            "layers": layer_specs,
            "final_norm": self._norm().specs(),
        }
        if not c.tie_word_embeddings:
            specs["lm_head"] = self._lm_head().specs()
        return specs

    def _sp_enabled(self) -> bool:
        return parallel_state.sequence_parallel_enabled()

    def _rope(self, s: int):
        """Rope tables shared across layers (reference sin/cos sharing,
        tp_zero1_llama_hf_pretrain.py:151-158). Overridden by partial-rotary
        families (GPT-NeoX/CodeGen)."""
        c = self.config
        return precompute_rope(c.head_dim, s, c.rope_theta, c.rope_scaling)

    def _zigzag_enter(self, x: jax.Array, positions: jax.Array):
        """Move (B, S, ...) hidden + positions into the zigzag cp layout —
        ONE permutation outside the layer stack (every op but attention is
        position-wise, and attention gets the permuted positions for RoPE),
        so the per-layer ring runs with zero layout shuffles. Returns
        (x, positions, inv) with inv=None when the layout stays contiguous."""
        cp = (
            parallel_state.get_context_parallel_size()
            if parallel_state.model_parallel_is_initialized()
            else 1
        )
        if cp <= 1:
            return x, positions, None
        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import (
            resolve_cp_layout,
        )

        layout = resolve_cp_layout(
            x.shape[1], cp, causal=True,
            force=getattr(self.config, "cp_ring_layout", "auto"),
        )
        if layout != "zigzag":
            return x, positions, None
        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention_pallas import (
            zigzag_permutation,
        )

        perm, inv = zigzag_permutation(x.shape[1], cp)
        return x.take(perm, axis=1), positions.take(perm, axis=1), inv

    @staticmethod
    def _zigzag_exit(x: jax.Array, inv) -> jax.Array:
        """Inverse permutation before anything order-sensitive (the loss
        shift, logits for eval) sees the hidden states."""
        return x if inv is None else x.take(inv, axis=1)

    def _backbone(self, params: Params, input_ids: jax.Array) -> jax.Array:
        """Embed + decoder stack + final norm → hidden states (B, S, H)."""
        c = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        sin, cos = self._rope(s)
        x = self._embed()(params["embed"], input_ids)
        x, positions, zz_inv = self._zigzag_enter(x, positions)
        if self._sp_enabled():
            # enter SP region: shard seq over tp (reference
            # scatter_to_sequence_parallel_region, modeling_llama_nxd.py:578)
            x = constrain(x, P(BATCH_AXES, TP_AXIS, None))

        layer = self._layer()

        def body(x, layer_params):
            y = layer(layer_params, x, sin, cos, positions)
            return y, None

        policy = _remat_policy(c.remat)
        if policy is not None:
            body = jax.checkpoint(body, policy=policy)
        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import (
            cp_layout_from_inv,
        )

        with cp_layout_from_inv(zz_inv):
            if c.scan_layers:
                x, _ = lax.scan(body, x, params["layers"])
            else:
                for i in range(c.num_layers):
                    x, _ = body(
                        x, jax.tree.map(lambda p: p[i], params["layers"])
                    )
        x = self._norm()(params["final_norm"], x)
        x = self._zigzag_exit(x, zz_inv)
        if self._sp_enabled():
            # exit SP region (reference gather_from_sequence_parallel_region,
            # modeling_llama_nxd.py:625)
            x = constrain(x, P(BATCH_AXES, None, None))
        return x

    @jax.named_scope("lm_head")
    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        c = self.config
        if c.tie_word_embeddings:
            logits = jnp.einsum("bsh,vh->bsv", hidden, params["embed"]["embedding"])
        else:
            logits = hidden @ params["lm_head"]["kernel"]
        return constrain(logits, P(BATCH_AXES, None, TP_AXIS))

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        """Return full logits (B, S, V) — use for eval/inference; for
        training prefer :meth:`loss` (vocab stays sharded)."""
        return self._logits(params, self._backbone(params, input_ids))

    def loss_from_hidden(
        self, params: Params, hidden: jax.Array, labels: jax.Array
    ) -> jax.Array:
        """Shared LM-head + masked-mean CE tail (used by the pipelined model
        too, so masking semantics can never diverge)."""
        shifted = labels[:, 1:]
        if self.config.loss_chunk_size is not None:
            from neuronx_distributed_llama3_2_tpu.parallel.loss import (
                fused_linear_cross_entropy,
            )

            loss_sum, count = fused_linear_cross_entropy(
                hidden[:, :-1, :],
                lambda hc: self._logits(params, hc),
                shifted,
                chunk_size=self.config.loss_chunk_size,
            )
            return loss_sum / jnp.maximum(count, 1.0)
        logits = self._logits(params, hidden[:, :-1, :])
        per_tok = parallel_cross_entropy(logits, shifted)
        from neuronx_distributed_llama3_2_tpu.parallel.loss import (
            valid_token_mask,
        )

        # same validity mask as the CE kernel, so the denominator never counts
        # tokens whose numerator was zeroed (ignore-index or out-of-vocab ids)
        valid = valid_token_mask(shifted, self.config.vocab_size).astype(
            jnp.float32
        )
        return jnp.sum(per_tok * valid) / jnp.maximum(jnp.sum(valid), 1.0)

    def loss(
        self, params: Params, input_ids: jax.Array, labels: jax.Array
    ) -> jax.Array:
        """Mean next-token cross-entropy. ``labels`` aligned with
        ``input_ids`` (HF convention: shift happens here, loss on positions
        predicting labels[:, 1:])."""
        return self.loss_from_hidden(
            params, self._backbone(params, input_ids), labels
        )


# ---------------------------------------------------------------------------
# HF checkpoint interop (reference scripts/checkpoint_converter.py:20 maps
# HF full checkpoints into the framework's layout; this is the in-memory core
# of that conversion, reused by the converter CLI and the parity tests)
# ---------------------------------------------------------------------------

def params_from_hf(state_dict: Dict[str, Any], config: LlamaConfig) -> Params:
    """Convert an HF Llama ``state_dict`` (numpy/torch tensors, HF names) to
    this model's stacked pytree. Torch Linear stores (out, in); we store
    (in, out)."""
    import numpy as np

    def t(name):
        w = state_dict[name]
        if hasattr(w, "detach"):
            w = w.detach().cpu().numpy()
        return np.asarray(w, dtype=np.float32)

    c = config
    L = c.num_layers

    def stack(fmt, transform):
        return jnp.asarray(
            np.stack([transform(t(fmt.format(i))) for i in range(L)]), dtype=c.dtype
        )

    def stack_norm(fmt):
        return jnp.asarray(
            np.stack([t(fmt.format(i)) for i in range(L)]), dtype=jnp.float32
        )

    # fused gate+up: (L, H, 2, I)
    gates = np.stack(
        [t(f"model.layers.{i}.mlp.gate_proj.weight").T for i in range(L)]
    )
    ups = np.stack([t(f"model.layers.{i}.mlp.up_proj.weight").T for i in range(L)])
    gate_up = jnp.asarray(np.stack([gates, ups], axis=2), dtype=c.dtype)

    params: Params = {
        "embed": {
            "embedding": jnp.asarray(t("model.embed_tokens.weight"), dtype=c.dtype)
        },
        "layers": {
            "attn_norm": {"scale": stack_norm("model.layers.{}.input_layernorm.weight")},
            "attn": {
                "qkv": {
                    "q_kernel": stack(
                        "model.layers.{}.self_attn.q_proj.weight", lambda w: w.T
                    ),
                    "k_kernel": stack(
                        "model.layers.{}.self_attn.k_proj.weight", lambda w: w.T
                    ),
                    "v_kernel": stack(
                        "model.layers.{}.self_attn.v_proj.weight", lambda w: w.T
                    ),
                },
                "o": {
                    "kernel": stack(
                        "model.layers.{}.self_attn.o_proj.weight", lambda w: w.T
                    )
                },
            },
            "mlp_norm": {
                "scale": stack_norm("model.layers.{}.post_attention_layernorm.weight")
            },
            "mlp": {
                "gate_up": gate_up,
                "down": {
                    "kernel": stack(
                        "model.layers.{}.mlp.down_proj.weight", lambda w: w.T
                    )
                },
            },
        },
        "final_norm": {
            "scale": jnp.asarray(t("model.norm.weight"), dtype=jnp.float32)
        },
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = {
            "kernel": jnp.asarray(t("lm_head.weight").T, dtype=c.dtype)
        }
    return params


def params_to_hf(params: Params, config: LlamaConfig) -> Dict[str, Any]:
    """Inverse of :func:`params_from_hf`: stacked pytree → HF Llama
    ``state_dict`` (numpy fp32, HF names, torch (out, in) Linear layout).
    The native→HF direction of the reference's checkpoint converter
    (scripts/checkpoint_converter.py:238 ``merge_tp_checkpoints`` — which
    additionally has to merge per-rank shards; global arrays dissolve that)."""
    import numpy as np

    c = config
    L = c.num_layers

    def np32(x):
        return np.asarray(x, dtype=np.float32)

    lyr = params["layers"]
    sd: Dict[str, Any] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
    }
    # one device->host transfer per stacked tensor, then index host-side
    # (per-layer slicing of device arrays would issue L x 7 blocking syncs)
    gate_up = np32(lyr["mlp"]["gate_up"])  # (L, H, 2, I)
    attn_norm = np32(lyr["attn_norm"]["scale"])
    mlp_norm = np32(lyr["mlp_norm"]["scale"])
    q_k = np32(lyr["attn"]["qkv"]["q_kernel"])
    k_k = np32(lyr["attn"]["qkv"]["k_kernel"])
    v_k = np32(lyr["attn"]["qkv"]["v_kernel"])
    o_k = np32(lyr["attn"]["o"]["kernel"])
    down = np32(lyr["mlp"]["down"]["kernel"])
    for i in range(L):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = attn_norm[i]
        sd[p + "post_attention_layernorm.weight"] = mlp_norm[i]
        sd[p + "self_attn.q_proj.weight"] = q_k[i].T
        sd[p + "self_attn.k_proj.weight"] = k_k[i].T
        sd[p + "self_attn.v_proj.weight"] = v_k[i].T
        sd[p + "self_attn.o_proj.weight"] = o_k[i].T
        sd[p + "mlp.gate_proj.weight"] = gate_up[i, :, 0, :].T
        sd[p + "mlp.up_proj.weight"] = gate_up[i, :, 1, :].T
        sd[p + "mlp.down_proj.weight"] = down[i].T
    if not c.tie_word_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]["kernel"]).T
    return sd
