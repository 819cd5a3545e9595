"""MiniCPM-SALA model family (``openbmb/MiniCPM-SALA``, HF ``model_type:
minicpm_sala``), TPU-native: a stack whose mixers are named layer by layer
(``mixer_types``) — **block-sparse softmax attention** (``minicpm4``) and
**Lightning linear attention** (``lightning-attn``), published 1 : 3 in an
irregular order — under muP's three scalings and a dense SwiGLU.

Common: ``h0 = scale_emb · Embed(ids)``; each sub-layer ``h ← h + (scale_depth
/ √mup_denominator) · f(RMSNorm(h))`` — the *published* depth stays in the
factor when a configuration cuts layers; logits ``= lm_head(RMSNorm(h) /
(hidden / dim_model_base))``.

*``lightning-attn``*, per head (``d`` = head_dim): ``q = RoPE(RMSNorm_d(W_q
x))``, ``k = RoPE(RMSNorm_d(W_k x))``, ``v = W_v x``; a float32 matrix state
``S_t = λ_h S_{t−1} + k_tᵀ v_t``, ``o_t = q_t S_t / √d``; ``y = W_o
(RMSNorm(o) ⊙ σ(W_g x))`` with the norm over the whole ``heads · d`` output.
``λ_h = exp(−2^{−8(h+1)/heads})``: Lightning Attention-2's fixed per-head
slopes, the same in every layer, no normaliser (:func:`lightning_slopes`).
A block of rows runs the chunk form (:func:`lightning_chunk`): ``O = ((Q Kᵀ)
⊙ D) V + Λ Q S_prev``, ``D_ij = λ^{i−j}`` for ``i ≥ j``.

*``minicpm4``* (grouped queries on ``num_kv_heads`` heads): ``q = RMSNorm_d(W_q
x)``, ``k = RMSNorm_d(W_k x)``, ``v = W_v x``, **no rotary**, scale ``1/√d``.
Pooled index keys ``K̄_j = mean(k[stride · j : stride · j + kernel])`` for
every complete kernel — no learned parameter (InfLLM-v2, arXiv:2506.07900).
The query row at position ``p`` scores the kernels that end at or before
``p``, per head a softmax over them, summed over the heads of a kv group; a
block of ``block`` rows scores the largest of the kernels that overlap it;
the first ``init_blocks`` blocks and the blocks that hold rows ``p − window +
1 … p`` are always taken, and of the rest the highest-scoring until ``topk``
blocks in all (:func:`select_blocks`). The row attends, causally, to the rows
of its chosen blocks; ``y = W_o (o ⊙ σ(W_g x))``. While a row has at most
``topk`` blocks behind it the layer is plain causal attention. The rule holds
at every row — the family's serving code attends densely while a *call's*
sequence is short, a property of a call a chunked engine does not have — so
the result does not depend on how a prompt was cut into chunks.

The training-side model (:class:`SalaForCausalLM`) makes the weights and runs
a whole sequence from the zero state with a dense mask; the serving engines
run :class:`..inference.model.SalaDecode` over a
:class:`..inference.model.HybridCache`. ``tp > 1`` is not run.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.jamba import layer_runs as kind_runs
from neuronx_distributed_llama3_2_tpu.models.laguna import Run, scan_run
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    LlamaMLP,
    apply_rope,
    make_norm,
)
from neuronx_distributed_llama3_2_tpu.parallel.layers import default_kernel_init

Params = Dict[str, Any]

# the published names of the two mixers (``mixer_types``)
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_STACKS = {SPARSE: "sparse_layers", LIGHTNING: "lightning_layers"}
# what a Lightning layer's state accumulates in: it sums every row so far
STATE_DTYPE = jnp.float32
# openbmb/MiniCPM-SALA config.json ``mixer_types``
PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING for i in range(32))
_MASKED = -1e30       # a plain float: a jnp scalar at import would start the backend


@dataclasses.dataclass(frozen=True)
class SalaConfig(LlamaConfig):
    """LlamaConfig with the family's keys. ``num_heads`` / ``num_kv_heads`` /
    ``head_dim`` are the sparse layers'; the Lightning layers have
    ``lightning_heads`` of ``head_dim`` for q, k and v alike. ``rope_theta``
    is the *Lightning* layers' (the sparse ones have no positional term)."""

    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    rope_theta: float = 10000.0
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    lightning_heads: int = 32
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32       # the published depth, inside the residual factor
    dim_model_base: int = 256
    # the family's published ``sparse_config`` (InfLLM-v2)
    kernel_size: int = 32
    kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048

    def __post_init__(self):
        super().__post_init__()
        if len(self.mixer_types) != self.num_layers or set(self.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"mixer_types names {len(self.mixer_types)} layers of kinds "
                f"{sorted(set(self.mixer_types))}; the stack has {self.num_layers} "
                f"of {SPARSE!r} / {LIGHTNING!r}")
        if self.kernel_size % self.kernel_stride or self.sparse_block_size % self.kernel_stride:
            raise ValueError("kernel_size and sparse_block_size are whole strides")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.mixer_types)

    def layers_of(self, kind: str) -> int:
        return self.mixer_types.count(kind)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    @property
    def kernels_per_block(self) -> int:
        return self.sparse_block_size // self.kernel_stride

    def state_bytes_per_layer(self) -> int:
        """Bytes one sequence leaves behind one Lightning layer."""
        return self.lightning_heads * self.head_dim ** 2 * jnp.dtype(STATE_DTYPE).itemsize


SALA_CONFIGS: Dict[str, SalaConfig] = {
    # openbmb/MiniCPM-SALA config.json values
    "minicpm-sala": SalaConfig(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        num_layers=32, num_heads=32, num_kv_heads=2, head_dim=128, max_seq_len=524288),
    # both kinds twice, a sparse layer first in neither run (L S L L S), and a
    # selection small enough that a context of a hundred rows drops blocks:
    # kernels of 4 rows every 2, blocks of 4, 6 blocks a row of which the
    # first and a window of 6 rows (2-3 blocks) are forced
    "tiny-sala": SalaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=2, head_dim=16,
        mixer_types=(LIGHTNING, SPARSE, LIGHTNING, LIGHTNING, SPARSE),
        lightning_heads=4, mup_denominator=5, dim_model_base=32,
        kernel_size=4, kernel_stride=2, sparse_block_size=4, sparse_topk=6,
        sparse_init_blocks=1, sparse_window=6,
        max_seq_len=128, dtype=jnp.float32, remat="none",
    ),
}


# ---------------------------------------------------------------------------
# pieces both mixers' forms share: every one takes a batch of sequences
# ---------------------------------------------------------------------------

def head_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over a head's own ``d`` values with the learned scale (d,)."""
    h = x.astype(jnp.float32)
    h = h * lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + eps) * scale
    return h.astype(x.dtype)


def lightning_slopes(heads: int) -> jax.Array:
    """(heads,) float32 ``−log λ_h = 2^{−8(h+1)/heads}``."""
    return jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)


def lightning_step(q, k, v, s, alive, slopes):
    """One token a sequence. q, k, v (b, n, d); s (b, n, d, d); ``alive`` (b,)
    bool: a lane that is not leaves its state as it was. The state is
    computed in float32 and goes on in the dtype it came in. Returns
    (o (b, n, d) float32, s)."""
    f32 = jnp.float32
    new = jnp.exp(-slopes)[:, None, None] * s.astype(f32) + (
        k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :])
    new = jnp.where(alive[:, None, None, None], new, s.astype(f32)).astype(s.dtype)
    o = jnp.einsum("bnd,bnde->bne", q.astype(f32), new.astype(f32))
    return o * q.shape[-1] ** -0.5, new


def lightning_chunk(q, k, v, s, live, slopes):
    """A block of rows a sequence in the chunk form. q, k, v (b, t, n, d); s
    (b, n, d, d) the state before the block; ``live`` (b,): rows at or past it
    are padding and leave the state untouched (their outputs mean nothing).
    Returns (o (b, t, n, d) float32, s after the last live row)."""
    f32 = jnp.float32
    t, d = q.shape[1], q.shape[-1]
    i = lax.iota(jnp.int32, t)
    gap = i[:, None] - i[None, :]
    # D (n, t, t): λ^{i−j} on and under the diagonal, an exponent never positive
    decay = jnp.where(
        gap >= 0, jnp.exp(-slopes[:, None, None] * jnp.maximum(gap, 0).astype(f32)), 0.0)
    scores = jnp.einsum("bind,bjnd->bnij", q, k, preferred_element_type=f32) * decay
    intra = jnp.einsum("bnij,bjnd->bind", scores.astype(v.dtype), v, preferred_element_type=f32)
    carried = jnp.exp(-slopes[None, :] * (i[:, None] + 1).astype(f32))       # (t, n): Λ
    inter = jnp.einsum("bind,bnde->bine", q.astype(f32) * carried[None, :, :, None], s.astype(f32))
    # what the live rows add to the state: row j weighs λ^{live − 1 − j}
    left = (live[:, None] - 1 - i[None, :]).astype(f32)                       # (b, t)
    weight = jnp.where(
        left[..., None] >= 0, jnp.exp(-slopes[None, None, :] * jnp.maximum(left, 0.0)[..., None]), 0.0)
    added = jnp.einsum(
        "bjnd,bjne->bnde", k.astype(f32) * weight[..., None], v.astype(f32))
    kept = jnp.exp(-slopes[None, :] * live[:, None].astype(f32))              # (b, n)
    new = kept[..., None, None] * s.astype(f32) + added
    return (intra + inter) * d ** -0.5, new.astype(s.dtype)


def pool_keys(rows: jax.Array) -> jax.Array:
    """rows (..., kernel, d) of one kernel each -> its pooled key (..., d):
    the plain mean, float32 inside."""
    return jnp.mean(rows.astype(jnp.float32), axis=-2).astype(rows.dtype)


def select_blocks(q, pooled, q_pos, config: SalaConfig):
    """Which blocks each query row reads. q (b, t, N, d); ``pooled`` (b, J,
    NKV, d), kernel ``j``'s key at index ``j`` with ``J`` whole blocks' worth
    (a kernel that is not complete yet may hold anything); q_pos (b, t).
    Returns (blocks (b, t, NKV, k) int32 — forced ones first — and ``taken``
    (b, t, NKV, k) bool: False where a row has fewer than k blocks behind it
    and the place names a block it cannot see)."""
    c = config
    b, t, n, d = q.shape
    nkv, f32 = pooled.shape[2], jnp.float32
    per_block, lead = c.kernels_per_block, c.kernel_size // c.kernel_stride - 1
    blocks = pooled.shape[1] // per_block
    scores = jnp.einsum(
        "btkgd,bjkd->bkgtj", q.reshape(b, t, nkv, n // nkv, d), pooled,
        preferred_element_type=f32) * d ** -0.5
    j = lax.iota(jnp.int32, pooled.shape[1])
    complete = (c.kernel_stride * j + c.kernel_size - 1)[None, None, :] <= q_pos[..., None]
    seen = complete[:, None, None]                                          # (b, 1, 1, t, J)
    scores = jnp.where(seen, scores, _MASKED)
    probs = jnp.where(seen, jax.nn.softmax(scores, axis=-1), 0.0)
    group = jnp.where(seen[:, :, 0], jnp.sum(probs, axis=2), -1.0)            # (b, k, t, J)
    # a block scores the largest of the kernels that overlap it: its own
    # ``per_block`` and the ``lead`` before them that reach into it
    padded = jnp.pad(group, ((0, 0), (0, 0), (0, 0), (lead, 0)), constant_values=-1.0)
    block_score = functools.reduce(jnp.maximum, [
        padded[..., o: o + blocks * per_block: per_block] for o in range(per_block + lead)])
    bb = lax.iota(jnp.int32, blocks)[None, None, :]                           # (1, 1, blocks)
    p = q_pos[..., None]
    causal = bb <= p // c.sparse_block_size
    first_of_window = jnp.maximum(p - c.sparse_window + 1, 0) // c.sparse_block_size
    forced = ((bb < c.sparse_init_blocks) | (bb >= first_of_window)) & causal
    score = jnp.where(forced[:, None], jnp.inf, block_score)
    score = jnp.where(causal[:, None], score, -jnp.inf)                       # (b, k, t, blocks)
    top, chosen = lax.top_k(score, min(c.sparse_topk, blocks))
    to_rows = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731  (b, t, k, ·)
    return to_rows(chosen).astype(jnp.int32), to_rows(top > -jnp.inf)


def block_mask(chosen, taken, blocks: int) -> jax.Array:
    """(b, t, NKV, blocks) bool from :func:`select_blocks`' lists."""
    hit = (chosen[..., None] == lax.iota(jnp.int32, blocks)) & taken[..., None]
    return jnp.any(hit, axis=-2)


def attend_tiles(q, q_pos, mask, read: Callable, tiles: int, tile_blocks: int, block: int):
    """Softmax attention of q (b, t, N, d) over the rows ``read(i)`` hands out
    a tile at a time — (k, v) each (b, tile_blocks · block, NKV, d), tile
    ``i``'s rows at positions ``i · tile_blocks · block ..`` — with a running
    max and sum, so that no (heads, t, context) array exists. A row sees the
    positions at or before its own inside the blocks ``mask`` (b, t, NKV,
    blocks) names. Returns (b, t, N, d) in q's dtype."""
    b, t, n, d = q.shape
    nkv, f32 = mask.shape[2], jnp.float32
    g, rows = n // nkv, tile_blocks * block
    qg = q.reshape(b, t, nkv, g, d)
    mask = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, tiles * tile_blocks - mask.shape[-1])))

    def tile(carry, i):
        high, total, acc = carry
        k, v = read(i)
        s = jnp.einsum("btkgd,bskd->bkgts", qg, k, preferred_element_type=f32) * d ** -0.5
        pos = i * rows + lax.iota(jnp.int32, rows)
        named = lax.dynamic_slice_in_dim(mask, i * tile_blocks, tile_blocks, axis=3)
        named = jnp.repeat(named, block, axis=3)                             # (b, t, k, rows)
        seen = named & (pos[None, None, None, :] <= q_pos[:, :, None, None])
        seen = jnp.swapaxes(seen, 1, 2)[:, :, None]                          # (b, k, 1, t, rows)
        s = jnp.where(seen, s, _MASKED)
        new_high = jnp.maximum(high, jnp.max(s, axis=-1))
        w = jnp.where(seen, jnp.exp(s - new_high[..., None]), 0.0)
        scale = jnp.exp(high - new_high)
        total = total * scale + jnp.sum(w, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgts,bskd->bkgtd", w.astype(v.dtype), v, preferred_element_type=f32)
        return (new_high, total, acc), None

    lead = (b, nkv, g, t)
    init = (jnp.full(lead, _MASKED, f32), jnp.zeros(lead, f32), jnp.zeros(lead + (d,), f32))
    (_, total, acc), _ = lax.scan(tile, init, jnp.arange(tiles, dtype=jnp.int32))
    out = acc / jnp.maximum(total, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(b, t, n, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# the blocks and the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SalaMixer:
    """One mixer of ``kind``: the projections (``qkv``, the full-width output
    gate ``gate``, ``o``), the per-head q / k norms, and — Lightning — the
    output norm. Scopes under the block's ``attn``: ``qkv``, ``qk_norm``,
    ``rope`` (Lightning), ``out_gate``, ``o_proj``, and the mixer's own
    ``sparse/{pool_keys, select, read}`` or ``lightning/{chunk, step,
    gate_norm}``."""

    config: SalaConfig
    kind: str

    @property
    def heads(self) -> Tuple[int, int]:
        c = self.config
        return (c.num_heads, c.num_kv_heads) if self.kind == SPARSE else (c.lightning_heads,) * 2

    def init(self, key: jax.Array) -> Params:
        c = self.config
        (n, nkv), d, h = self.heads, c.head_dim, c.hidden_size
        kq, kk, kv, kg, ko = jax.random.split(key, 5)
        ones = lambda width: {"scale": jnp.ones((width,), jnp.float32)}  # noqa: E731
        params = {
            "qkv": {"q_kernel": default_kernel_init(kq, (h, n * d), c.dtype),
                    "k_kernel": default_kernel_init(kk, (h, nkv * d), c.dtype),
                    "v_kernel": default_kernel_init(kv, (h, nkv * d), c.dtype)},
            "q_norm": ones(d), "k_norm": ones(d),
            "gate": {"kernel": default_kernel_init(kg, (h, n * d), c.dtype)},
            "o": {"kernel": default_kernel_init(ko, (n * d, h), c.dtype)},
        }
        if self.kind == LIGHTNING:
            params["out_norm"] = ones(n * d)
        return params

    def specs(self) -> Params:
        return jax.tree.map(lambda a: P(*(None,) * a.ndim), jax.eval_shape(self.init, jax.random.key(0)))

    def project(self, params: Params, x: jax.Array, sin, cos, positions):
        """x (b, t, H) normed -> q (b, t, N, d), k, v (b, t, NKV, d): projected,
        q and k normed a head, and — Lightning — rotated by ``positions``
        (b, t)."""
        c = self.config
        (n, nkv), d = self.heads, c.head_dim
        b, t, _ = x.shape
        with jax.named_scope("qkv"):
            q = (x @ params["qkv"]["q_kernel"]).reshape(b, t, n, d)
            k = (x @ params["qkv"]["k_kernel"]).reshape(b, t, nkv, d)
            v = (x @ params["qkv"]["v_kernel"]).reshape(b, t, nkv, d)
        with jax.named_scope("qk_norm"):
            q = head_norm(q, params["q_norm"]["scale"], c.rms_norm_eps)
            k = head_norm(k, params["k_norm"]["scale"], c.rms_norm_eps)
        if self.kind == LIGHTNING:
            with jax.named_scope("rope"):
                q, k = apply_rope(q, sin, cos, positions), apply_rope(k, sin, cos, positions)
        return q, k, v

    def output(self, params: Params, x: jax.Array, o: jax.Array) -> jax.Array:
        """The mixer's way out: o (b, t, N, d) — Lightning's through its
        output norm — times σ(W_g x), through ``o``."""
        c = self.config
        b, t = o.shape[:2]
        o = o.reshape(b, t, -1)
        if self.kind == LIGHTNING:
            with jax.named_scope("lightning"), jax.named_scope("gate_norm"):
                o = head_norm(o, params["out_norm"]["scale"], c.rms_norm_eps)
        with jax.named_scope("out_gate"):
            o = o.astype(x.dtype) * jax.nn.sigmoid(x @ params["gate"]["kernel"])
        with jax.named_scope("o_proj"):
            return o @ params["o"]["kernel"]

    def __call__(self, params: Params, x: jax.Array, sin, cos, positions) -> jax.Array:
        """The whole sequence from the zero state (training, parity): the
        Lightning layer as one chunk, the sparse layer under a dense mask."""
        c = self.config
        b, s, _ = x.shape
        with jax.named_scope("attn"):
            q, k, v = self.project(params, x, sin, cos, positions)
            if self.kind == LIGHTNING:
                state = jnp.zeros((b, c.lightning_heads, c.head_dim, c.head_dim), STATE_DTYPE)
                o, _ = lightning_chunk(
                    q, k, v, state, jnp.full((b,), s, jnp.int32), lightning_slopes(c.lightning_heads))
            else:
                o = sparse_attention_dense(q, k, v, positions, c)
            return self.output(params, x, o)


def whole_kernels(k: jax.Array, blocks: int, config: SalaConfig) -> jax.Array:
    """k (b, s, NKV, d) of a whole sequence -> the pooled keys of ``blocks``
    blocks' worth of kernels (b, J, NKV, d); a kernel the sequence does not
    complete is zeros (no row may score it)."""
    c = config
    count = blocks * c.kernels_per_block
    need = c.kernel_stride * (count - 1) + c.kernel_size
    padded = jnp.pad(k, ((0, 0), (0, max(need - k.shape[1], 0)), (0, 0), (0, 0)))
    rows = (c.kernel_stride * jnp.arange(count)[:, None] + jnp.arange(c.kernel_size)[None, :])
    return pool_keys(jnp.moveaxis(padded[:, rows], 2, 3))                    # (b, J, NKV, kernel, d) -> mean


def sparse_attention_dense(q, k, v, positions, config: SalaConfig) -> jax.Array:
    """The sparse layer over a whole sequence it holds in hand: q (b, s, N, d),
    k, v (b, s, NKV, d) at ``positions`` (b, s) counting from 0."""
    c = config
    s = q.shape[1]
    blocks = -(-s // c.sparse_block_size)
    chosen, taken = select_blocks(q, whole_kernels(k, blocks, c), positions, c)
    pad = blocks * c.sparse_block_size - s
    k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (k, v))
    return attend_tiles(
        q, positions, block_mask(chosen, taken, blocks), lambda i: (k, v),
        1, blocks, c.sparse_block_size)


@dataclasses.dataclass(frozen=True)
class SalaDecoderLayer:
    """One block of ``kind``: both sub-layers add ``residual_scale`` times
    their output; the feed-forward is :class:`..llama.LlamaMLP`."""

    config: SalaConfig
    kind: str

    def init(self, key: jax.Array) -> Params:
        k_mixer, k_mlp = jax.random.split(key)
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.init(key), "attn": SalaMixer(self.config, self.kind).init(k_mixer),
            "mlp_norm": norm.init(key), "mlp": LlamaMLP(self.config).init(k_mlp),
        }

    def specs(self) -> Params:
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.specs(), "attn": SalaMixer(self.config, self.kind).specs(),
            "mlp_norm": norm.specs(), "mlp": LlamaMLP(self.config).specs(),
        }

    def __call__(self, params: Params, x: jax.Array, sin, cos, positions) -> jax.Array:
        c, norm = self.config, make_norm(self.config)
        h = norm(params["attn_norm"], x)
        x = x + c.residual_scale * SalaMixer(c, self.kind)(params["attn"], h, sin, cos, positions)
        h = norm(params["mlp_norm"], x)
        return x + c.residual_scale * LlamaMLP(c)(params["mlp"], h)


def stack_name(kind: str) -> str:
    return _STACKS[kind]


def layer_runs(config: SalaConfig) -> List[Run]:
    """``mixer_types`` as runs of consecutive layers of one kind (a kind is a
    stack of weights: :func:`..jamba.layer_runs` under this family's names)."""
    return kind_runs(config, stack_name)


@dataclasses.dataclass(frozen=True)
class _SalaBase(LlamaForCausalLM):
    """Embedding, final norm, head and loss tail with muP's two scalings."""

    def embed(self, params: Params, ids: jax.Array) -> jax.Array:
        x = self._embed()(params["embed"], ids)
        return x * jnp.asarray(self.config.scale_emb, x.dtype)

    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        scaled = hidden / jnp.asarray(self.config.logit_divisor, hidden.dtype)
        return super()._logits(params, scaled)


@dataclasses.dataclass(frozen=True)
class SalaForCausalLM:
    """Same protocol as :class:`..jamba.JambaForCausalLM`
    (init/specs/__call__/loss); the weights are one stack a mixer kind."""

    config: SalaConfig

    def _llama(self) -> _SalaBase:
        return _SalaBase(self.config)

    def _embed(self):
        return self._llama()._embed()

    def embed(self, params: Params, ids: jax.Array) -> jax.Array:
        return self._llama().embed(params, ids)

    def _norm(self):
        return self._llama()._norm()

    def _rope(self, s: int):
        return self._llama()._rope(s)

    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        return self._llama()._logits(params, hidden)

    def _kinds(self) -> List[str]:
        return list(dict.fromkeys(self.config.layer_kinds))

    def init(self, key: jax.Array) -> Params:
        c = self.config
        ke, kl, kh = jax.random.split(key, 3)
        params = {"embed": self._embed().init(ke), "final_norm": self._norm().init(kh)}
        for i, kind in enumerate(self._kinds()):
            keys = jax.random.split(jax.random.fold_in(kl, i), c.layers_of(kind))
            params[stack_name(kind)] = jax.vmap(SalaDecoderLayer(c, kind).init)(keys)
        if not c.tie_word_embeddings:
            params["lm_head"] = self._llama()._lm_head().init(kh)
        return params

    def specs(self) -> Params:
        c = self.config
        specs = {"embed": self._embed().specs(), "final_norm": self._norm().specs()}
        for kind in self._kinds():
            specs[stack_name(kind)] = jax.tree.map(
                lambda s: P(None, *s), SalaDecoderLayer(c, kind).specs(),
                is_leaf=lambda s: isinstance(s, P),
            )
        if not c.tie_word_embeddings:
            specs["lm_head"] = self._llama()._lm_head().specs()
        return specs

    def _backbone(self, params: Params, input_ids: jax.Array) -> jax.Array:
        c = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        sin, cos = self._rope(s)
        x = self.embed(params, input_ids)
        for run in layer_runs(c):
            layer = SalaDecoderLayer(c, run.kind)
            x, _ = scan_run(
                lambda x, lp, _: (layer(lp, x, sin, cos, positions), None), x, params[run.stack], run)
        return self._norm()(params["final_norm"], x)

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        return self._logits(params, self._backbone(params, input_ids))

    def loss_from_hidden(self, params, hidden, labels):
        return self._llama().loss_from_hidden(params, hidden, labels)

    def loss(self, params: Params, input_ids: jax.Array, labels: jax.Array) -> jax.Array:
        return self.loss_from_hidden(params, self._backbone(params, input_ids), labels)
