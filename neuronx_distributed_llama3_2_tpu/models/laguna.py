"""Laguna model family (``poolside/Laguna-XS.2``, HF ``model_type: laguna``),
TPU-native: a stack whose layers differ in what they attend over and in
their shapes, each a fact of the *model* config that the blocks read.

- **Two kinds of attention layer, mixed by a published list**
  (``layer_types``): a *full* layer attends over every earlier token, a
  *window* layer over the last ``sliding_window`` of them, the token itself
  among them (``i - window < j <= i``, the ``transformers`` mask for the key
  ``sliding_window``). The kinds have **different query-head counts**
  (``num_attention_heads_per_layer``: 48 full, 64 window at XS.2) over the
  same 8 kv heads, and **a rotary table each**: full — YaRN
  (``rope_type: yarn`` as ``transformers`` computes it, ``truncate`` on) on
  the first ``partial_rotary_factor`` of each head, cos and sin scaled by
  ``attention_factor``; window — plain, the whole head.
- **A per-head output gate** (``gating``): ``y_m <- sigmoid(h W_g)_m * y_m``,
  one scalar a query head a token from the layer's normed input, applied
  before ``W_o``.
- **The feed-forward by a published list too** (``mlp_layer_types``): a
  dense SwiGLU of ``intermediate_size``, or the expert block — softmax over
  all experts, the ``top_k`` largest renormalised and scaled by
  ``moe_routed_scaling_factor`` (:func:`..moe.routing.top_k_routing` with
  ``MoEConfig.routed_scale``), plus one ungated shared expert.

Layers of one (kind, feed-forward) shape are **one stack of weights**
(``params["full_dense_layers"]``, ``["window_layers"]``, ``["full_layers"]``),
run in the published order as runs of consecutive layers of one stack
(:func:`layer_runs`, :func:`scan_run`).

The training-side model (:class:`LagunaForCausalLM`) makes the weights and
runs every layer at full length with the window as a mask; the paged serving
engine runs :class:`..inference.model.LagunaDecode`, whose window layers keep
a ring of rows a lane. Rotary dimensions pair as *halves* (rotate-half, the
rotated half first). Training at scale (flash attention with a lower bound,
sequence or context parallelism) is not worked out.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaAttention,
    LlamaForCausalLM,
    LlamaMLP,
    _head_axis,
    apply_rope,
    make_norm,
    precompute_rope,
)
from neuronx_distributed_llama3_2_tpu.models.mixtral import MixtralConfig
from neuronx_distributed_llama3_2_tpu.models.sarvam import yarn_rope
from neuronx_distributed_llama3_2_tpu.moe.loss import load_balancing_loss
from neuronx_distributed_llama3_2_tpu.moe.model import MoE, MoEConfig
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    BATCH_AXES,
    constrain,
    default_kernel_init,
)
from neuronx_distributed_llama3_2_tpu.parallel.state import TP_AXIS

Params = Dict[str, Any]

FULL, WINDOW = "full", "window"
# the published spellings of ``layer_types``
LAYER_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
MLP_KINDS = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class LagunaConfig(MixtralConfig):
    """The published keys of ``laguna`` on top of the shared Llama/MoE
    fields. ``num_heads`` is the full layers' query-head count
    (``num_attention_heads``); ``intermediate_size`` the dense layers' width;
    ``rope_theta`` the full kind's."""

    # one entry a layer, as published
    layer_types: Tuple[str, ...] = ()
    num_heads_per_layer: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    sliding_window: int = 512
    # full kind: (factor, original_max_position, beta_fast, beta_slow,
    # attention_factor) of ``rope_type: yarn``, on the first
    # ``partial_rotary_factor`` of each head; None = plain tables
    yarn: Optional[Tuple[float, int, float, float, float]] = None
    partial_rotary_factor: float = 0.5
    # window kind: plain rotary over the whole head
    window_rope_theta: float = 10000.0
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    routed_scaling_factor: float = 2.5
    num_experts: int = 256
    top_k: int = 8
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        lists = (self.layer_types, self.num_heads_per_layer, self.mlp_layer_types)
        if {len(l) for l in lists} != {self.num_layers}:
            raise ValueError(
                f"layer_types, num_heads_per_layer and mlp_layer_types need "
                f"num_layers = {self.num_layers} entries each, got {[len(l) for l in lists]}"
            )
        if not set(self.layer_types) <= set(LAYER_KINDS):
            raise ValueError(f"layer_types knows {sorted(LAYER_KINDS)}, got {set(self.layer_types)}")
        if not set(self.mlp_layer_types) <= set(MLP_KINDS):
            raise ValueError(f"mlp_layer_types knows {MLP_KINDS}, got {set(self.mlp_layer_types)}")
        for kind in (FULL, WINDOW):
            heads = {n for n, k in zip(self.num_heads_per_layer, self.kinds) if k == kind}
            if len(heads) > 1:
                raise ValueError(
                    f"the {kind} layers' weights are one stack: one query-head count, got {sorted(heads)}"
                )
            if heads and next(iter(heads)) % self.num_kv_heads:
                raise ValueError("a layer's query heads must be a multiple of num_kv_heads")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be positive")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``full`` / ``window``, a layer."""
        return tuple(LAYER_KINDS[t] for t in self.layer_types)

    def heads_of(self, kind: str) -> int:
        """Query heads of the layers of ``kind`` (``num_heads`` where the
        stack has none of that kind)."""
        return next(
            (n for n, k in zip(self.num_heads_per_layer, self.kinds) if k == kind),
            self.num_heads,
        )

    def layers_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    def rotary_dim(self, kind: str) -> int:
        if kind == WINDOW:
            return self.head_dim
        return int(self.head_dim * self.partial_rotary_factor)

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
            shared_intermediate_size=self.shared_expert_intermediate_size,
            dtype=self.dtype,
        )


def _published_lists(num_layers: int, full_heads: int, window_heads: int):
    """XS.2's three lists at ``num_layers``: full, then window x 3, repeated;
    a leading dense layer."""
    types = tuple(
        "full_attention" if i % 4 == 0 else "sliding_attention" for i in range(num_layers)
    )
    return dict(
        layer_types=types,
        num_heads_per_layer=tuple(
            full_heads if t == "full_attention" else window_heads for t in types
        ),
        mlp_layer_types=("dense",) + ("sparse",) * (num_layers - 1),
    )


LAGUNA_CONFIGS: Dict[str, LagunaConfig] = {
    # poolside/Laguna-XS.2 config.json values
    "laguna-xs.2": LagunaConfig(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_layers=40, num_heads=48, num_kv_heads=8, head_dim=128,
        max_seq_len=262144, rope_theta=500000.0,
        yarn=(64.0, 4096, 64.0, 1.0, 1.4158883083359672),
        **_published_lists(40, 48, 64),
    ),
    # five layers f, w, w, w, f with two head counts, a window of 8 (a
    # rehearsal's chunk + prompt wrap the ring), 8 experts top-2 + shared, a
    # dense layer 0
    "tiny-laguna": LagunaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        rope_theta=10000.0, yarn=(4.0, 16, 8.0, 1.0, 1.1386294361119891),
        window_rope_theta=100.0, sliding_window=8,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=8, top_k=2, dtype=jnp.float32, remat="none",
        **_published_lists(5, 4, 6),
    ),
}


# ---------------------------------------------------------------------------
# rotary tables, masks, attention
# ---------------------------------------------------------------------------

def rope_tables(config: LagunaConfig, kind: str, max_len: int):
    """(sin, cos) of ``kind``'s layers, (max_len, rotary_dim(kind)), fp32."""
    c = config
    if kind == WINDOW:
        return precompute_rope(c.head_dim, max_len, c.window_rope_theta)
    if c.yarn is None:
        return precompute_rope(c.rotary_dim(FULL), max_len, c.rope_theta)
    factor, original, beta_fast, beta_slow, attention_factor = c.yarn
    # the blend of ``yarn_rope`` is ``transformers``' (truncate on); its table
    # scale is left at 1 (equal mscales) and the published factor applied
    sin, cos = yarn_rope(
        c.rotary_dim(FULL), max_len, c.rope_theta,
        (factor, original, beta_fast, beta_slow, 1.0, 1.0),
    )
    return sin * attention_factor, cos * attention_factor


def rotate(x: jax.Array, sin, cos, positions) -> jax.Array:
    """Rotate the first ``sin.shape[-1]`` dimensions of each head of x
    (b, t, n, d) by position; the rest pass through."""
    r = sin.shape[-1]
    if r == x.shape[-1]:
        return apply_rope(x, sin, cos, positions)
    return jnp.concatenate([apply_rope(x[..., :r], sin, cos, positions), x[..., r:]], axis=-1)


def visible(q_pos: jax.Array, k_pos: jax.Array, window: Optional[int]) -> jax.Array:
    """Which keys a query sees: ``0 <= k_pos <= q_pos`` and, for a window
    layer, ``q_pos - k_pos < window``. q_pos (b, t), k_pos (b, t, s) or
    broadcastable to it; returns (b, t, s) bool."""
    q = q_pos[..., None]
    seen = (k_pos >= 0) & (k_pos <= q)
    if window is not None:
        seen &= q - k_pos < window
    return seen


def masked_attention(q, k, v, mask) -> jax.Array:
    """softmax(q·k / sqrt(d), keys where ``mask``) · v. q (b, t, N, d); k, v
    (b, s, NKV, d); mask (b | 1, t, s) bool. Grouped-query einsums (no repeat
    of k and v), softmax in float32, as ``LlamaDecode._cache_attention``."""
    b, t, n, d = q.shape
    s, nkv = k.shape[1], k.shape[2]
    g = n // nkv
    ha = _head_axis(n)
    qg = q.reshape(b, t, nkv, g, d)
    scores = jnp.einsum("bskd,btkgd->bkgts", k, qg) * (d ** -0.5)
    scores = constrain(scores.reshape(b, n, t, s), P(BATCH_AXES, ha, None, None))
    scores = jnp.where(mask[:, None], scores.astype(jnp.float32), jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.reshape(b, nkv, g, t, s), v)
    return constrain(out.reshape(b, t, n, d), P(BATCH_AXES, None, ha, None))


@dataclasses.dataclass(frozen=True)
class LagunaAttention:
    """The attention block of one kind: :class:`..llama.LlamaAttention`'s
    fused q/k/v and ``o`` projections at the kind's query-head count, the
    kind's rotary table, and the per-head output gate. Scopes: ``attn/full``
    or ``attn/window`` around the block's usual children, and
    ``attn/out_gate``."""

    config: LagunaConfig
    kind: str = FULL

    @property
    def heads(self) -> int:
        return self.config.heads_of(self.kind)

    def _llama(self) -> LlamaAttention:
        return LlamaAttention(dataclasses.replace(self.config, num_heads=self.heads))

    def init(self, key: jax.Array) -> Params:
        c = self.config
        params = self._llama().init(key)
        params["out_gate"] = {"kernel": default_kernel_init(
            jax.random.fold_in(key, 7), (c.hidden_size, self.heads), c.dtype)}
        return params

    def specs(self) -> Params:
        specs = self._llama().specs()
        # by query head, like q: tp 2, 4 and 8 divide 48, 64 and 8
        specs["out_gate"] = {"kernel": P(None, TP_AXIS)}
        return specs

    def project(self, params: Params, h: jax.Array, sin, cos, positions):
        """q (b, t, N, d), k, v (b, t, NKV, d) of h (b, t, H), q and k
        rotated by the kind's table (``sin`` None: a kind that carries no
        position, :mod:`.smallthinker`'s full layers)."""
        c = self.config
        b, t, _ = h.shape
        with jax.named_scope("qkv"):
            q, k, v = self._llama()._qkv()(params["qkv"], h)
            q = q.reshape(b, t, self.heads, c.head_dim)
            k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
            v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
        if sin is None:
            return q, k, v
        with jax.named_scope("rope"):
            return rotate(q, sin, cos, positions), rotate(k, sin, cos, positions), v

    def output(self, params: Params, h: jax.Array, att: jax.Array) -> jax.Array:
        """Gate each head of att (b, t, N, d) by the layer's normed input h,
        then ``W_o``."""
        b, t = att.shape[:2]
        with jax.named_scope("out_gate"):
            gate = jax.nn.sigmoid((h @ params["out_gate"]["kernel"]).astype(jnp.float32))
            att = att * gate.astype(att.dtype)[..., None]
        with jax.named_scope("o_proj"):
            return self._llama()._o()(params["o"], att.reshape(b, t, -1))

    def window(self) -> Optional[int]:
        return self.config.sliding_window if self.kind == WINDOW else None

    def __call__(self, params, h, sin, cos, positions):
        """The whole block over h's own rows (training, the reference's
        shape): every layer at full length, the window a mask."""
        with jax.named_scope("attn"), jax.named_scope(self.kind):
            q, k, v = self.project(params, h, sin, cos, positions)
            with jax.named_scope("sdpa"):
                att = masked_attention(
                    q, k, v, visible(positions, positions[:, None, :], self.window()))
            return self.output(params, h, att)


# ---------------------------------------------------------------------------
# layers, stacks and the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LagunaDecoderLayer:
    """Pre-norm block: attention of ``kind``, then the dense SwiGLU MLP or
    the expert block."""

    config: LagunaConfig
    kind: str = FULL
    sparse: bool = True

    def _attn(self) -> LagunaAttention:
        return LagunaAttention(self.config, self.kind)

    def _ffn(self):
        return MoE(self.config.moe_config()) if self.sparse else LlamaMLP(self.config)

    def _name(self) -> str:
        return "moe" if self.sparse else "mlp"

    def init(self, key: jax.Array) -> Params:
        ka, km = jax.random.split(key)
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.init(key), "attn": self._attn().init(ka),
            "mlp_norm": norm.init(key), self._name(): self._ffn().init(km),
        }

    def specs(self) -> Params:
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.specs(), "attn": self._attn().specs(),
            "mlp_norm": norm.specs(), self._name(): self._ffn().specs(),
        }

    def ffn(self, params: Params, h: jax.Array):
        """(y, aux): the feed-forward of the normed h, and the layer's
        load-balancing loss (0 for a dense layer)."""
        if not self.sparse:
            return LlamaMLP(self.config)(params["mlp"], h), jnp.zeros((), jnp.float32)
        y, router_logits, idx = self._ffn()(params["moe"], h)
        return y, load_balancing_loss(router_logits, idx, self.config.num_experts)

    def __call__(self, params, x, sin, cos, positions):
        norm = make_norm(self.config)
        x = x + self._attn()(params["attn"], norm(params["attn_norm"], x), sin, cos, positions)
        y, aux = self.ffn(params, norm(params["mlp_norm"], x))
        return x + y, aux


def stack_name(kind: str, sparse: bool) -> str:
    return f"{kind}_layers" if sparse else f"{kind}_dense_layers"


class Run(NamedTuple):
    """Consecutive layers of one stack: ``count`` layers from the stack's
    ``first``; ``kind_first`` is the first one's index among the layers of
    its kind (a cache of that kind's layer index), ``layer`` its index in
    the model."""

    stack: str
    kind: str
    sparse: bool
    first: int
    count: int
    kind_first: int
    layer: int


def layer_runs(config: LagunaConfig) -> List[Run]:
    """The published order as runs of consecutive layers of one stack."""
    runs: List[Run] = []
    in_stack: Dict[str, int] = {}
    in_kind: Dict[str, int] = {}
    for layer, (kind, mlp) in enumerate(zip(config.kinds, config.mlp_layer_types)):
        sparse = mlp == "sparse"
        name = stack_name(kind, sparse)
        if runs and runs[-1].stack == name:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(Run(name, kind, sparse, in_stack.get(name, 0), 1,
                            in_kind.get(kind, 0), layer))
        in_stack[name] = in_stack.get(name, 0) + 1
        in_kind[kind] = in_kind.get(kind, 0) + 1
    return runs


def stack_sizes(config: LagunaConfig) -> Dict[str, Tuple[str, bool, int]]:
    """stack name -> (kind, sparse, layers), in order of first appearance."""
    sizes: Dict[str, Tuple[str, bool, int]] = {}
    for run in layer_runs(config):
        kind, sparse, n = sizes.get(run.stack, (run.kind, run.sparse, 0))
        sizes[run.stack] = (kind, sparse, n + run.count)
    return sizes


def scan_run(body, carry, stack: Params, run: Run):
    """``carry, ys = body(carry, layer params, j)`` over the layers of
    ``run``, ``j`` counting from 0: a scan over the stack's indices, each
    layer's weights sliced out of the stack inside the loop as a scan's own
    ``xs`` are — a slice of the stack ahead of the loop would be a copy of
    it."""

    def step(carry, j):
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, run.first + j, 0, keepdims=False), stack)
        return body(carry, lp, j)

    return lax.scan(step, carry, jnp.arange(run.count, dtype=jnp.int32))


@dataclasses.dataclass(frozen=True)
class LagunaForCausalLM:
    """Same protocol as :class:`..mixtral.MixtralForCausalLM`
    (init/specs/__call__/loss); the weights are one stack a layer shape."""

    config: LagunaConfig

    def _llama(self) -> LlamaForCausalLM:
        return LlamaForCausalLM(self.config)     # embed / head / final norm / loss tail

    def _embed(self):
        return self._llama()._embed()

    def _norm(self):
        return self._llama()._norm()

    def _logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        return self._llama()._logits(params, hidden)

    def _layer(self, kind: str, sparse: bool):
        """The decoder layer of one stack (a family on this class gives its own)."""
        return LagunaDecoderLayer(self.config, kind, sparse)

    def _ropes(self, s: int) -> Dict[str, Tuple[jax.Array, jax.Array]]:
        """A (sin, cos) pair a kind."""
        return {kind: rope_tables(self.config, kind, s) for kind in (FULL, WINDOW)}

    def init(self, key: jax.Array) -> Params:
        c = self.config
        ke, kl, kh = jax.random.split(key, 3)
        params = {"embed": self._embed().init(ke), "final_norm": self._norm().init(kh)}
        for i, (name, (kind, sparse, count)) in enumerate(stack_sizes(c).items()):
            keys = jax.random.split(jax.random.fold_in(kl, i), count)
            params[name] = jax.vmap(self._layer(kind, sparse).init)(keys)
        if not c.tie_word_embeddings:
            params["lm_head"] = self._llama()._lm_head().init(kh)
        return params

    def specs(self) -> Params:
        c = self.config
        specs = {"embed": self._embed().specs(), "final_norm": self._norm().specs()}
        for name, (kind, sparse, _) in stack_sizes(c).items():
            specs[name] = jax.tree.map(
                lambda s: P(None, *s), self._layer(kind, sparse).specs(),
                is_leaf=lambda s: isinstance(s, P),
            )
        if not c.tie_word_embeddings:
            specs["lm_head"] = self._llama()._lm_head().specs()
        return specs

    def _backbone(self, params: Params, input_ids: jax.Array):
        """Embed + the layers in the published order + final norm: (hidden,
        mean aux loss of the expert layers)."""
        c = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        ropes = self._ropes(s)
        x = self._embed()(params["embed"], input_ids)
        aux, sparse_layers = jnp.zeros((), jnp.float32), 0
        for run in layer_runs(c):
            layer = self._layer(run.kind, run.sparse)
            sin, cos = ropes[run.kind]
            x, auxes = scan_run(
                lambda x, lp, _: layer(lp, x, sin, cos, positions), x, params[run.stack], run)
            if run.sparse:
                aux, sparse_layers = aux + jnp.sum(auxes), sparse_layers + run.count
        return self._norm()(params["final_norm"], x), aux / max(sparse_layers, 1)

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        return self._logits(params, self._backbone(params, input_ids)[0])

    def loss_from_hidden(self, params, hidden, labels):
        return self._llama().loss_from_hidden(params, hidden, labels)

    def loss(self, params: Params, input_ids: jax.Array, labels: jax.Array) -> jax.Array:
        hidden, aux = self._backbone(params, input_ids)
        return self.loss_from_hidden(params, hidden, labels) + self.config.router_aux_loss_coef * aux


# ---------------------------------------------------------------------------
# HF names
# ---------------------------------------------------------------------------

def _hf_layer_leaves(config: LagunaConfig, layer: int, sparse: bool, out_gate: bool = True):
    """(path in a layer's params, HF name, the map between torch's layout and
    ours — its own inverse) of one layer's leaves but the feed-forward's;
    ``out_gate``: whether the layer has one (a family on this file's stacks
    without it, :mod:`.smallthinker`). The catalog publishes the configuration and no tensor names:
    these are the Qwen-MoE lineage's, whose keys the configuration uses
    (``mlp.gate`` the router, ``mlp.shared_expert``), the output gate as
    ``self_attn.g_proj``. Linear weights are torch's (out, in)."""
    p = f"model.layers.{layer}."
    t = lambda w: w.T  # noqa: E731
    same = lambda w: w  # noqa: E731
    rows = [
        (("attn_norm", "scale"), p + "input_layernorm.weight", same),
        (("mlp_norm", "scale"), p + "post_attention_layernorm.weight", same),
        (("attn", "qkv", "q_kernel"), p + "self_attn.q_proj.weight", t),
        (("attn", "qkv", "k_kernel"), p + "self_attn.k_proj.weight", t),
        (("attn", "qkv", "v_kernel"), p + "self_attn.v_proj.weight", t),
        (("attn", "o", "kernel"), p + "self_attn.o_proj.weight", t),
    ]
    if out_gate:
        rows.append((("attn", "out_gate", "kernel"), p + "self_attn.g_proj.weight", t))
    if sparse:
        rows.append((("moe", "router", "kernel"), p + "mlp.gate.weight", t))
    return rows


def _hf_swiglu_names(prefix: str):
    return tuple(f"{prefix}.{n}_proj.weight" for n in ("gate", "up", "down"))


def params_to_hf_laguna(params: Params, config: LagunaConfig) -> Dict[str, Any]:
    """Stacked pytree -> a ``state_dict`` under the names of
    :func:`_hf_layer_leaves` (numpy fp32, torch (out, in) layout). An output
    gate and a shared expert are written where the layer has them."""
    import numpy as np

    def np32(x):
        return np.asarray(x, dtype=np.float32)

    def swiglu(sd, prefix, gate_up, down):      # (H, 2, I), (I, H)
        g, u, d = _hf_swiglu_names(prefix)
        sd[g], sd[u], sd[d] = gate_up[:, 0].T, gate_up[:, 1].T, down.T

    sd: Dict[str, Any] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
    }
    if not config.tie_word_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]["kernel"]).T
    for run in layer_runs(config):
        stack = jax.tree.map(np32, params[run.stack])
        for j in range(run.count):
            layer, lp = run.layer + j, jax.tree.map(lambda a: a[run.first + j], stack)
            for path, name, to_ours in _hf_layer_leaves(
                    config, layer, run.sparse, "out_gate" in lp["attn"]):
                leaf = lp
                for key in path:
                    leaf = leaf[key]
                sd[name] = to_ours(leaf)        # a transpose is its own inverse
            mlp = f"model.layers.{layer}.mlp"
            if not run.sparse:
                swiglu(sd, mlp, lp["mlp"]["gate_up"], lp["mlp"]["down"]["kernel"])
                continue
            if "shared" in lp["moe"]:
                swiglu(sd, mlp + ".shared_expert", lp["moe"]["shared"]["gate_up"], lp["moe"]["shared"]["down"])
            for e in range(config.num_experts):
                swiglu(sd, f"{mlp}.experts.{e}", lp["moe"]["experts"]["gate_up"][e],
                       lp["moe"]["experts"]["down"][e])
    return sd


def params_from_hf_laguna(state_dict: Dict[str, Any], config: LagunaConfig) -> Params:
    """Inverse of :func:`params_to_hf_laguna` (an output gate and a shared
    expert read where the ``state_dict`` names them)."""
    import numpy as np

    def t(name):
        w = state_dict[name]
        if hasattr(w, "detach"):
            w = w.detach().cpu().numpy()
        return np.asarray(w, dtype=np.float32)

    def swiglu(prefix):
        g, u, d = (t(n) for n in _hf_swiglu_names(prefix))
        return np.stack([g.T, u.T], axis=1), d.T

    def layer_params(layer: int, sparse: bool):
        lp: Params = {}
        gated = f"model.layers.{layer}.self_attn.g_proj.weight" in state_dict
        for path, name, to_ours in _hf_layer_leaves(config, layer, sparse, gated):
            at = lp
            for key in path[:-1]:
                at = at.setdefault(key, {})
            at[path[-1]] = to_ours(t(name))
        mlp = f"model.layers.{layer}.mlp"
        if not sparse:
            gate_up, down = swiglu(mlp)
            lp["mlp"] = {"gate_up": gate_up, "down": {"kernel": down}}
            return lp
        experts = [swiglu(f"{mlp}.experts.{e}") for e in range(config.num_experts)]
        lp["moe"]["experts"] = {
            "gate_up": np.stack([g for g, _ in experts]), "down": np.stack([d for _, d in experts])}
        if _hf_swiglu_names(mlp + ".shared_expert")[0] in state_dict:
            shared = swiglu(mlp + ".shared_expert")
            lp["moe"]["shared"] = {"gate_up": shared[0], "down": shared[1]}
        return lp

    def typed(path, a):
        norm = path[-1].key == "scale"
        return jnp.asarray(a, jnp.float32 if norm else config.dtype)

    params: Params = {
        "embed": {"embedding": t("model.embed_tokens.weight")},
        "final_norm": {"scale": t("model.norm.weight")},
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = {"kernel": t("lm_head.weight").T}
    layers: Dict[str, List[Params]] = {}
    for run in layer_runs(config):
        layers.setdefault(run.stack, []).extend(
            layer_params(run.layer + j, run.sparse) for j in range(run.count))
    for name, each in layers.items():
        params[name] = jax.tree.map(lambda *a: np.stack(a), *each)
    return jax.tree_util.tree_map_with_path(typed, params)
