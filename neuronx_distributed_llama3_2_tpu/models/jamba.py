"""Jamba model family (``ai21labs/AI21-Jamba2-3B``, HF ``model_type: jamba``),
TPU-native: a stack of **Mamba-1 state-space layers** with an attention layer
every ``attn_layer_period`` (layer ``i`` is attention where ``i mod period ==
offset``), a dense SwiGLU after every mixer, a tied head — and **no positional
term of any kind**: the attention layers apply no rotary embedding, the
state-space layers carry the order.

A block is ``x ← x + mixer(RMSNorm(x))``, ``x ← x + SwiGLU(RMSNorm(x))``.

*The Mamba mixer*, for token ``t`` with normed input ``x_t`` (``D`` =
``mamba_expand · hidden``, ``N`` = ``mamba_d_state``, ``R`` = ``mamba_dt_rank``,
``K`` = ``mamba_d_conv``):

- ``[u_t ‖ g_t] = W_in x_t``;
- ``c_t = SiLU(b_conv + Σ_j w_conv[j] ⊙ u_{t−K+1+j})`` — depthwise, causal,
  zeros before the first token (:func:`conv_rows`);
- ``[δ_t ‖ B_t ‖ C_t] = W_x c_t`` (R, N, N), **each through an RMSNorm with a
  learned scale** (the Jamba family's addition to Mamba-1), then
  ``Δ_t = softplus(W_dt δ_t + b_dt)`` (:func:`ssm_params`);
- ``h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ c_t) ⊗ B_t`` with ``A = −exp(A_log)``,
  ``y_t = h_t C_t + D ⊙ c_t`` (:func:`selective_scan`, :func:`selective_step`);
- the output is ``W_out (y_t ⊙ SiLU(g_t))``.

What a sequence leaves behind a Mamba layer is ``h`` — held ``(N, D)``, the
wide axis minor, so a float32 state tiles the device's (8, 128) without padding
— and the convolution's tail ``u_{t−K+2..t}``. The decay is per channel *and*
per state, so the scan has no matmul form: every form here is a loop over
time with ``h`` as its carry, and no ``(rows, N, D)`` array exists. A block of
rows is one Mosaic call a layer where the caller asks for the kernel
(:func:`..kernels.ssm_scan_pallas.ssm_chunk_scan`), else a ``lax.scan``.

The training-side model (:class:`JambaForCausalLM`) makes the weights and
runs the whole sequence from the zero state; the serving engines run
:class:`..inference.model.JambaDecode` over a
:class:`..inference.model.HybridCache`. ``tp > 1`` is specs only (the Mamba
leaves replicated): not run on a chip.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.laguna import Run, scan_run
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    LlamaForCausalLM,
    LlamaMLP,
    make_norm,
)
from neuronx_distributed_llama3_2_tpu.parallel.layers import default_kernel_init

Params = Dict[str, Any]

MAMBA, ATTENTION = "mamba", "attention"
# what a serving state accumulates in: a recurrence sums thousands of updates
# (published hybrid configs state the same, ``mamba_ssm_dtype: float32``)
STATE_DTYPE = jnp.float32
# rows of a chunk's scan a loop trip runs as straight-line code
SCAN_UNROLL = 8
# the family's addition to Mamba-1: Δ's input, B and C each pass through an
# RMSNorm with a learned scale, in this order
INNER_NORMS = ("dt_norm", "b_norm", "c_norm")
# Mamba-1's published initialisation of what sets a state's memory: the step
# Δ is drawn log-uniform in this range (its bias is the inverse softplus)
DT_INIT_RANGE = (1e-3, 1e-1)


@dataclasses.dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    """LlamaConfig with the family's keys. Which layer is which follows from
    ``attn_layer_period`` / ``attn_layer_offset``; every feed-forward is the
    dense SwiGLU (``num_experts`` 1: the family builds a sparse block only
    where there are several). ``rope_theta`` means nothing here."""

    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset else MAMBA
            for i in range(self.num_layers)
        )

    def layers_of(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    def state_bytes_per_layer(self, tail_dtype: Any = None) -> int:
        """Bytes one sequence leaves behind one Mamba layer: ``h`` (N, D) in
        :data:`STATE_DTYPE` and the convolution's tail (K − 1, D)."""
        tail = jnp.dtype(tail_dtype or self.dtype).itemsize
        return self.d_inner * (
            self.mamba_d_state * jnp.dtype(STATE_DTYPE).itemsize
            + (self.mamba_d_conv - 1) * tail
        )


JAMBA_CONFIGS: Dict[str, JambaConfig] = {
    # ai21labs/AI21-Jamba2-3B config.json values
    "jamba2-3b": JambaConfig(
        vocab_size=65536, hidden_size=2560, intermediate_size=8192,
        num_layers=28, num_heads=20, num_kv_heads=1, head_dim=128,
        max_seq_len=262144,
    ),
    # both kinds twice, an attention layer first in neither run: M A M M A
    "tiny-jamba": JambaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=1, head_dim=16,
        attn_layer_period=3, attn_layer_offset=1,
        mamba_d_state=8, mamba_dt_rank=8,
        max_seq_len=128, dtype=jnp.float32, remat="none",
    ),
}


# ---------------------------------------------------------------------------
# the mixer's pieces: every one takes a batch of sequences
# ---------------------------------------------------------------------------

def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def conv_rows(weight, bias, tail, u, live):
    """The causal depthwise convolution over a block of rows. weight (K, D),
    bias (D,) float32; tail (b, K − 1, D) — the rows before the block, zeros
    at a sequence's start — u (b, t, D); ``live`` (b,) the count of real rows.
    Returns (c (b, t, D) in u's dtype, the tail after the last real row, in
    the dtype the old one came in): rows at or past ``live`` are padding and
    never enter it."""
    k = weight.shape[0]
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)       # (b, K-1+t, D)
    t = u.shape[1]
    acc = bias.astype(jnp.float32)
    for j in range(k):
        acc = acc + weight[j].astype(jnp.float32) * full[:, j:j + t].astype(jnp.float32)
    # u's row i is full's row K-1+i: the last K-1 real rows start at `live`
    if t == 1:      # the tail moves up by the one row, or stays: a select, where a slice a lane is a gather
        new_tail = jnp.where((live > 0)[:, None, None], full[:, 1:], full[:, :k - 1])
    else:
        new_tail = jax.vmap(lambda f, n: lax.dynamic_slice_in_dim(f, n, k - 1, axis=0))(full, live)
    return jax.nn.silu(acc).astype(u.dtype), new_tail.astype(tail.dtype)


def ssm_params(params: Params, c: jax.Array, config: JambaConfig):
    """c (b, t, D) -> Δ (b, t, D), B and C (b, t, N), float32: ``x_proj``,
    the three inner norms, ``dt_proj`` and the softplus."""
    n, r = config.mamba_d_state, config.mamba_dt_rank
    f32 = jnp.float32
    xdbc = jnp.einsum("btd,dr->btr", c, params["x_proj"]["kernel"], preferred_element_type=f32)
    parts = (xdbc[..., :r], xdbc[..., r:r + n], xdbc[..., r + n:])
    delta, b_t, c_t = (
        _rms(part, params[name]["scale"], config.rms_norm_eps)
        for name, part in zip(INNER_NORMS, parts)
    )
    dt = jnp.einsum(
        "btr,rd->btd", delta.astype(c.dtype), params["dt_proj"]["kernel"],
        preferred_element_type=f32)
    return jax.nn.softplus(dt + params["dt_proj"]["bias"]), b_t, c_t


def selective_step(h, delta, c, b_t, c_t, a, d_skip):
    """One token a sequence. h (b, N, D); delta, c (b, D); b_t, c_t (b, N);
    a (N, D) = −exp(A_log); d_skip (D,). The state is computed in float32 and
    goes on in the dtype it came in. Returns (y (b, D) float32, h)."""
    f32 = jnp.float32
    c = c.astype(f32)
    decay = jnp.exp(delta[:, None, :] * a)
    new = decay * h.astype(f32) + (delta * c)[:, None, :] * b_t[:, :, None]
    new = new.astype(h.dtype)
    y = jnp.sum(new.astype(f32) * c_t[:, :, None], axis=1) + d_skip * c
    return y, new


def selective_scan(h, delta, c, b_t, c_t, a, d_skip, live, kernel=False):
    """A block of rows a sequence, one row after another with ``h`` (b, N, D)
    as the carry. delta, c (b, t, D); b_t, c_t (b, t, N); ``live`` (b,): a row
    at or past it has Δ = 0 — decay 1, nothing added — and leaves the state
    as it was (its output means nothing). ``kernel``: the loop is one Mosaic
    call where the block is whole trips of it, else a ``lax.scan``. Returns
    (y (b, t, D) float32, h)."""
    # importing Pallas for the TPU starts the backend: not at this module's import
    from neuronx_distributed_llama3_2_tpu.kernels.ssm_scan_pallas import (
        chunk_scan_fits,
        ssm_chunk_scan,
    )

    t = delta.shape[1]
    alive = lax.iota(jnp.int32, t)[None, :] < live[:, None]
    delta = jnp.where(alive[..., None], delta, 0.0)
    if kernel and chunk_scan_fits(t, delta.shape[2]):
        c = c.astype(jnp.float32)
        y, h = ssm_chunk_scan(h, delta, delta * c, b_t, c_t, a, live)
        return y + d_skip * c, h

    def row(h, xs):
        y, h = selective_step(h, *xs, a, d_skip)
        return h, y

    rows = tuple(jnp.swapaxes(x, 0, 1) for x in (delta, c, b_t, c_t))   # time first
    h, y = lax.scan(row, h, rows, unroll=min(SCAN_UNROLL, t))
    return jnp.swapaxes(y, 0, 1), h


# ---------------------------------------------------------------------------
# the blocks and the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MambaMixer:
    """The Mamba-1 mixer with the family's three inner norms. Scopes, under
    the block's ``attn/ssm`` (the two projections sit under it bare):
    ``conv``, ``params``, ``scan`` (a block of rows) or ``step`` (one token a
    lane)."""

    config: JambaConfig

    def init(self, key: jax.Array) -> Params:
        c = self.config
        d, n, r, h = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.hidden_size
        k_in, k_conv, k_x, k_dt, k_bias, k_out = jax.random.split(key, 6)
        lo, hi = (math.log(v) for v in DT_INIT_RANGE)
        dt = jnp.exp(jax.random.uniform(k_bias, (d,), jnp.float32, lo, hi))
        ones = lambda width: {"scale": jnp.ones((width,), jnp.float32)}  # noqa: E731
        return {
            "in_proj": {"kernel": default_kernel_init(k_in, (h, 2 * d), c.dtype)},
            "conv": {"kernel": default_kernel_init(k_conv, (c.mamba_d_conv, d), jnp.float32),
                     "bias": jnp.zeros((d,), jnp.float32)},
            "x_proj": {"kernel": default_kernel_init(k_x, (d, r + 2 * n), c.dtype)},
            "dt_norm": ones(r), "b_norm": ones(n), "c_norm": ones(n),
            # softplus(bias) = dt: the inverse softplus
            "dt_proj": {"kernel": default_kernel_init(k_dt, (r, d), c.dtype),
                        "bias": dt + jnp.log(-jnp.expm1(-dt))},
            # held (N, D), the state's own layout; HF's is (D, N)
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, d)),
            "d_skip": jnp.ones((d,), jnp.float32),
            "out_proj": {"kernel": default_kernel_init(k_out, (d, h), c.dtype)},
        }

    def specs(self) -> Params:
        return jax.tree.map(lambda a: P(*(None,) * a.ndim), jax.eval_shape(self.init, jax.random.key(0)))

    def project(self, params: Params, x: jax.Array):
        """x (b, t, H) -> u, g (b, t, D)."""
        return jnp.split(x @ params["in_proj"]["kernel"], 2, axis=-1)

    def mix(self, params: Params, u, g, h, tail, live, kernel=False, walk=None):
        """Rows u, g (b, t, D) from the carried ``h`` (b, N, D) and ``tail``
        (b, K − 1, D), ``live`` (b,) of them real: (the mixer's output
        (b, t, H), h, tail). One token a lane runs the step form; ``kernel``
        is :func:`selective_scan`'s. ``walk`` — (slot, lane, count) — says
        that ``h`` is the whole pool as one run of slots and the step form
        runs in it, in place, a live lane's slot a visit
        (:func:`..kernels.ssm_step_pallas.ssm_step_paged`)."""
        # importing Pallas for the TPU starts the backend: not at this module's import
        from neuronx_distributed_llama3_2_tpu.kernels.ssm_step_pallas import ssm_step_paged

        cfg = self.config
        with jax.named_scope("conv"):
            c, tail = conv_rows(params["conv"]["kernel"], params["conv"]["bias"], tail, u, live)
        with jax.named_scope("params"):
            delta, b_t, c_t = ssm_params(params, c, cfg)
        a = -jnp.exp(params["a_log"])
        if u.shape[1] == 1:
            row = (c[:, 0], b_t[:, 0], c_t[:, 0], a, params["d_skip"])
            with jax.named_scope("step"):
                if walk is None:
                    # a lane with no live row: Δ = 0 leaves its state as it was
                    y, h = selective_step(h, jnp.where((live > 0)[:, None], delta[:, 0], 0.0), *row)
                else:       # a lane with no live row is not visited
                    y, h = ssm_step_paged(h, *walk, live > 0, delta[:, 0], *row)
                y = y[:, None]
        else:
            with jax.named_scope("scan"):
                y, h = selective_scan(
                    h, delta, c, b_t, c_t, a, params["d_skip"], live, kernel)
        out = (y.astype(g.dtype) * jax.nn.silu(g)) @ params["out_proj"]["kernel"]
        return out, h, tail

    def zero_state(self, batch: int, dtype: Any = None, tail_dtype: Any = None):
        c = self.config
        return (
            jnp.zeros((batch, c.mamba_d_state, c.d_inner), dtype or STATE_DTYPE),
            jnp.zeros((batch, c.mamba_d_conv - 1, c.d_inner), tail_dtype or c.dtype),
        )

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        """The whole sequence from the zero state (training, parity)."""
        with jax.named_scope("attn"), jax.named_scope("ssm"):
            u, g = self.project(params, x)
            live = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
            return self.mix(params, u, g, *self.zero_state(x.shape[0]), live)[0]


@dataclasses.dataclass(frozen=True)
class JambaDecoderLayer:
    """One block of ``kind``; the feed-forward is :class:`..llama.LlamaMLP`."""

    config: JambaConfig
    kind: str

    def _mixer(self):
        return MambaMixer(self.config) if self.kind == MAMBA else LlamaAttention(self.config)

    def init(self, key: jax.Array) -> Params:
        k_mixer, k_mlp = jax.random.split(key)
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.init(key), self.kind: self._mixer().init(k_mixer),
            "mlp_norm": norm.init(key), "mlp": LlamaMLP(self.config).init(k_mlp),
        }

    def specs(self) -> Params:
        norm = make_norm(self.config)
        return {
            "attn_norm": norm.specs(), self.kind: self._mixer().specs(),
            "mlp_norm": norm.specs(), "mlp": LlamaMLP(self.config).specs(),
        }

    def __call__(self, params: Params, x: jax.Array, positions: jax.Array) -> jax.Array:
        norm = make_norm(self.config)
        h = norm(params["attn_norm"], x)
        if self.kind == MAMBA:
            x = x + self._mixer()(params[MAMBA], h)
        else:       # no rotary table: the block has no positional term
            x = x + self._mixer()(params[ATTENTION], h, None, None, positions)
        h = norm(params["mlp_norm"], x)
        return x + LlamaMLP(self.config)(params["mlp"], h)


def stack_name(kind: str) -> str:
    return f"{kind}_layers"


def layer_runs(config: JambaConfig, name=stack_name) -> List[Run]:
    """The published order (``config.layer_kinds``) as runs of consecutive
    layers of one kind (a kind is a stack of weights, ``name(kind)``:
    :class:`..laguna.Run`)."""
    runs: List[Run] = []
    seen: Dict[str, int] = {}
    for layer, kind in enumerate(config.layer_kinds):
        if runs and runs[-1].kind == kind:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            first = seen.get(kind, 0)
            runs.append(Run(name(kind), kind, False, first, 1, first, layer))
        seen[kind] = seen.get(kind, 0) + 1
    return runs


@dataclasses.dataclass(frozen=True)
class JambaForCausalLM:
    """Same protocol as :class:`..laguna.LagunaForCausalLM`
    (init/specs/__call__/loss); the weights are one stack a layer kind."""

    config: JambaConfig

    def _llama(self) -> LlamaForCausalLM:
        return LlamaForCausalLM(self.config)     # embed / head / final norm / loss tail

    def _embed(self):
        return self._llama()._embed()

    def _norm(self):
        return self._llama()._norm()

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
            params[stack_name(kind)] = jax.vmap(JambaDecoderLayer(c, kind).init)(keys)
        if not c.tie_word_embeddings:
            params["lm_head"] = self._llama()._lm_head().init(kh)
        return params

    def specs(self) -> Params:
        c = self.config
        specs = {"embed": self._embed().specs(), "final_norm": self._norm().specs()}
        for kind in self._kinds():
            specs[stack_name(kind)] = jax.tree.map(
                lambda s: P(None, *s), JambaDecoderLayer(c, kind).specs(),
                is_leaf=lambda s: isinstance(s, P),
            )
        if not c.tie_word_embeddings:
            specs["lm_head"] = self._llama()._lm_head().specs()
        return specs

    def _backbone(self, params: Params, input_ids: jax.Array) -> jax.Array:
        c = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = self._embed()(params["embed"], input_ids)
        for run in layer_runs(c):
            layer = JambaDecoderLayer(c, run.kind)
            x, _ = scan_run(
                lambda x, lp, _: (layer(lp, x, positions), None), x, params[run.stack], run)
        return self._norm()(params["final_norm"], x)

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        return self._logits(params, self._backbone(params, input_ids))

    def loss_from_hidden(self, params, hidden, labels):
        return self._llama().loss_from_hidden(params, hidden, labels)

    def loss(self, params: Params, input_ids: jax.Array, labels: jax.Array) -> jax.Array:
        return self.loss_from_hidden(params, self._backbone(params, input_ids), labels)


# ---------------------------------------------------------------------------
# HF names (transformers' ``JambaForCausalLM``)
# ---------------------------------------------------------------------------

def _hf_layer_leaves(layer: int, kind: str):
    """(path in a layer's params, HF name, torch's layout -> ours, ours ->
    torch's) of one layer's leaves but the feed-forward's. Linear weights are
    torch's (out, in); ``conv1d.weight`` is (D, 1, K) and ``A_log`` (D, N),
    where ours are (K, D) and (N, D)."""
    p = f"model.layers.{layer}."
    t = lambda w: w.T  # noqa: E731
    same = lambda w: w  # noqa: E731
    rows = [
        (("attn_norm", "scale"), p + "input_layernorm.weight", same, same),
        (("mlp_norm", "scale"), p + "pre_ff_layernorm.weight", same, same),
    ]
    if kind == ATTENTION:
        for ours, theirs in (("q_kernel", "q_proj"), ("k_kernel", "k_proj"), ("v_kernel", "v_proj")):
            rows.append(((ATTENTION, "qkv", ours), f"{p}self_attn.{theirs}.weight", t, t))
        rows.append(((ATTENTION, "o", "kernel"), p + "self_attn.o_proj.weight", t, t))
        return rows
    m = p + "mamba."
    rows += [
        ((MAMBA, "in_proj", "kernel"), m + "in_proj.weight", t, t),
        ((MAMBA, "conv", "kernel"), m + "conv1d.weight", lambda w: w[:, 0, :].T, lambda w: w.T[:, None, :]),
        ((MAMBA, "conv", "bias"), m + "conv1d.bias", same, same),
        ((MAMBA, "x_proj", "kernel"), m + "x_proj.weight", t, t),
        ((MAMBA, "dt_norm", "scale"), m + "dt_layernorm.weight", same, same),
        ((MAMBA, "b_norm", "scale"), m + "b_layernorm.weight", same, same),
        ((MAMBA, "c_norm", "scale"), m + "c_layernorm.weight", same, same),
        ((MAMBA, "dt_proj", "kernel"), m + "dt_proj.weight", t, t),
        ((MAMBA, "dt_proj", "bias"), m + "dt_proj.bias", same, same),
        ((MAMBA, "a_log",), m + "A_log", t, t),
        ((MAMBA, "d_skip",), m + "D", same, same),
        ((MAMBA, "out_proj", "kernel"), m + "out_proj.weight", t, t),
    ]
    return rows


_HF_SWIGLU = tuple(f"feed_forward.{n}_proj.weight" for n in ("gate", "up", "down"))


def params_to_hf_jamba(params: Params, config: JambaConfig) -> Dict[str, Any]:
    """Stacked pytree -> a ``state_dict`` under transformers' names (numpy
    fp32, torch layouts). The head is tied: no ``lm_head.weight`` then."""
    import numpy as np

    np32 = lambda x: np.asarray(x, dtype=np.float32)  # noqa: E731
    sd: Dict[str, Any] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.final_layernorm.weight": np32(params["final_norm"]["scale"]),
    }
    if not config.tie_word_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]["kernel"]).T
    for run in layer_runs(config):
        stack = jax.tree.map(np32, params[run.stack])
        for j in range(run.count):
            layer, lp = run.layer + j, jax.tree.map(lambda a: a[run.first + j], stack)
            for path, name, _, to_torch in _hf_layer_leaves(layer, run.kind):
                leaf = lp
                for key in path:
                    leaf = leaf[key]
                sd[name] = to_torch(leaf)
            gate, up, down = (f"model.layers.{layer}.{n}" for n in _HF_SWIGLU)
            gate_up = lp["mlp"]["gate_up"]                      # (H, 2, I)
            sd[gate], sd[up] = gate_up[:, 0].T, gate_up[:, 1].T
            sd[down] = lp["mlp"]["down"]["kernel"].T
    return sd


def params_from_hf_jamba(state_dict: Dict[str, Any], config: JambaConfig) -> Params:
    """Inverse of :func:`params_to_hf_jamba`. Kernels come back in the
    configuration's dtype; norm scales, biases, the convolution, ``A_log`` and
    ``D`` in float32, as :meth:`MambaMixer.init` makes them."""
    import numpy as np

    def t(name):
        w = state_dict[name]
        if hasattr(w, "detach"):
            w = w.detach().cpu().numpy()
        return np.asarray(w, dtype=np.float32)

    def layer_params(layer: int, kind: str) -> Params:
        lp: Params = {}
        for path, name, to_ours, _ in _hf_layer_leaves(layer, kind):
            at = lp
            for key in path[:-1]:
                at = at.setdefault(key, {})
            at[path[-1]] = to_ours(t(name))
        gate, up, down = (t(f"model.layers.{layer}.{n}") for n in _HF_SWIGLU)
        lp["mlp"] = {"gate_up": np.stack([gate.T, up.T], axis=1), "down": {"kernel": down.T}}
        return lp

    params: Params = {
        "embed": {"embedding": t("model.embed_tokens.weight")},
        "final_norm": {"scale": t("model.final_layernorm.weight")},
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = {"kernel": t("lm_head.weight").T}
    layers: Dict[str, List[Params]] = {}
    for run in layer_runs(config):
        layers.setdefault(run.stack, []).extend(
            layer_params(run.layer + j, run.kind) for j in range(run.count))
    for name, each in layers.items():
        params[name] = jax.tree.map(lambda *a: np.stack(a), *each)
    like = jax.eval_shape(JambaForCausalLM(config).init, jax.random.key(0))
    return jax.tree.map(lambda a, spec: jnp.asarray(a, spec.dtype), params, like)
