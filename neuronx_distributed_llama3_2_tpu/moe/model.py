"""MoE top module: router → dispatch → (EP all-to-all) → experts → combine.

TPU-native replacement for the reference's ``modules/moe/model.py`` (``MoE``
:7): SP exit all-gather → flatten (S,B,H)→(T,H) → router → ExpertMLPs → SP
re-entry (:112-150), returning router logits for the load-balancing loss.

Execution has two paths:

- **ep == 1** (or uninitialized mesh): pure global math; GSPMD handles tp/dp
  from the weight specs.
- **ep > 1**: a partial-manual ``shard_map`` over (dp, ep) — tokens stay
  sharded, each shard dispatches its tokens into per-expert buffers, and the
  ``enter/exit_expert_parallel_region`` all-to-alls from
  :mod:`..parallel.mappings` (reference mappings.py:412-486) move token
  buffers to the ep-ranks that own the experts. tp stays GSPMD-auto inside
  the body (same hybrid technique as the pipeline executor). Capacity is
  computed on shard-local token counts, matching the reference's rank-local
  capacity semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.moe.experts import ACTIVATIONS, ExpertMLPs
from neuronx_distributed_llama3_2_tpu.moe.routing import (
    Router,
    sigmoid_bias_routing,
    sinkhorn_routing,
    top_k_routing,
)
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.mappings import (
    enter_expert_parallel_region,
    exit_expert_parallel_region,
)
from neuronx_distributed_llama3_2_tpu.parallel.state import (
    DP_AXIS,
    EP_AXIS,
    TP_AXIS,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    intermediate_size: int
    num_experts: int
    top_k: int = 2
    # None => all-experts path (no dropping); reference SELECTIVE_LOADING /
    # forward_all_experts dispatch (expert_mlps.py:298-357)
    capacity_factor: Optional[float] = None
    # "topk" (softmax, then the k largest, scaled by ``routed_scale``) |
    # "sinkhorn" | "sigmoid_bias" (sigmoid scores chosen by score + a learned
    # bias, gates renormalised and scaled by ``routed_scale``:
    # routing.sigmoid_bias_routing)
    routing: str = "topk"
    normalize_top_k: bool = True
    sinkhorn_iterations: int = 3
    routed_scale: float = 1.0
    glu: bool = True
    # the experts' (and the shared expert's) gate activation: "silu" (SwiGLU)
    # | "relu" (ReGLU)
    activation: str = "silu"
    dtype: Any = jnp.bfloat16
    # width of the shared expert(s) every token passes through beside its
    # routed ones (n shared experts of width w are one MLP of width n·w);
    # 0 = none
    shared_intermediate_size: int = 0
    # which of the router's ``num_experts`` this block holds: ids
    # ``first_held .. first_held + experts_held - 1`` (one rank's share of an
    # expert-parallel deployment, run alone). The router stays ``num_experts``
    # wide; a pair routed to an expert held elsewhere contributes nothing
    # here. None = all of them (every model but a cut deployment).
    experts_held: Optional[int] = None
    first_held: int = 0

    def __post_init__(self):
        if self.routing not in ("topk", "sinkhorn", "sigmoid_bias"):
            raise ValueError(
                f"routing must be topk|sinkhorn|sigmoid_bias, got {self.routing!r}"
            )
        if self.activation not in ACTIVATIONS:
            raise ValueError(
                f"activation must be one of {sorted(ACTIVATIONS)}, got {self.activation!r}"
            )
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError("need 1 <= top_k <= num_experts")
        if not (self.held >= 1 and 0 <= self.first_held <= self.num_experts - self.held):
            raise ValueError(
                f"held experts {self.first_held}..{self.first_held + self.held - 1} "
                f"are not among the router's {self.num_experts}"
            )

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held


@dataclasses.dataclass(frozen=True)
class MoE:
    """The MoE block. ``__call__(params, x (B,S,H))`` →
    ``(y (B,S,H), router_logits (T,E), expert_idx (T,k))``. A model that
    routes from another tensor than the one it dispatches (the layer's input,
    before attention) calls :meth:`route` on that one and hands the result to
    ``__call__(..., routes=)``."""

    config: MoEConfig
    # trace layout depends on global parallel state (shardlint SL002); valid
    # across re-init only because initialize/destroy_model_parallel clear
    # the jit cache (parallel/state.py)
    __layout_deps__ = (
        "get_expert_model_parallel_size", "get_parallel_state",
        "model_parallel_is_initialized",
    )

    def _router(self) -> Router:
        c = self.config
        return Router(
            c.hidden_size, c.num_experts, c.dtype,
            selection_bias=c.routing == "sigmoid_bias",
        )

    def _experts(self) -> ExpertMLPs:
        c = self.config
        return ExpertMLPs(
            num_experts=c.held,
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            capacity_factor=c.capacity_factor,
            glu=c.glu,
            dtype=c.dtype,
            activation=c.activation,
            routed_experts=c.num_experts,
            first_expert=c.first_held,
        )

    def init(self, key: jax.Array) -> Params:
        c = self.config
        kr, ke = jax.random.split(key)
        params = {
            "router": self._router().init(kr),
            "experts": self._experts().init(ke),
        }
        if c.shared_intermediate_size:
            kg, kd = jax.random.split(jax.random.fold_in(key, 2))
            h, i = c.hidden_size, c.shared_intermediate_size
            params["shared"] = {
                "gate_up": (jax.random.normal(kg, (h, 2, i), jnp.float32) * 0.02).astype(c.dtype),
                "down": (jax.random.normal(kd, (i, h), jnp.float32) * 0.02).astype(c.dtype),
            }
        return params

    def specs(self) -> Params:
        specs = {
            "router": self._router().specs(),
            "experts": self._experts().specs(),
        }
        if self.config.shared_intermediate_size:
            specs["shared"] = {"gate_up": P(None, None, TP_AXIS), "down": P(TP_AXIS, None)}
        return specs

    @jax.named_scope("shared")
    def _shared(self, params: Params, x_flat: jax.Array) -> jax.Array:
        """The shared expert: a gated MLP over every token, x (T, H)."""
        h1 = jnp.einsum("th,hui->tui", x_flat, params["gate_up"])
        return (ACTIVATIONS[self.config.activation](h1[:, 0]) * h1[:, 1]) @ params["down"]

    @jax.named_scope("router")
    def _route(self, router_params: Params, x_flat: jax.Array):
        c = self.config
        logits = self._router()(router_params, x_flat)
        if c.routing == "sinkhorn":
            gates, idx = sinkhorn_routing(
                logits, c.top_k, c.sinkhorn_iterations, c.normalize_top_k
            )
        elif c.routing == "sigmoid_bias":
            gates, idx = sigmoid_bias_routing(
                logits, router_params["bias"], c.top_k, c.routed_scale,
                c.normalize_top_k,
            )
        else:
            gates, idx = top_k_routing(logits, c.top_k, c.normalize_top_k)
            if c.routed_scale != 1.0:
                # only where a model states one: the others' programs keep
                # their HLO
                gates = gates * c.routed_scale
        return logits, gates, idx

    def _ep_size(self) -> int:
        if not parallel_state.model_parallel_is_initialized():
            return 1
        return parallel_state.get_expert_model_parallel_size()

    @jax.named_scope("moe")
    def route(self, params: Params, x: jax.Array):
        """(router_logits (T,E), gates (T,k), expert_idx (T,k)) of x (B,S,H),
        as ``__call__`` routes its own input — for a block that dispatches
        another tensor by them (``routes=``). Scope ``moe/router``."""
        return self._route(params["router"], x.reshape(-1, x.shape[-1]))

    # device-trace scopes (serving/tracing.py SCOPES): moe/router, moe/experts
    @jax.named_scope("moe")
    def __call__(
        self, params: Params, x: jax.Array, routes=None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """``routes``: what :meth:`route` returned for the tensor this block
        is routed by, where that is not ``x``; None routes ``x`` itself."""
        b, s, h = x.shape
        x_flat = x.reshape(b * s, h)  # (T, H) — reference flatten :112
        if self._ep_size() > 1:
            if routes is not None:
                raise NotImplementedError("routes made elsewhere under an ep > 1 mesh")
            y, logits, idx = self._ep_forward(params, x_flat)
        else:
            logits, gates, idx = (
                self._route(params["router"], x_flat) if routes is None else routes)
            y = self._experts()(params["experts"], x_flat, gates, idx)
        if self.config.shared_intermediate_size:
            y = y + self._shared(params["shared"], x_flat)
        return y.reshape(b, s, h), logits, idx

    # -- EP execution ------------------------------------------------------

    def _ep_forward(self, params: Params, x_flat: jax.Array):
        """shard_map over (dp, ep): dispatch shard-local tokens, all-to-all
        token buffers onto the expert-owning ep ranks (reference
        enter/exit_expert_parallel_region choreography, mappings.py:412-486 +
        Experts EP entry/exit, experts.py:121-152), run the local experts,
        all-to-all back, combine."""
        c = self.config
        if c.held != c.num_experts:
            raise NotImplementedError(
                "a held share of the experts is one rank run alone; under an "
                "ep > 1 mesh the mesh holds them all"
            )
        experts = self._experts()
        mesh = parallel_state.get_parallel_state().mesh
        # inside a partial-manual region (the pp pipeline stage) the nested
        # shard_map must target the ambient abstract mesh (its manual axes
        # are marked) — same rule as layers.constrain / parallel CE
        ambient = jax.sharding.get_abstract_mesh()
        if not ambient.empty:
            mesh = ambient
        t = x_flat.shape[0]
        dp_ep = mesh.shape[DP_AXIS] * mesh.shape[EP_AXIS]
        if t % dp_ep != 0:
            raise ValueError(
                f"token count {t} not divisible by dp*ep {dp_ep}"
            )

        # bf16 weights crossing the manual boundary abort XLA:CPU — shared
        # round-trip workaround (layers.shardmap_cpu_bf16_workaround)
        from neuronx_distributed_llama3_2_tpu.parallel.layers import (
            shardmap_cpu_bf16_workaround,
        )

        expert_params, restore_experts = shardmap_cpu_bf16_workaround(
            params["experts"]
        )

        if c.capacity_factor is None:
            # A no-drop EP dispatch must size every expert buffer for the
            # all-tokens-to-one-expert worst case: E× the necessary a2a bytes
            # and expert FLOPs. Refuse instead of silently collapsing
            # throughput; cf=num_experts/top_k already guarantees no dropping
            # under perfect balance and is the sane upper region.
            raise ValueError(
                "expert parallelism (ep > 1) requires a capacity_factor; "
                "capacity_factor=None (all-experts dispatch) would buffer "
                "T·top_k slots per expert. Set e.g. capacity_factor="
                f"{float(c.num_experts) / c.top_k:g} for a no-drop-at-balance "
                "budget."
            )

        def body(router_p, expert_p, xl):
            # xl: (T_loc, H) shard-local tokens
            expert_p = restore_experts(expert_p)
            logits, gates, idx = self._route(router_p, xl)
            cap = experts.capacity(xl.shape[0], c.top_k)
            with jax.named_scope("experts"):
                buf, slot, keep = experts.dispatch(xl, gates, idx, cap)
                # (E, C, H) -> (E/ep, ep·C, H): tokens travel to expert owners
                buf = enter_expert_parallel_region(buf)
                y = experts._mlp(expert_p, buf)
                # (E/ep, ep·C, H) -> (E, C, H): outputs return to token owners
                y = exit_expert_parallel_region(y)
                out = experts.combine(y, slot, keep, gates, xl.shape[0])
            return out, logits, idx

        token_spec = P((DP_AXIS, EP_AXIS))
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(),                      # router weights replicated
                P(EP_AXIS),               # expert dim manual over ep
                token_spec,               # tokens sharded over (dp, ep)
            ),
            out_specs=(token_spec, token_spec, token_spec),
            axis_names={DP_AXIS, EP_AXIS},
            check_vma=False,
        )(params["router"], expert_params, x_flat)
