"""Expert MLPs: fused 3D expert weights + static-shape dispatch paths.

TPU-native replacement for the reference's ``modules/moe/expert_mlps.py``
(``ExpertMLPs`` :13) and ``moe_parallel_layers.py`` (fused 3D
``ExpertFusedColumnParallelLinear`` :141 / ``...RowParallelLinear`` :227).

Weights are *global* 3D arrays with PartitionSpecs — expert dim over ``ep``,
intermediate dim over ``tp`` — instead of the reference's per-rank
``num_experts/ep``-sized locals (:166). Three forward paths mirror the
reference's dispatch (:298-357):

- ``forward_all_experts`` (:139): every token × every expert, no permutation —
  cheapest when T is small (token-gen).
- ``forward_capacity_factor`` (:169): static-shape token dropping. Capacity
  ``C = ceil(T·top_k·cf/E)``; position-in-expert via a cumsum over the
  token-major flattened assignment (the reference computes this cumsum with a
  tril matmul in fp64, tensor_utils.py — here a plain fp32 ``jnp.cumsum``,
  per SURVEY §7's fp64→fp32 substitution); tokens beyond capacity dropped;
  scatter to (E, C, H), batched expert einsum on the MXU, gather back and
  scale by gates.
- EP execution lives in :mod:`.model` (shard_map + all-to-all); the math here
  is mesh-agnostic global code usable inside or outside shard_map.

``gate_up`` is logically ``(E, H, 2, I)`` (stacked ``(L, E, H, 2, I)``) here,
in checkpoints and in training. The serving engines give it another
*physical* layout — major-to-minor ``(L, E, 2, H, I)``, the size-2 axis
ahead of the contraction axis (``inference/placement.py``): tiled by
default on a TPU, a second-minor axis of extent 2 makes the parameter
``T(2,128)``, and the einsum in :meth:`ExpertMLPs._mlp`, which wants ``(H, I)`` minor in
``T(8,128)``, re-tiles a whole layer (1.88 GB at Mixtral's widths) before
every matmul. At rest in the order the dot converts to, the layer scan's
slice fuses into the dot. Indexing (``gate_up[..., 0, :]``) is unchanged.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.moe import tap as routing_tap
from neuronx_distributed_llama3_2_tpu.parallel.state import EP_AXIS, TP_AXIS

Params = Dict[str, Any]

# what ``activation`` may name: the function on the gate branch of a gated
# expert (``down(act(gate) * up)``), on the only branch of an ungated one
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclasses.dataclass(frozen=True)
class ExpertMLPs:
    """Fused gate_up/down projections for E experts (SwiGLU; ReGLU where
    ``activation`` is ``"relu"``)."""

    num_experts: int
    hidden_size: int
    intermediate_size: int
    capacity_factor: Optional[float] = None  # None => all-experts path
    glu: bool = True
    dtype: Any = jnp.bfloat16
    activation: str = "silu"     # a key of ACTIVATIONS
    # a share of a wider router's experts (one rank of an expert-parallel
    # deployment run alone): the stack holds ids ``first_expert ..
    # first_expert + num_experts - 1`` of ``routed_experts``; a pair routed
    # to any other id contributes nothing here. None / 0: the stack is all
    # the router has.
    routed_experts: Optional[int] = None
    first_expert: int = 0

    def init(self, key: jax.Array) -> Params:
        e, h, i = self.num_experts, self.hidden_size, self.intermediate_size
        kg, kd = jax.random.split(key)
        scale = 0.02
        n_up = 2 if self.glu else 1
        gate_up = (
            jax.random.normal(kg, (e, h, n_up, i), jnp.float32) * scale
        ).astype(self.dtype)
        down = (
            jax.random.normal(kd, (e, i, h), jnp.float32) * scale
        ).astype(self.dtype)
        return {"gate_up": gate_up, "down": down}

    def specs(self) -> Params:
        """Expert dim over ep, intermediate over tp — the GSPMD equivalent of
        the reference's (e_local, in, out/tp) shards (moe_parallel_layers.py
        :141,:227 partition_dim tables)."""
        return {
            "gate_up": P(EP_AXIS, None, None, TP_AXIS),
            "down": P(EP_AXIS, TP_AXIS, None),
        }

    # -- expert math (shared by both dispatch paths) ----------------------

    def _mlp(self, params: Params, x: jax.Array) -> jax.Array:
        """Batched per-expert MLP: x (E, C, H) -> (E, C, H). One einsum pair
        over the whole expert batch → large MXU matmuls (reference einsum
        'e...h,ehi->e...i', moe_parallel_layers.py:13)."""
        h1 = jnp.einsum("ech,ehti->ecti", x, params["gate_up"])
        fn = ACTIVATIONS[self.activation]
        if self.glu:
            gate, up = h1[:, :, 0], h1[:, :, 1]
            act = fn(gate) * up
        else:
            act = fn(h1[:, :, 0])
        return jnp.einsum("eci,eio->eco", act, params["down"])

    # -- dispatch paths ----------------------------------------------------

    def forward_all_experts(
        self, params: Params, x: jax.Array, gates: jax.Array, idx: jax.Array
    ) -> jax.Array:
        """Every token through every expert, combine by gate (reference
        forward_all_experts expert_mlps.py:139). x (T,H), gates/idx (T,k)."""
        t = x.shape[0]
        xb = jnp.broadcast_to(x, (self.num_experts, t, x.shape[1]))
        y_all = self._mlp(params, xb)  # (E, T, H)
        # combine: for each token, sum over its k chosen experts. Built as a
        # compare-to-iota one-hot einsum, NOT a scatter-add: scatters with
        # data-dependent indices inside a partial-manual shard_map region
        # (the 1F1B pp executor) trip an XLA SPMD partitioner CHECK
        # (spmd_partitioner_util.cc:495, replica-group derivation — see
        # docs/moe_1f1b_tp.md for the minimal repro), and dense one-hot
        # contractions are the MXU-friendly formulation anyway (same trick
        # as the reference's top-k one-hot in moe/loss_function.py:5).
        held = jnp.arange(
            self.first_expert, self.first_expert + self.num_experts, dtype=idx.dtype
        )
        onehot = (idx[:, :, None] == held).astype(jnp.float32)  # (T, k, E)
        combine = jnp.einsum("tke,tk->te", onehot, gates)  # (T, E)
        return jnp.einsum(
            "te,eth->th", combine.astype(x.dtype), y_all
        )

    def capacity(self, num_tokens: int, top_k: int) -> int:
        """C = ceil(T·k·cf/E) (reference expert_mlps.py:169)."""
        assert self.capacity_factor is not None
        return max(
            1,
            math.ceil(
                num_tokens * top_k * self.capacity_factor / self.num_experts
            ),
        )

    def dispatch(
        self, x: jax.Array, gates: jax.Array, idx: jax.Array, capacity: int
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Scatter tokens into (E, C, H) expert buffers.

        Returns (buffers (E,C,H), slot (T·k,) flat slot index with dummy E·C
        for dropped, keep (T·k,) fp32 mask). Position-in-expert is assigned
        token-major: earlier tokens win capacity (reference cumsum ordering,
        expert_mlps.py:169+tensor_utils)."""
        t, k = idx.shape
        e, c = self.num_experts, capacity
        e_flat = idx.reshape(-1)  # (T·k,) token-major
        onehot = (
            e_flat[:, None] == jnp.arange(e, dtype=e_flat.dtype)[None, :]
        ).astype(jnp.float32)
        # fp32 cumsum is exact for counts up to 2^24 — far beyond any T·k
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1.0, e_flat[:, None].astype(jnp.int32), axis=1
        )[:, 0]
        keep = (pos < c).astype(jnp.float32)
        slot = jnp.where(
            pos < c, e_flat * c + pos.astype(jnp.int32), e * c
        ).astype(jnp.int32)
        x_rep = jnp.repeat(x, k, axis=0)  # (T·k, H) token-major
        buf = jnp.zeros((e * c + 1, x.shape[1]), x.dtype)
        buf = buf.at[slot].add(x_rep * keep[:, None].astype(x.dtype))
        return buf[: e * c].reshape(e, c, -1), slot, keep

    def combine(
        self,
        y: jax.Array,
        slot: jax.Array,
        keep: jax.Array,
        gates: jax.Array,
        num_tokens: int,
    ) -> jax.Array:
        """Gather expert outputs back to tokens and scale by gate affinity
        (dropped tokens contribute zero — reference unpermute+affinity-scale,
        expert_mlps.py:169)."""
        e, c, h = y.shape
        y_pad = jnp.concatenate([y.reshape(e * c, h), jnp.zeros((1, h), y.dtype)])
        out_tk = y_pad[slot] * (keep * gates.reshape(-1))[:, None].astype(y.dtype)
        return jnp.sum(out_tk.reshape(num_tokens, -1, h), axis=1)

    def forward_capacity_factor(
        self, params: Params, x: jax.Array, gates: jax.Array, idx: jax.Array
    ) -> jax.Array:
        """Static-shape capacity-factor dispatch (reference
        forward_capacity_factor expert_mlps.py:169). x (T,H)."""
        t = x.shape[0]
        cap = self.capacity(t, idx.shape[1])
        buf, slot, keep = self.dispatch(x, gates, idx, cap)
        y = self._mlp(params, buf)
        return self.combine(y, slot, keep, gates, t)

    def forward_selective(
        self, params: Params, x: jax.Array, gates: jax.Array, idx: jax.Array
    ) -> jax.Array:
        """Token-gen path: gather only each token's chosen expert weights
        (reference ``forward_selective_loading`` expert_mlps.py:267, which
        loads just the selected experts from HBM during decode).

        On TPU the win is the same currency — HBM traffic: decode is
        bandwidth-bound, and for T tokens this reads T·k experts' weights
        instead of all E (a k·T/E reduction; at Mixtral's T=1, k=2, E=8 that
        is 4× less weight traffic per MoE layer). x (T,H), gates/idx (T,k).

        Not dispatched to by :meth:`__call__`: compiled over a layer scan the
        gather moves more bytes than all-experts at every T (the table
        there). Kept as the plain reference of the routed-experts-only read.
        """
        t, k = idx.shape
        # (T,k,H,n_up,I) / (T,k,I,H) dynamic gathers of whole-expert slices
        w_gu = jnp.take(params["gate_up"], idx, axis=0)
        w_dn = jnp.take(params["down"], idx, axis=0)
        h1 = jnp.einsum("th,tkhui->tkui", x, w_gu)
        fn = ACTIVATIONS[self.activation]
        if self.glu:
            act = fn(h1[:, :, 0]) * h1[:, :, 1]
        else:
            act = fn(h1[:, :, 0])
        y = jnp.einsum("tki,tkih->tkh", act, w_dn)  # (T,k,H)
        return jnp.sum(y * gates[:, :, None].astype(y.dtype), axis=1)

    @jax.named_scope("experts")
    def __call__(
        self, params: Params, x: jax.Array, gates: jax.Array, idx: jax.Array
    ) -> jax.Array:
        # the no-drop path taken (all that serving runs) is a scope of its own
        # under moe/experts, so a device trace says which one a program ran; a
        # traced serving engine's tap (moe/tap.py) is told what was routed and
        # how many pairs are computed
        t = idx.shape[0]
        tap = routing_tap.current()
        if self.capacity_factor is None:
            # all experts at every shape. A gather of the T·k routed experts
            # asks for fewer bytes where T·k < E, but as compiled over the
            # serving engines' layer scan it cannot fuse with the scan's
            # slice: the layer's whole stack is copied out first (read E,
            # write E), the gather scans it again and writes T·k slices to a
            # buffer the einsum reads back — against one fused read of E
            # here. Device ms a layer, weights as a scan's xs in
            # placement.py's layout, selective / all (v5e, chip run, PR 27):
            #   E 64, k 8, H 2048, I 1024   T 1: 4.89 / 1.08   2: 5.29 / 1.07
            #                               T 4: 6.20 / 1.07   8: 9.02 / 1.07
            #   E 8, k 2, H 4096, I 14336   T 1: 25.5 / 3.74   2: 30.2 / 3.73
            #                               T 4: 39.8 / 3.73
            # (the reference dispatches on SELECTIVE_LOADING_THRESHOLD here,
            # expert_mlps.py:298-357; forward_selective stays as the plain
            # reference for a kernel that reads only the routed experts)
            if tap is not None:
                tap.record(
                    idx, self.routed_experts or self.num_experts, "all",
                    t * self.num_experts, held=(self.first_expert, self.num_experts),
                )
            with jax.named_scope("all"):
                return self.forward_all_experts(params, x, gates, idx)
        if self.routed_experts not in (None, self.num_experts):
            raise NotImplementedError(
                "a held share of the experts runs the no-drop path only "
                "(capacity_factor=None): the capacity dispatch buffers every "
                "routed id"
            )
        return self.forward_capacity_factor(params, x, gates, idx)
