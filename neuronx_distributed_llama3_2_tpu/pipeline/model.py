"""SPMD pipeline-parallel causal LM.

TPU-native replacement for the reference's PP runtime (``pipeline/model.py``
``NxDPPModel`` :54 + ``pipeline/comm.py`` + ``pipeline/partition.py``). The
reference needs ~3.3K LoC because torch-xla is MPMD: FX-trace the model, split
the graph per rank (partition.py:18), emulate p2p send/recv with 2-rank
all-gathers (comm.py:38-92), exchange shape metadata over TCPStore
(comm.py:130-197), and execute a per-rank task list with one XLA graph per
task (model.py:1382). Under single-program SPMD all of that collapses to:

- **partition** = reshape the stacked layer params (L, ...) →
  (pp, L/pp, ...) and shard dim 0 over the ``pp`` mesh axis (the reference's
  ``create_partitions`` even split, partition.py:280);
- **p2p** = ``jnp.roll`` of the pp-sharded microbatch stream, which XLA
  lowers to a neighbor ``collective-permute`` over ICI — real p2p, not the
  all-gather trick (SURVEY.md §5 backend note);
- **schedule** = one ``lax.scan`` over the rotation count (or an unrolled
  static rotation plan). Three executors:
  ``schedule="gpipe"`` scans ``M + pp - 1`` forward rotations
  (:class:`..pipeline.scheduler.TrainGPipeSchedule`) and lets autodiff run
  the backward pipeline in reverse — O(M) stored rotation streams;
  ``schedule="1f1b"`` (:meth:`PipelinedCausalLM.loss_and_grad`) executes
  :class:`..pipeline.scheduler.Train1F1BSchedule`'s timing with a manual
  per-stage VJP inside a single scan — each stage forward runs once, its
  pullback's residuals wait in a ring of 2pp-1 sets: activations bounded
  O(pp) (measured: 284MB vs 480MB at pp=4, M=32, and M-independent);
  ``schedule="interleaved"`` executes Megatron virtual-pipeline chunking
  as a static chunked-rotation plan (docs/interleaved_vpp.md);
- **shared embedding** (tied embeddings used by stage 0 and the head) needs
  no grad-sync machinery (reference ``analyze_shared_weights_across_stages``
  partition.py:232 / ``_reduce_shared_weights`` model.py:620): it is one
  global parameter used twice, GSPMD sums its gradient contributions.

Bubble fraction is (pp-1)/(M+pp-1) like GPipe; choose num_microbatches ≥ 4·pp
to amortize (same guidance as the reference's 1F1B).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LlamaForCausalLM,
    _remat_policy,
)
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import BATCH_AXES, constrain
from neuronx_distributed_llama3_2_tpu.parallel.state import PP_AXIS, TP_AXIS

Params = Dict[str, Any]

SCHEDULES = ("gpipe", "1f1b", "interleaved")

# One entry per schedule an executor below has traced, in order:
# ``PipelinedCausalLM.schedule_counters()`` of that trace and the bytes of the
# residual ring it laid out (Python ints, written at trace time — no device
# work). The benchmark's ``pipeline_bubble_share`` reads the last one;
# ``make_train_step`` puts the same numbers into the step's metrics.
COMPILED_SCHEDULES: list = []


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _seq_slice(x, start, chunk: int):
    """dynamic_slice along seq whose VJP avoids the data-dependent scatter
    that aborts the XLA partitioner inside a partial-manual (pp-manual,
    tp-auto) region (spmd_partitioner_util CHECK — same class as
    docs/moe_1f1b_tp.md): the backward rebuilds the padded cotangent with
    pad+roll, which lowers to gathers only."""
    return lax.dynamic_slice_in_dim(x, start, chunk, axis=1)


def _seq_slice_fwd(x, start, chunk: int):
    return _seq_slice(x, start, chunk), (x.shape[1], start)


def _seq_slice_bwd(chunk: int, res, dy):
    full, start = res
    dx = jnp.pad(dy, ((0, 0), (0, full - chunk), (0, 0)))
    return jnp.roll(dx, start, axis=1), None


_seq_slice.defvjp(_seq_slice_fwd, _seq_slice_bwd)


def _split_pullback(pullback, invariant):
    """``(waiting, rebuild)`` of a ``jax.vjp`` pullback taken inside a scan
    body. The pullback is a pytree; its array leaves are its residuals.
    ``waiting`` lists those this trace made — what has to be kept if the
    pullback is to be called in a later trip of the scan. The others are the
    same in every trip (``invariant``'s own leaves, told by identity: the
    weights come back as the very tracers that went in; and constants) and
    stay where they are. ``rebuild(values)`` is the pullback again with
    ``values`` in the waiting leaves' places."""
    leaves, treedef = jax.tree.flatten(pullback)
    same_every_trip = {id(x) for x in jax.tree.leaves(invariant)}
    waits = [
        isinstance(x, jax.core.Tracer) and id(x) not in same_every_trip
        for x in leaves
    ]

    def rebuild(values):
        values = iter(values)
        return jax.tree.unflatten(
            treedef, [next(values) if w else x for w, x in zip(waits, leaves)]
        )

    return [x for w, x in zip(waits, leaves) if w], rebuild


def _psum_pp(v):
    """psum over the pp axis, CPU-bf16-safe (parallel.layers helper)."""
    from neuronx_distributed_llama3_2_tpu.parallel.layers import (
        psum_cpu_bf16_safe,
    )

    return psum_cpu_bf16_safe(v, PP_AXIS)


@dataclasses.dataclass(frozen=True)
class PipelinedCausalLM:
    """Pipeline wrapper around :class:`LlamaForCausalLM` with the same
    init/specs/loss interface, so the trainer and checkpoint layers work
    unchanged (the uniform-facade role of the reference's NxDModel,
    trainer/model.py:8)."""

    model: LlamaForCausalLM
    num_microbatches: int
    # shardlint SL002 — see models/llama.py LlamaAttention
    __layout_deps__ = ("get_parallel_state", "get_pipeline_model_parallel_size")
    # "gpipe": fwd scan + autodiff backward — O(M) stashed stage-streams,
    #   lowest bubble (M/(M+pp-1) utilization).
    # "1f1b": single scan doing one fwd + one manual-VJP bwd stage-apply per
    #   rotation — kept activations bounded O(pp) (ring of 2pp-1 sets of the
    #   stage pullback's residuals: what config.remat saves, a layer)
    #   regardless of M, at the cost of pp-1 extra bubble rotations
    #   and the head computed in-lane (see loss_and_grad). The memory/compute
    #   tradeoff the reference's Train1F1BSchedule exists for
    #   (pipeline/scheduler.py:157).
    schedule: str = "gpipe"
    # "interleaved" only: virtual-pipeline model chunks per lane (Megatron
    # VPP, reference scheduler.py:256). Executed as a chunked SPMD rotation
    # following scheduler.InterleavedRotationPlan — measured tradeoffs in
    # docs/interleaved_vpp.md.
    num_model_chunks: int = 1
    # interleaved only: True (default) runs the 1F1B-grade memory-bounded
    # backward (Interleaved1F1BPlan: manual-VJP per virtual stage, stash
    # ring O(pp·V)); False restores the autodiff backward (gpipe memory
    # profile, O(M) stashed rotation streams) — docs/interleaved_vpp.md
    memory_bounded_backward: bool = True
    # 1F1B only: split the LM-head/CE computation across pp lanes by
    # sequence slice instead of running the FULL head on every lane with
    # (pp-1)/pp of it masked to garbage. Under SPMD the masked head sits on
    # every rotation's critical path (the last lane must finish it before
    # the next exchange), so splitting divides the per-rotation head cost
    # by pp at the price of two (mbs, S, H) psums. At Llama-3 vocab (128K)
    # the head is a large rotation fraction — docs/head_waste.md quantifies.
    head_sequence_split: bool = True

    def __post_init__(self):
        if not (isinstance(self.model, LlamaForCausalLM) or self._is_moe()):
            raise TypeError(
                f"PipelinedCausalLM supports LlamaForCausalLM / "
                f"MixtralForCausalLM, got {type(self.model).__name__}"
            )
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {self.schedule!r}"
            )
        if self.num_model_chunks < 1:
            raise ValueError(
                f"num_model_chunks must be >= 1, got {self.num_model_chunks}"
            )
        if self.num_model_chunks > 1 and self.schedule != "interleaved":
            raise ValueError(
                "num_model_chunks > 1 requires schedule='interleaved'"
            )

    def _is_moe(self) -> bool:
        from neuronx_distributed_llama3_2_tpu.models.mixtral import (
            MixtralForCausalLM,
        )

        return isinstance(self.model, MixtralForCausalLM)

    @property
    def uses_manual_vjp(self) -> bool:
        """True when training must go through :meth:`loss_and_grad` (the
        fused manual-VJP executors) instead of autodiff on :meth:`loss` —
        the trainer dispatches on this."""
        return self.schedule == "1f1b" or (
            self.schedule == "interleaved" and self.memory_bounded_backward
        )

    @property
    def config(self):
        return self.model.config

    def _pp(self) -> int:
        return parallel_state.get_pipeline_model_parallel_size()

    def schedule_counters(self) -> Dict[str, int]:
        """What the schedule's rotation count buys, per lane: every one of
        ``rotations`` offers a lane one forward and one backward slot (the
        1F1B executors run both in one rotation; under autodiff the backward
        slots are the forward scan's rotations run in reverse), and
        ``useful_lane_rotations`` of those ``2 * rotations`` slots hold a
        real micro-batch — the others compute on masked data, which no
        device trace can tell from work. Bubble share =
        ``1 - useful_lane_rotations / (2 * rotations)``.

        ``stage_forwards_per_slot``: how often a micro-batch's stage forward
        runs, before what the ``remat`` policy re-runs inside the backward —
        1 where the backward reads the forward's own residuals (autodiff, and
        the 1F1B executor's ring), 2 where it replays the stage from a stashed
        input (the interleaved memory-bounded executor)."""
        pp, M, V = self._pp(), self.num_microbatches, self.num_model_chunks
        replays = False
        if self.schedule == "1f1b":
            rotations = M + 2 * (pp - 1)
        elif self.schedule == "gpipe":
            rotations = M + pp - 1
        else:
            from neuronx_distributed_llama3_2_tpu.pipeline import scheduler

            replays = self.memory_bounded_backward
            plan = (
                scheduler.Interleaved1F1BPlan if replays
                else scheduler.InterleavedRotationPlan
            )
            rotations = plan(M, V, pp).num_rotations
        return {
            "rotations": rotations, "useful_lane_rotations": 2 * M * V,
            "stage_forwards_per_slot": 2 if replays else 1,
        }

    def _traced_as(self) -> Dict[str, Any]:
        """What names this model's entries in :data:`COMPILED_SCHEDULES`."""
        return {
            "schedule": self.schedule, "pp": self._pp(),
            "num_microbatches": self.num_microbatches,
        }

    def traced_counters(self) -> Dict[str, int]:
        """:meth:`schedule_counters` with what only a trace knows, from this
        schedule's newest entry in :data:`COMPILED_SCHEDULES`:
        ``residual_ring_bytes``, the 1F1B executor's ring of pullback
        residuals — all ``2·pp - 1`` sets, on one lane, before tensor
        parallelism splits it; 0 where there is no ring or no trace yet."""
        mine = self._traced_as().items()
        newest = next(
            (c for c in reversed(COMPILED_SCHEDULES) if mine <= c.items()), {}
        )
        return {
            **self.schedule_counters(),
            "residual_ring_bytes": newest.get("residual_ring_bytes", 0),
        }

    def _note_compiled(self, rotations: int, residual_ring_bytes: int = 0) -> None:
        """An executor traced ``rotations`` rotations: record the counters,
        which have to be the ones the scan really runs."""
        counters = self.schedule_counters()
        assert counters["rotations"] == rotations, (counters, rotations)
        COMPILED_SCHEDULES.append({
            **self._traced_as(), **counters,
            "residual_ring_bytes": residual_ring_bytes,
        })

    def _layers_per_stage(self) -> int:
        L, pp = self.config.num_layers, self._pp()
        v = self.num_model_chunks
        if L % (pp * v) != 0:
            raise ValueError(
                f"num_layers {L} not divisible by pp*chunks {pp}*{v}"
            )
        return L // (pp * v)

    # -- parameter layout ------------------------------------------------

    def to_pipeline(self, params: Params) -> Params:
        """(L, ...) stacked layers → (pp, L/pp, ...). Stage s owns layers
        [s·L/pp, (s+1)·L/pp) — the reference's even auto-partition
        (partition.py:280, model.py:306-318).

        schedule="interleaved": → (V, pp, L/(pp·V), ...) where lane s's
        chunk v is the contiguous layer block of virtual stage u = v·pp + s
        (Megatron chunk assignment, reference scheduler.py:319-353)."""
        pp, lps = self._pp(), self._layers_per_stage()
        out = dict(params)
        if self.schedule == "interleaved":
            v = self.num_model_chunks
            out["layers"] = jax.tree.map(
                lambda p: p.reshape(v, pp, lps, *p.shape[1:]), params["layers"]
            )
        else:
            out["layers"] = jax.tree.map(
                lambda p: p.reshape(pp, lps, *p.shape[1:]), params["layers"]
            )
        return out

    def from_pipeline(self, params: Params) -> Params:
        L = self.config.num_layers
        skip = 3 if self.schedule == "interleaved" else 2
        out = dict(params)
        out["layers"] = jax.tree.map(
            lambda p: p.reshape(L, *p.shape[skip:]), params["layers"]
        )
        return out

    def init(self, key: jax.Array) -> Params:
        return self.to_pipeline(self.model.init(key))

    def specs(self) -> Params:
        base = self.model.specs()
        out = dict(base)
        # layer leaves are P(None, *per-layer); pipeline adds the pp axis on
        # the stage dim: P("pp", None, *per-layer) — or, interleaved,
        # P(None, "pp", None, *per-layer) for the (V, pp, Lv, ...) layout
        if self.schedule == "interleaved":
            out["layers"] = jax.tree.map(
                lambda s: P(None, PP_AXIS, *s),
                base["layers"],
                is_leaf=lambda s: isinstance(s, P),
            )
        else:
            out["layers"] = jax.tree.map(
                lambda s: P(PP_AXIS, *s),
                base["layers"],
                is_leaf=lambda s: isinstance(s, P),
            )
        return out

    # -- execution -------------------------------------------------------

    def _scan_stage(self, stage_layers, x, sin, cos, positions):
        """One stage's layer scan: (L/pp-stacked params, x) → (y, aux_mean).
        MoE layers return (x, router aux); dense layers contribute aux 0.
        The single stage body shared by BOTH executors — gpipe and 1F1B must
        never diverge on the layer protocol."""
        layer = self.model._layer()
        moe = self._is_moe()
        policy = _remat_policy(self.config.remat)

        def body(x, one_layer):
            out = layer(one_layer, x, sin, cos, positions)
            if moe:
                return out[0], out[1]
            return out, jnp.float32(0.0)

        if policy is not None:
            body = jax.checkpoint(body, policy=policy)
        y, auxes = lax.scan(body, x, stage_layers)
        return y, jnp.mean(auxes)

    def _stage_apply(self, stage_layers, stream, sin, cos, positions):
        """Every stage applies its layer block to its current microbatch.
        shard_map manual over pp only; tp/sp/dp shardings inside the stage
        body remain GSPMD-auto, so the per-layer constraints keep working."""
        mesh = parallel_state.get_parallel_state().mesh

        def body(stage_layers_l, stream_l, sin, cos, positions):
            x = stream_l[0]  # (mbs, S, H) — this stage's microbatch
            lp = jax.tree.map(lambda p: p[0], stage_layers_l)
            x, aux = self._scan_stage(lp, x, sin, cos, positions)
            return x[None], aux[None]

        layer_specs = jax.tree.map(
            lambda _: P(PP_AXIS),
            stage_layers,
        )
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(layer_specs, P(PP_AXIS), P(), P(), P()),
            out_specs=(P(PP_AXIS), P(PP_AXIS)),
            axis_names={PP_AXIS},
            check_vma=False,
        )(stage_layers, stream, sin, cos, positions)

    def _pipeline_hidden(self, params: Params, input_ids: jax.Array) -> jax.Array:
        """Embed → pipelined decoder stack → (B, S, H) hidden states."""
        cfg = self.config
        pp, M = self._pp(), self.num_microbatches
        gbs, S = input_ids.shape
        if gbs % M != 0:
            raise ValueError(f"batch {gbs} not divisible by microbatches {M}")
        mbs = gbs // M

        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mbs, S))
        # the model's own rope hook: partial-rotary families (GPT-NeoX/
        # CodeGen) override _rope, and using cfg.head_dim here would feed
        # them wrong tables
        sin, cos = self.model._rope(S)

        x = self.model._embed()(params["embed"], input_ids)  # (GBS, S, H)
        # cp zigzag layout: permute once here (position-wise stages keep the
        # layout; attention resolves the same cp layout), inverse at the loss
        x, positions, zz_inv = self.model._zigzag_enter(x, positions)
        # strided microbatch split (see trainer.make_train_step): microbatch
        # m = rows m::M, keeping every dp shard present in every microbatch
        x_mb = x.reshape(mbs, M, S, -1).swapaxes(0, 1)  # (M, mbs, S, H)
        x_mb = constrain(x_mb, P(None, BATCH_AXES, None, None))

        stream = jnp.zeros((pp, mbs, S, x.shape[-1]), cfg.dtype)
        out_buf = jnp.zeros((M, mbs, S, x.shape[-1]), cfg.dtype)

        def rotate(carry, t):
            stream, out_buf, aux_sum = carry
            # inject the next microbatch into stage 0; the clamped reads past
            # M feed garbage whose outputs never reach out_buf (they would
            # arrive after the last rotation)
            inject = lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            # neighbor shift stage s-1 → s: lowers to collective-permute over
            # the pp axis (the reference's emulated send/recv, comm.py:38-92)
            stream = jnp.roll(stream, 1, axis=0)
            stream = lax.dynamic_update_index_in_dim(
                stream, inject.astype(cfg.dtype), 0, axis=0
            )
            stream = constrain(stream, P(PP_AXIS, BATCH_AXES, None, None))
            stream, stage_aux = self._stage_apply(
                params["layers"], stream, sin, cos, positions
            )
            # router aux (MoE): lane s is processing a real microbatch at
            # rotation t iff 0 <= t - s < M; fill/drain lanes run on garbage
            # and must not contaminate the aux mean
            lane = jnp.arange(pp)
            valid = ((t - lane) >= 0) & ((t - lane) < M)
            aux_sum = aux_sum + jnp.sum(
                jnp.where(valid, stage_aux.astype(jnp.float32), 0.0)
            )
            out = lax.index_in_dim(stream, pp - 1, axis=0, keepdims=False)
            # writes for t < pp-1 land on index 0 and are overwritten by the
            # first valid write (t = pp-1) before any later index is touched
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf, out, jnp.clip(t - (pp - 1), 0, M - 1), axis=0
            )
            return (stream, out_buf, aux_sum), None

        from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import (
            cp_layout_from_inv,
        )

        if self.schedule == "gpipe":
            # (a 1F1B model's forward-only loss also scans this: not its
            # training schedule)
            self._note_compiled(M + pp - 1)
        with cp_layout_from_inv(zz_inv):
            (stream, out_buf, aux_sum), _ = lax.scan(
                rotate, (stream, out_buf, jnp.float32(0.0)),
                jnp.arange(M + pp - 1),
            )
        # undo the strided microbatch split
        hidden = out_buf.swapaxes(0, 1).reshape(gbs, S, -1)
        hidden = self.model._norm()(params["final_norm"], hidden)
        hidden = self.model._zigzag_exit(hidden, zz_inv)
        # every (stage, microbatch) pair contributed its stage-mean aux once
        return hidden, aux_sum / (pp * M)

    def _interleaved_hidden(
        self, params: Params, input_ids: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Chunked SPMD rotation realizing interleaved VPP (reference
        ``TrainInterleavedSchedule`` scheduler.py:256): each lane owns
        ``V = num_model_chunks`` virtual stages of ``L/(pp·V)`` layers, and
        every rotation executes one virtual stage per lane following the
        static host-simulated :class:`..pipeline.scheduler
        .InterleavedRotationPlan` (admission stalls resolved
        oldest-hop-first). The stream's neighbor ppermute is unchanged —
        virtual stage u → u+1 is always lane s → s+1 — so interleaving
        costs no new collective patterns, only more rotations of shorter
        stages. Measured tradeoffs vs gpipe/1F1B: docs/interleaved_vpp.md.

        Forward-only plan; backward is autodiff through the unrolled
        rotations (gpipe-memory-profile). Returns (hidden (B,S,H),
        mean router aux)."""
        cfg = self.config
        pp, M, V = self._pp(), self.num_microbatches, self.num_model_chunks
        gbs, S = input_ids.shape
        if gbs % M != 0:
            raise ValueError(f"batch {gbs} not divisible by microbatches {M}")
        mbs = gbs // M
        H = cfg.hidden_size
        mesh = parallel_state.get_parallel_state().mesh

        from neuronx_distributed_llama3_2_tpu.pipeline.scheduler import (
            InterleavedRotationPlan,
        )

        plan = InterleavedRotationPlan(M, V, pp)
        if not self.uses_manual_vjp:
            self._note_compiled(plan.num_rotations)

        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mbs, S))
        sin, cos = self.model._rope(S)
        x = self.model._embed()(params["embed"], input_ids)  # (GBS, S, H)
        x_mb = x.reshape(mbs, M, S, -1).swapaxes(0, 1)  # (M, mbs, S, H)
        x_mb = constrain(x_mb, P(None, BATCH_AXES, None, None))
        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

        # bf16 operands crossing the manual boundary abort XLA:CPU — the
        # shared round-trip workaround (layers.shardmap_cpu_bf16_workaround);
        # the replicated microbatch stream's gradient is the psum that trips
        # the bug, so it goes through the boundary cast too
        from neuronx_distributed_llama3_2_tpu.parallel.layers import (
            shardmap_cpu_bf16_workaround,
        )

        layers_in, restore_layers = shardmap_cpu_bf16_workaround(params["layers"])
        x_mb, restore_x = shardmap_cpu_bf16_workaround(x_mb)

        # static plan → (R, pp) gather tables scanned by a UNIFORM rotation
        # body: program size O(1) in M·V (VERDICT r4 #4; the reference's
        # schedule is likewise a constant-size per-task loop,
        # scheduler.py:256). Receiver-side routing (in_slot) and stream
        # exits are derived per rotation on the host, like the sender-side
        # columns.
        tables = {
            "chunk": jnp.asarray([st.chunk for st in plan.steps_], jnp.int32),
            "mb": jnp.asarray([st.mb for st in plan.steps_], jnp.int32),
            "admit": jnp.asarray([st.admit for st in plan.steps_], jnp.int32),
            # lane d's inbound stream comes from lane d-1 and lands in the
            # chunk slot the sender computed
            "in_slot": jnp.asarray(
                [
                    [st.out_slot[(d - 1) % pp] for d in range(pp)]
                    for st in plan.steps_
                ],
                jnp.int32,
            ),
            # a stream exits when its output is not stored anywhere
            # (out_slot -1) while the lane ran a real microbatch
            "exits": jnp.asarray(
                [
                    [
                        1 if (st.out_slot[d] == -1 and st.mb[d] >= 0) else 0
                        for d in range(pp)
                    ]
                    for st in plan.steps_
                ],
                jnp.int32,
            ),
        }

        def lane_body(layers_l, x_all):
            layers_l = restore_layers(layers_l)
            x_all = restore_x(x_all)
            # pp-manual leaves arrive (V, 1, Lv, ...); drop the lane dim
            layers_lane = jax.tree.map(lambda p: p[:, 0], layers_l)
            s = lax.axis_index(PP_AXIS)

            def rotation(carry, xs):
                slots, out_buf, aux_sum = carry
                chunk_a = xs["chunk"][s]
                mb_a = xs["mb"][s]
                admit_a = xs["admit"][s]
                in_slot = xs["in_slot"][s]
                exits = xs["exits"][s]

                c_cl = jnp.clip(chunk_a, 0, V - 1)
                x_slot = lax.dynamic_index_in_dim(
                    slots, c_cl, axis=0, keepdims=False
                )
                x_fresh = lax.dynamic_index_in_dim(
                    x_all, jnp.clip(admit_a, 0, M - 1), axis=0, keepdims=False
                ).astype(cfg.dtype)
                x_in = jnp.where(admit_a >= 0, x_fresh, x_slot)
                stage_layers = jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(
                        p, c_cl, axis=0, keepdims=False
                    ),
                    layers_lane,
                )
                y, aux = self._scan_stage(
                    stage_layers, x_in, sin, cos, positions
                )
                y = y.astype(cfg.dtype)
                aux_sum = aux_sum + jnp.where(
                    mb_a >= 0, aux.astype(jnp.float32), 0.0
                )
                # collect exiting microbatches (only lane pp-1 ever exits:
                # the last virtual stage pp·V-1 ≡ pp-1 mod pp)
                m_cl = jnp.clip(mb_a, 0, M - 1)
                cur = lax.dynamic_index_in_dim(
                    out_buf, m_cl, axis=0, keepdims=False
                )
                out_buf = lax.dynamic_update_index_in_dim(
                    out_buf, jnp.where(exits > 0, y, cur), m_cl, axis=0
                )
                # rotate; park the inbound stream in its chunk slot
                recv = lax.ppermute(y, PP_AXIS, fwd_perm)
                in_cl = jnp.clip(in_slot, 0, V - 1)
                cur_slot = lax.dynamic_index_in_dim(
                    slots, in_cl, axis=0, keepdims=False
                )
                slots = lax.dynamic_update_index_in_dim(
                    slots, jnp.where(in_slot >= 0, recv, cur_slot), in_cl, axis=0
                )
                return (slots, out_buf, aux_sum), None

            carry0 = (
                jnp.zeros((V, mbs, S, H), cfg.dtype),
                jnp.zeros((M, mbs, S, H), cfg.dtype),
                jnp.float32(0.0),
            )
            (slots, out_buf, aux_sum), _ = lax.scan(rotation, carry0, tables)
            return out_buf[None], aux_sum[None]

        layer_specs = jax.tree.map(lambda _: P(None, PP_AXIS), params["layers"])
        out_buf, aux_lanes = jax.shard_map(
            lane_body,
            mesh=mesh,
            in_specs=(layer_specs, P()),
            out_specs=(P(PP_AXIS), P(PP_AXIS)),
            axis_names={PP_AXIS},
            check_vma=False,
        )(layers_in, x_mb)

        hidden_mb = out_buf[pp - 1]  # (M, mbs, S, H) — exits live on lane pp-1
        hidden = hidden_mb.swapaxes(0, 1).reshape(gbs, S, -1)
        hidden = self.model._norm()(params["final_norm"], hidden)
        # every (virtual stage, microbatch) visit contributed its chunk-mean
        # aux once; stages have equal layer counts so this equals the global
        # per-(layer, microbatch) mean the other executors compute
        aux = jnp.sum(aux_lanes) / (pp * V * M)
        return hidden, aux

    def _hidden(self, params: Params, input_ids: jax.Array):
        if self.schedule == "interleaved":
            return self._interleaved_hidden(params, input_ids)
        return self._pipeline_hidden(params, input_ids)

    def __call__(self, params: Params, input_ids: jax.Array) -> jax.Array:
        hidden, _ = self._hidden(params, input_ids)
        return self.model._logits(params, hidden)

    def loss(
        self, params: Params, input_ids: jax.Array, labels: jax.Array
    ) -> jax.Array:
        hidden, aux = self._hidden(params, input_ids)
        ce = self.model.loss_from_hidden(params, hidden, labels)
        if self._is_moe():
            # per-(layer, microbatch) aux mean — the microbatched analogue of
            # the unpipelined per-layer full-batch mean (identical at M=1;
            # the trainer's grad-accumulation path averages the same way)
            return ce + self.config.router_aux_loss_coef * aux
        return ce

    # -- 1F1B: fused forward+backward with O(pp) activation memory ----------

    def _head_params(self, params: Params) -> Params:
        """Final-norm + LM-head parameters (owned by the last stage under
        1F1B — the reference pins the head to the last pp rank too,
        partition.py:232)."""
        hp = {"final_norm": params["final_norm"], "embed": params["embed"]}
        if "lm_head" in params:
            hp["lm_head"] = params["lm_head"]
        return hp

    @jax.named_scope("ce")
    def _head_loss_sum(self, head_params: Params, h: jax.Array, labels_m):
        """Un-normalized CE sum for one microbatch's final hidden states."""
        cfg = self.config
        h = self.model._norm()(head_params["final_norm"], h)
        shifted = labels_m[:, 1:]
        from neuronx_distributed_llama3_2_tpu.parallel.loss import (
            fused_linear_cross_entropy,
        )

        loss_sum, _ = fused_linear_cross_entropy(
            h[:, :-1, :],
            lambda hc: self.model._logits(head_params, hc),
            shifted,
            chunk_size=cfg.loss_chunk_size or h.shape[1],
        )
        return loss_sum

    @jax.named_scope("ce")
    def _head_loss_sum_slice(
        self, head_params: Params, h: jax.Array, labels_m, lane, pp: int
    ):
        """This lane's 1/pp sequence slice of the un-normalized CE sum.

        Summed over lanes (psum) this equals :meth:`_head_loss_sum` exactly:
        the shifted sequence is padded to pp equal chunks with ignore-index
        labels, which the CE's validity mask zeroes. The per-lane head cost
        drops to head/pp — the 1F1B head-waste mitigation (docs/
        head_waste.md)."""
        cfg = self.config
        h = self.model._norm()(head_params["final_norm"], h)
        hs = h[:, :-1, :]
        lab = labels_m[:, 1:]
        sm1 = hs.shape[1]
        chunk = -(-sm1 // pp)  # ceil
        pad = pp * chunk - sm1
        if pad:
            hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
            lab = jnp.pad(lab, ((0, 0), (0, pad)), constant_values=-100)
        hs = _seq_slice(hs, lane * chunk, chunk)
        lab = lax.dynamic_slice_in_dim(lab, lane * chunk, chunk, axis=1)
        from neuronx_distributed_llama3_2_tpu.parallel.loss import (
            fused_linear_cross_entropy,
        )

        loss_sum, _ = fused_linear_cross_entropy(
            hs,
            lambda hc: self.model._logits(head_params, hc),
            lab,
            chunk_size=min(cfg.loss_chunk_size or chunk, chunk),
        )
        return loss_sum

    def loss_and_grad(
        self, params: Params, input_ids: jax.Array, labels: jax.Array
    ) -> Tuple[jax.Array, Params]:
        """One-scan 1F1B: returns (masked-mean loss, grads tree like params).

        Executes the reference's ``Train1F1BSchedule`` timing
        (scheduler.py:157: per-stage warmup pp-1-s, steady alternating
        fwd/bwd, cooldown) as a single ``lax.scan`` of ``M + 2(pp-1)``
        rotations inside a pp-manual shard_map. Lane s at rotation t runs
        forward for microbatch ``t - s`` and manual-VJP backward for
        microbatch ``t - (2(pp-1) - s)``. The forward is taken under
        ``jax.vjp`` and runs once: its pullback's residuals — per layer, the
        layer's input and whatever ``config.remat``'s policy saves — wait in
        a ring of depth ``2pp-1`` (written at ``t % D``, read ``2(pp-1-s)``
        rotations later; the stage's weights are not in it, they are put
        back when the pullback is rebuilt) — the O(pp) activation bound that
        is 1F1B's reason to exist (vs this class's gpipe schedule whose
        autodiff stores O(M) rotation streams). ``remat`` is the one dial:
        it holds ``2pp-1`` sets (and one in flight) of what it saves, and
        re-runs in the backward slot what it does not
        (``traced_counters()["residual_ring_bytes"]``).

        Layout choices vs the reference: embedding runs on lane 0 and the
        final-norm/LM-head/CE on lane pp-1 (fixing the advisor's
        "embed/head replicated across stages" note); with tied embeddings
        both lanes contribute to the embedding grad and the lane-grads are
        psum-merged over pp. With ``head_sequence_split`` (default) the
        head/CE is sequence-split across lanes — per-rotation head cost
        head/pp plus two (mbs, S, H) psums instead of a full masked head
        on every lane (was head/(head+stage) of each rotation's critical
        path — 34% for 8B at pp=8; quantified in docs/head_waste.md).
        """
        if self.schedule == "interleaved":
            return self._interleaved_loss_and_grad(params, input_ids, labels)
        cfg = self.config
        pp, M = self._pp(), self.num_microbatches
        gbs, S = input_ids.shape
        if gbs % M != 0:
            raise ValueError(f"batch {gbs} not divisible by microbatches {M}")
        mbs = gbs // M
        H = cfg.hidden_size
        D = 2 * pp - 1  # ring depth ≥ max in-flight (2(pp-1)) + 1
        T = M + 2 * (pp - 1)
        mesh = parallel_state.get_parallel_state().mesh

        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mbs, S))
        sin, cos = self.model._rope(S)

        # strided microbatch split (same convention as the gpipe path)
        ids_mb = input_ids.reshape(mbs, M, S).swapaxes(0, 1)  # (M, mbs, S)
        lab_mb = labels.reshape(mbs, M, S).swapaxes(0, 1)

        # global normalizer, known upfront from the labels alone
        from neuronx_distributed_llama3_2_tpu.parallel.loss import valid_token_mask

        total_count = jnp.maximum(
            valid_token_mask(labels[:, 1:], cfg.vocab_size)
            .astype(jnp.float32)
            .sum(),
            1.0,
        )

        embed = self.model._embed()
        head_params = self._head_params(params)
        moe = self._is_moe()
        # per-(stage, microbatch) router-aux weight: loss adds
        # coef · mean(aux over pp·M stage-visits), so each visit's cotangent
        # is the constant coef/(pp·M) — how the aux term enters a manual VJP
        aux_ct = (
            jnp.float32(cfg.router_aux_loss_coef / (pp * M))
            if moe
            else jnp.float32(0.0)
        )

        split_head = self.head_sequence_split and pp > 1

        def stage_fwd(stage_layers, x):
            return self._scan_stage(stage_layers, x, sin, cos, positions)

        def stage_vjp(stage_layers, x):
            """``(y, aux)``, the residuals of the pullback that have to wait
            for the backward slot, and the pullback rebuilt around them."""
            out, pullback = jax.vjp(stage_fwd, stage_layers, x)
            return out, *_split_pullback(
                pullback, (stage_layers, sin, cos, positions)
            )

        def lane_body(stage_layers, head_p, embed_p, ids_all, lab_all):
            """Runs on one pp lane (manual over pp; tp/dp stay auto)."""
            # pp-sharded leaves arrive as (1, L/pp, ...) per lane
            stage_layers = jax.tree.map(lambda p: p[0], stage_layers)
            # what a rotation's pullback keeps beside these: D sets of it
            # are the ring, the whole of 1F1B's activation memory
            ring_avals = jax.eval_shape(
                lambda w, x: stage_vjp(w, x)[1],
                stage_layers, jax.ShapeDtypeStruct((mbs, S, H), cfg.dtype),
            )
            self._note_compiled(T, residual_ring_bytes=D * sum(
                r.size * r.dtype.itemsize for r in ring_avals
            ))
            s = lax.axis_index(PP_AXIS)
            fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
            bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]

            zeros_g = {
                "layers": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), stage_layers
                ),
                "head": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), head_p
                ),
                "embed": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), embed_p
                ),
            }
            carry0 = {
                "inbox_fwd": jnp.zeros((mbs, S, H), cfg.dtype),
                "inbox_bwd": jnp.zeros((mbs, S, H), cfg.dtype),
                "ring": [jnp.zeros((D, *r.shape), r.dtype) for r in ring_avals],
                "grads": zeros_g,
                "loss_sum": jnp.float32(0.0),
                "aux_sum": jnp.float32(0.0),
            }

            def rotation(carry, t):
                m_f = t - s                      # fwd microbatch of this lane
                m_b = t - (2 * (pp - 1) - s)     # bwd microbatch of this lane
                fwd_valid = (m_f >= 0) & (m_f < M)
                bwd_valid = (m_b >= 0) & (m_b < M)
                is_first = s == 0
                is_last = s == pp - 1

                ids_f = lax.dynamic_index_in_dim(
                    ids_all, jnp.clip(m_f, 0, M - 1), axis=0, keepdims=False
                )
                lab_f = lax.dynamic_index_in_dim(
                    lab_all, jnp.clip(m_f, 0, M - 1), axis=0, keepdims=False
                )
                ids_b = lax.dynamic_index_in_dim(
                    ids_all, jnp.clip(m_b, 0, M - 1), axis=0, keepdims=False
                )

                # ---- forward ----
                x_embed = embed(embed_p, ids_f).astype(cfg.dtype)
                x_in = jnp.where(is_first, x_embed, carry["inbox_fwd"])
                # the one stage forward of this rotation: taken under
                # jax.vjp, its pullback's residuals wait in the ring
                (y, aux_m), waiting, rebuild = stage_vjp(stage_layers, x_in)
                assert [(w.shape, w.dtype) for w in waiting] == [
                    (r.shape, r.dtype) for r in ring_avals
                ], "the pullback's residuals are not the ones the ring was laid out for"
                ring = [
                    lax.dynamic_update_index_in_dim(r, w, t % D, axis=0)
                    for r, w in zip(carry["ring"], waiting)
                ]
                aux_sum = carry["aux_sum"] + jnp.where(
                    fwd_valid, aux_m.astype(jnp.float32), 0.0
                )

                # ---- head ----
                if split_head:
                    # sequence-split: every lane computes the CE for a 1/pp
                    # token slice of the LAST lane's current microbatch —
                    # the full-head-on-every-lane waste becomes useful
                    # parallelism (per-rotation head cost: head/pp + two
                    # (mbs, S, H) psums). docs/head_waste.md quantifies.
                    m_last = t - (pp - 1)
                    last_valid = (m_last >= 0) & (m_last < M)
                    lab_last = lax.dynamic_index_in_dim(
                        lab_all, jnp.clip(m_last, 0, M - 1), axis=0,
                        keepdims=False,
                    )
                    y_bcast = _psum_pp(
                        jnp.where(is_last, y, jnp.zeros_like(y))
                    )

                    def head_fn(hp, h):
                        return self._head_loss_sum_slice(
                            hp, h, lab_last, s, pp
                        )

                    loss_m, head_vjp = jax.vjp(head_fn, head_p, y_bcast)
                    dhead, dh_slice = head_vjp(
                        jnp.float32(1.0) / total_count
                    )
                    # each lane produced the dh rows of its slice; the sum
                    # is the full cotangent (the VJP of the broadcast psum)
                    dh = _psum_pp(dh_slice)
                    head_active = last_valid
                    loss_sum = carry["loss_sum"] + jnp.where(
                        last_valid, loss_m, 0.0
                    )
                else:
                    def head_fn(hp, h):
                        return self._head_loss_sum(hp, h, lab_f)

                    loss_m, head_vjp = jax.vjp(head_fn, head_p, y)
                    dhead, dh = head_vjp(
                        jnp.float32(1.0) / total_count
                    )
                    head_active = is_last & fwd_valid
                    loss_sum = carry["loss_sum"] + jnp.where(
                        head_active, loss_m, 0.0
                    )

                # ---- backward ----
                # last lane's bwd cotangent is its own head grad from this
                # very rotation (m_b == m_f there); other lanes receive dy
                dy_in = jnp.where(
                    is_last, dh.astype(cfg.dtype), carry["inbox_bwd"]
                )
                # the pullback of the forward this lane ran 2(pp-1-s)
                # rotations ago (the last lane: in this very rotation),
                # rebuilt around that rotation's residuals. Before there is
                # one (bwd_valid is false, the result masked) it is rotation
                # 0's: a ring still at its zeros is no forward's residuals,
                # and a pullback over them may divide by one (MoE: nan · 0)
                slot = (t - jnp.minimum(2 * (pp - 1 - s), t)) % D
                saved = [
                    lax.dynamic_index_in_dim(r, slot, axis=0, keepdims=False)
                    for r in ring
                ]
                # (dy, daux): the router-aux gradient rides the same stage
                # VJP as a constant cotangent on the aux output
                dw, dx = rebuild(saved)((dy_in, aux_ct))

                # embedding bwd on lane 0: dx is d(embed output)
                _, embed_vjp = jax.vjp(lambda e: embed(e, ids_b), embed_p)
                (dembed,) = embed_vjp(dx)

                g = carry["grads"]
                bwd_f = bwd_valid.astype(jnp.float32)
                grads = {
                    "layers": jax.tree.map(
                        lambda a, d: a + bwd_f * d.astype(jnp.float32),
                        g["layers"], dw,
                    ),
                    "head": jax.tree.map(
                        lambda a, d: a
                        + jnp.where(head_active, 1.0, 0.0) * d.astype(jnp.float32),
                        g["head"], dhead,
                    ),
                    "embed": jax.tree.map(
                        lambda a, d: a
                        + (bwd_f * is_first.astype(jnp.float32))
                        * d.astype(jnp.float32),
                        g["embed"], dembed,
                    ),
                }

                # ---- exchange ----
                inbox_fwd = lax.ppermute(y.astype(cfg.dtype), PP_AXIS, fwd_perm)
                inbox_bwd = lax.ppermute(dx.astype(cfg.dtype), PP_AXIS, bwd_perm)
                return {
                    "inbox_fwd": inbox_fwd,
                    "inbox_bwd": inbox_bwd,
                    "ring": ring,
                    "grads": grads,
                    "loss_sum": loss_sum,
                    "aux_sum": aux_sum,
                }, None

            carry, _ = lax.scan(rotation, carry0, jnp.arange(T))
            # merge lane contributions for replicated params; loss lives on
            # the last lane only. Grads were seeded with cotangent
            # 1/total_count, so normalize the loss the same way here.
            loss = lax.psum(carry["loss_sum"], PP_AXIS) / total_count
            if moe:
                # matches the gpipe/unpipelined objective: per-(stage,
                # microbatch) aux mean times the coefficient
                aux_mean = lax.psum(carry["aux_sum"], PP_AXIS) / (pp * M)
                loss = loss + cfg.router_aux_loss_coef * aux_mean
            head_g = jax.tree.map(
                lambda x: lax.psum(x, PP_AXIS), carry["grads"]["head"]
            )
            embed_g = jax.tree.map(
                lambda x: lax.psum(x, PP_AXIS), carry["grads"]["embed"]
            )
            # restore the leading pp-shard dim for the P(PP_AXIS) out_spec
            layers_g = jax.tree.map(lambda g: g[None], carry["grads"]["layers"])
            return layers_g, head_g, embed_g, loss

        layer_specs = jax.tree.map(lambda _: P(PP_AXIS), params["layers"])
        rep = jax.tree.map(lambda _: P(), head_params)
        layers_g, head_g, embed_g, loss = jax.shard_map(
            lane_body,
            mesh=mesh,
            in_specs=(layer_specs, rep, P(), P(), P()),
            out_specs=(layer_specs, rep, P(), P()),
            axis_names={PP_AXIS},
            check_vma=False,
        )(params["layers"], head_params, params["embed"],
          ids_mb, lab_mb)

        # reassemble a grads tree shaped like params. The embedding grad has
        # two sources: lane-0 embedding bwd (embed_g) and — when tied — the
        # last lane's head (head_g["embed"]); separate accumulators avoid
        # double-psum of a single buffer.
        grads: Params = {
            "layers": layers_g,
            "final_norm": head_g["final_norm"],
            "embed": jax.tree.map(
                lambda a, b: a + b, embed_g, head_g["embed"]
            ),
        }
        if "lm_head" in params:
            grads["lm_head"] = head_g["lm_head"]
        # pin grad shardings to the param specs: the manual-pp shard_map
        # leaves them partially unspecified, and the combination with ZeRO's
        # dp-sharded optimizer update trips XLA's SPMD partitioner otherwise
        grads = jax.tree.map(
            lambda g, s: constrain(g, s),
            grads,
            self.specs(),
            is_leaf=lambda x: isinstance(x, P),
        )
        return loss, grads

    def _interleaved_loss_and_grad(
        self, params: Params, input_ids: jax.Array, labels: jax.Array
    ) -> Tuple[jax.Array, Params]:
        """Interleaved VPP with a 1F1B-grade memory-bounded backward.

        Executes the host-simulated :class:`..pipeline.scheduler
        .Interleaved1F1BPlan` (reference ``TrainInterleavedSchedule``
        scheduler.py:256,319-353 interleaves fwd AND bwd per model chunk):
        each rotation every lane runs at most one virtual-stage forward and
        one manual-VJP backward. Saved stage inputs live in a stash ring of
        ``plan.stash_depth`` entries (≈ 2·pp·V) — O(pp·V), bounded in M,
        unlike the autodiff interleaved backward that stashes every
        rotation's stream (O(M); ``memory_bounded_backward=False``
        restores it). Chunk-indexed state uses one-hot masked
        reads/updates: a scatter-add at a lane-dependent index aborts the
        partial-manual partitioner (docs/moe_1f1b_tp.md class); the stash
        ring's write index t % D is lane-independent so the plain
        dynamic-update pattern of the V=1 executor stays safe.
        """
        cfg = self.config
        pp, M, V = self._pp(), self.num_microbatches, self.num_model_chunks
        gbs, S = input_ids.shape
        if gbs % M != 0:
            raise ValueError(f"batch {gbs} not divisible by microbatches {M}")
        mbs = gbs // M
        H = cfg.hidden_size
        mesh = parallel_state.get_parallel_state().mesh

        from neuronx_distributed_llama3_2_tpu.pipeline.scheduler import (
            Interleaved1F1BPlan,
        )

        plan = Interleaved1F1BPlan(M, V, pp)
        D = plan.stash_depth
        T = plan.num_rotations
        self._note_compiled(T)
        split_head = self.head_sequence_split and pp > 1

        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mbs, S))
        sin, cos = self.model._rope(S)
        ids_mb = input_ids.reshape(mbs, M, S).swapaxes(0, 1)
        lab_mb = labels.reshape(mbs, M, S).swapaxes(0, 1)

        from neuronx_distributed_llama3_2_tpu.parallel.loss import valid_token_mask

        total_count = jnp.maximum(
            valid_token_mask(labels[:, 1:], cfg.vocab_size)
            .astype(jnp.float32)
            .sum(),
            1.0,
        )

        embed = self.model._embed()
        head_params = self._head_params(params)
        moe = self._is_moe()
        aux_ct = (
            jnp.float32(cfg.router_aux_loss_coef / (pp * V * M))
            if moe
            else jnp.float32(0.0)
        )

        # static plan → (T, pp) gather tables
        def tbl(attr):
            return jnp.asarray(
                [getattr(st, attr) for st in plan.steps_], jnp.int32
            )

        tables = {
            k: tbl(k)
            for k in (
                "f_chunk", "f_mb", "f_admit", "f_final", "b_chunk", "b_mb",
                "b_first", "b_read_slot", "recv_f_chunk", "recv_b_chunk",
            )
        }
        tables["head_mb"] = jnp.asarray(
            [st.head_mb for st in plan.steps_], jnp.int32
        )
        tables["t"] = jnp.arange(T, dtype=jnp.int32)

        def stage_fwd(chunk_layers, x):
            return self._scan_stage(chunk_layers, x, sin, cos, positions)

        def lane_body(stage_layers, head_p, embed_p, ids_all, lab_all):
            # (V, 1, Lv, ...) per lane → (V, Lv, ...)
            stage_layers = jax.tree.map(lambda p: p[:, 0], stage_layers)
            s = lax.axis_index(PP_AXIS)
            fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
            bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]
            is_last = s == pp - 1

            def oh_stream(idx):
                """(V, 1, 1, 1) one-hot over chunk wait slots; idx<0 ⇒ 0."""
                return (
                    (jnp.arange(V) == idx).astype(jnp.float32)
                )[:, None, None, None]

            zeros_g = {
                "layers": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), stage_layers
                ),
                "head": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), head_p
                ),
                "embed": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), embed_p
                ),
            }
            carry0 = {
                "inbox_f": jnp.zeros((mbs, S, H), cfg.dtype),
                "inbox_b": jnp.zeros((mbs, S, H), cfg.dtype),
                "fwait": jnp.zeros((V, mbs, S, H), cfg.dtype),
                "bwait": jnp.zeros((V, mbs, S, H), cfg.dtype),
                "stash": jnp.zeros((D, mbs, S, H), cfg.dtype),
                "grads": zeros_g,
                "loss_sum": jnp.float32(0.0),
                "aux_sum": jnp.float32(0.0),
            }

            def rotation(carry, xs):
                fc = xs["f_chunk"][s]
                fm = xs["f_mb"][s]
                fad = xs["f_admit"][s]
                ffin = xs["f_final"][s]
                bc = xs["b_chunk"][s]
                bm = xs["b_mb"][s]
                bfir = xs["b_first"][s]
                bslot = xs["b_read_slot"][s]
                rfc = xs["recv_f_chunk"][s]
                rbc = xs["recv_b_chunk"][s]
                head_m = xs["head_mb"]
                t = xs["t"]

                # ---- land last rotation's streams in their wait slots ----
                mf = oh_stream(rfc).astype(cfg.dtype)
                fwait = carry["fwait"] * (1 - mf) + carry["inbox_f"][None] * mf
                mb_in = oh_stream(rbc).astype(cfg.dtype)
                bwait = carry["bwait"] * (1 - mb_in) + carry["inbox_b"][None] * mb_in

                # ---- forward: consume wait slot / fresh admission --------
                fwd_valid = fc >= 0
                ids_f = lax.dynamic_index_in_dim(
                    ids_all, jnp.clip(fm, 0, M - 1), axis=0, keepdims=False
                )
                x_embed = embed(embed_p, ids_f).astype(cfg.dtype)
                sel_f = oh_stream(fc).astype(cfg.dtype)
                x_wait = jnp.sum(sel_f * fwait, axis=0)
                x_in = jnp.where(fad > 0, x_embed, x_wait)
                consume_f = oh_stream(
                    jnp.where(fad > 0, -1, fc)
                ).astype(cfg.dtype)
                fwait = fwait * (1 - consume_f)

                # stash ring write at the lane-INDEPENDENT index t % D
                old = lax.dynamic_index_in_dim(
                    carry["stash"], t % D, axis=0, keepdims=False
                )
                stash = lax.dynamic_update_index_in_dim(
                    carry["stash"], jnp.where(fwd_valid, x_in, old),
                    t % D, axis=0,
                )

                w_f = jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(
                        p, jnp.clip(fc, 0, V - 1), axis=0, keepdims=False
                    ),
                    stage_layers,
                )
                y, aux_f = stage_fwd(w_f, x_in)
                y = y.astype(cfg.dtype)

                # ---- backward: consume waiting cotangent -----------------
                bwd_valid = bc >= 0
                sel_b = oh_stream(bc).astype(cfg.dtype)
                dy_in = jnp.sum(sel_b * bwait, axis=0)
                bwait = bwait * (1 - sel_b)

                # ---- head (after bwd consumption, before its deposit) ----
                head_valid = head_m >= 0
                lab_h = lax.dynamic_index_in_dim(
                    lab_all, jnp.clip(head_m, 0, M - 1), axis=0, keepdims=False
                )
                if split_head:
                    y_bcast = _psum_pp(
                        jnp.where(is_last & (ffin > 0), y, jnp.zeros_like(y))
                    )

                    def head_fn(hp, h):
                        return self._head_loss_sum_slice(hp, h, lab_h, s, pp)

                    loss_m, head_vjp = jax.vjp(head_fn, head_p, y_bcast)
                    dhead, dh_slice = head_vjp(jnp.float32(1.0) / total_count)
                    dh = _psum_pp(dh_slice)
                    head_w = jnp.where(head_valid, 1.0, 0.0)
                else:

                    def head_fn(hp, h):
                        return self._head_loss_sum(hp, h, lab_h)

                    loss_m, head_vjp = jax.vjp(head_fn, head_p, y)
                    dhead, dh = head_vjp(jnp.float32(1.0) / total_count)
                    head_w = jnp.where(is_last & (ffin > 0), 1.0, 0.0)
                loss_sum = carry["loss_sum"] + head_w * loss_m
                # deposit dh into the LOCAL final-chunk cotangent slot on
                # the last lane (the plan's phase-4 head landing)
                dep = oh_stream(
                    jnp.where(is_last & (ffin > 0), V - 1, -1)
                ).astype(cfg.dtype)
                bwait = bwait * (1 - dep) + dh.astype(cfg.dtype)[None] * dep

                # ---- backward compute (manual VJP, stashed input) --------
                x_saved = lax.dynamic_index_in_dim(
                    stash, jnp.clip(bslot, 0, D - 1), axis=0, keepdims=False
                )
                w_b = jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(
                        p, jnp.clip(bc, 0, V - 1), axis=0, keepdims=False
                    ),
                    stage_layers,
                )
                _, stage_vjp = jax.vjp(
                    lambda w, x: stage_fwd(w, x), w_b, x_saved
                )
                dw, dx = stage_vjp((dy_in.astype(cfg.dtype), aux_ct))

                ids_b = lax.dynamic_index_in_dim(
                    ids_all, jnp.clip(bm, 0, M - 1), axis=0, keepdims=False
                )
                _, embed_vjp = jax.vjp(lambda e: embed(e, ids_b), embed_p)
                (dembed,) = embed_vjp(dx)

                g = carry["grads"]
                bwd_f = bwd_valid.astype(jnp.float32)
                # one-hot accumulate into the (V, Lv, ...) chunk grads — a
                # dynamic-index scatter-ADD here aborts the partitioner
                oh_v = (jnp.arange(V) == bc).astype(jnp.float32)
                grads = {
                    "layers": jax.tree.map(
                        lambda a, d: a
                        + oh_v.reshape((V,) + (1,) * d.ndim)
                        * (bwd_f * d.astype(jnp.float32))[None],
                        g["layers"], dw,
                    ),
                    "head": jax.tree.map(
                        lambda a, d: a + head_w * d.astype(jnp.float32),
                        g["head"], dhead,
                    ),
                    "embed": jax.tree.map(
                        lambda a, d: a
                        + (bwd_f * (bfir > 0).astype(jnp.float32))
                        * d.astype(jnp.float32),
                        g["embed"], dembed,
                    ),
                }
                aux_sum = carry["aux_sum"] + jnp.where(
                    fwd_valid, aux_f.astype(jnp.float32), 0.0
                )

                # ---- exchange ----
                inbox_f = lax.ppermute(y, PP_AXIS, fwd_perm)
                inbox_b = lax.ppermute(dx.astype(cfg.dtype), PP_AXIS, bwd_perm)
                return {
                    "inbox_f": inbox_f,
                    "inbox_b": inbox_b,
                    "fwait": fwait,
                    "bwait": bwait,
                    "stash": stash,
                    "grads": grads,
                    "loss_sum": loss_sum,
                    "aux_sum": aux_sum,
                }, None

            carry, _ = lax.scan(rotation, carry0, tables)
            loss = lax.psum(carry["loss_sum"], PP_AXIS) / total_count
            if moe:
                aux_mean = lax.psum(carry["aux_sum"], PP_AXIS) / (pp * V * M)
                loss = loss + cfg.router_aux_loss_coef * aux_mean
            head_g = jax.tree.map(
                lambda x: lax.psum(x, PP_AXIS), carry["grads"]["head"]
            )
            embed_g = jax.tree.map(
                lambda x: lax.psum(x, PP_AXIS), carry["grads"]["embed"]
            )
            # restore the pp-shard dim for the P(None, PP_AXIS) out_spec
            layers_g = jax.tree.map(
                lambda g: g[:, None], carry["grads"]["layers"]
            )
            return layers_g, head_g, embed_g, loss

        layer_specs = jax.tree.map(lambda _: P(None, PP_AXIS), params["layers"])
        rep = jax.tree.map(lambda _: P(), head_params)

        from neuronx_distributed_llama3_2_tpu.parallel.layers import (
            shardmap_cpu_bf16_workaround,
        )

        layers_in, restore_layers = shardmap_cpu_bf16_workaround(
            params["layers"]
        )

        def lane_body_restored(layers_l, head_p, embed_p, ids_all, lab_all):
            return lane_body(
                restore_layers(layers_l), head_p, embed_p, ids_all, lab_all
            )

        layers_g, head_g, embed_g, loss = jax.shard_map(
            lane_body_restored,
            mesh=mesh,
            in_specs=(layer_specs, rep, P(), P(), P()),
            out_specs=(layer_specs, rep, P(), P()),
            axis_names={PP_AXIS},
            check_vma=False,
        )(layers_in, head_params, params["embed"], ids_mb, lab_mb)

        grads: Params = {
            "layers": layers_g,
            "final_norm": head_g["final_norm"],
            "embed": jax.tree.map(
                lambda a, b: a + b, embed_g, head_g["embed"]
            ),
        }
        if "lm_head" in params:
            grads["lm_head"] = head_g["lm_head"]
        grads = jax.tree.map(
            lambda g, sp: constrain(g, sp),
            grads,
            self.specs(),
            is_leaf=lambda x: isinstance(x, P),
        )
        return loss, grads
