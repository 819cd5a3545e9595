"""Medusa decoding: tree-based multi-token speculation with extra LM heads.

TPU-native replacement for the reference's Medusa utilities
(``utils/medusa_utils.py``: ``generate_medusa_buffers`` :32 — static tree
buffers; ``generate_candidates`` :120 — cartesian/tree candidate assembly;
``evaluate_posterior`` :151 — greedy acceptance; ``update_inference_inputs``
:175 — frontier bookkeeping) and the Medusa head wiring the reference keeps
in its inference model wrappers.

Design for the jit/AOT engine here:

- **Buffers are static numpy** computed once per ``medusa_choices`` tree —
  shapes never depend on data, so the verification program compiles once.
- **Verification is one forward** of the whole candidate tree through
  :class:`..inference.model.LlamaDecode` using its ``tree=`` mode: tree
  tokens rope at ``position + depth`` and attend ancestors only (the
  reference builds the same tree attention into its traced medusa model).
- **Commit is a second forward** over the accepted path (≤ K+1 tokens):
  it rewrites the accepted tokens' KV at the true frontier rows (tree rows
  hold a superset written branch-interleaved) and yields the next round's
  base+medusa logits. Two fixed-shape programs per round replace up to
  K+1 sequential decode steps.

Greedy semantics: emitted tokens are provably the target model's greedy
continuation (acceptance only keeps candidates matching the base head's
argmax — reference evaluate_posterior :163-167).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_llama3_2_tpu.inference.engine import (
    InferenceEngine, pick_bucket, refuse_unless_positional,
)
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    ColumnParallelLinear,
    Params,
)

#: default tree from the Medusa paper (reference mc_sim_7b_63 style, trimmed)
DEFAULT_MEDUSA_CHOICES: Tuple[Tuple[int, ...], ...] = (
    (0,), (0, 0), (1,), (0, 1), (2,), (0, 0, 0), (1, 0), (0, 2),
)


@dataclasses.dataclass(frozen=True)
class MedusaBuffers:
    """Static tree buffers (reference generate_medusa_buffers :32)."""

    # tree_indices[i]: which flat candidate (1 + head*topk + rank) feeds
    # tree slot i; slot 0 is the base-head token
    tree_indices: np.ndarray      # (L,) int32
    depths: np.ndarray            # (L,) int32  (0 for the root)
    ancestor_mask: np.ndarray     # (L, L) bool, diagonal True
    # retrieve_indices[p]: tree slots of root→leaf path p, -1-padded
    retrieve_indices: np.ndarray  # (P, max_depth+1) int32
    topk: int

    @property
    def tree_len(self) -> int:
        return len(self.tree_indices)

    def packed_parents(self) -> np.ndarray:
        """The tree as a packed parents vector — the form the paged
        engine's tree-verify path (``LlamaDecode.tree_verify_step``,
        ``serving/drafter.py`` ``propose_tree``) consumes: ``parents[i]``
        is slot ``i``'s parent slot, ``parents[0] == 0`` (the root is its
        own parent by convention). Slots are prefix-sorted by (depth,
        ranks), so parents always precede children — a Medusa static tree
        plugs straight into the packed ancestor-bitmask kernel operand
        with draft-head top-k tokens filling the node slots."""
        parents = np.zeros(self.tree_len, np.int32)
        for i in range(1, self.tree_len):
            anc = np.nonzero(
                self.ancestor_mask[i] & (self.depths == self.depths[i] - 1)
            )[0]
            parents[i] = int(anc[0])
        return parents


def generate_medusa_buffers(
    medusa_choices: Sequence[Sequence[int]] = DEFAULT_MEDUSA_CHOICES,
    topk: int = 10,
) -> MedusaBuffers:
    """Build the static tree from path choices: each choice is a tuple of
    per-head top-k ranks, e.g. (0, 1) = head0's top-1 then head1's top-2."""
    paths = sorted(set(tuple(c) for c in medusa_choices), key=lambda p: (len(p), p))
    if not paths:
        raise ValueError("medusa_choices must be non-empty")
    for p in paths:
        if any(r >= topk for r in p):
            raise ValueError(f"choice {p} exceeds topk={topk}")

    # slot 0 = base token (root); remaining slots = unique path prefixes
    prefixes: List[Tuple[int, ...]] = []
    for p in paths:
        for d in range(1, len(p) + 1):
            pre = p[:d]
            if pre not in prefixes:
                prefixes.append(pre)
    prefixes.sort(key=lambda p: (len(p), p))

    L = 1 + len(prefixes)
    slot_of = {(): 0}
    tree_indices = np.zeros(L, np.int32)
    depths = np.zeros(L, np.int32)
    for i, pre in enumerate(prefixes, start=1):
        slot_of[pre] = i
        head = len(pre) - 1
        rank = pre[-1]
        tree_indices[i] = 1 + head * topk + rank
        depths[i] = len(pre)

    mask = np.zeros((L, L), bool)
    for pre, slot in slot_of.items():
        for d in range(len(pre) + 1):
            mask[slot, slot_of[pre[:d]]] = True

    max_d = max(len(p) for p in paths)
    retrieve = np.full((len(paths), max_d + 1), -1, np.int32)
    for pi, p in enumerate(paths):
        for d in range(len(p) + 1):
            retrieve[pi, d] = slot_of[p[:d]]
    return MedusaBuffers(
        tree_indices=tree_indices,
        depths=depths,
        ancestor_mask=mask,
        retrieve_indices=retrieve,
        topk=topk,
    )


@dataclasses.dataclass(frozen=True)
class MedusaHeads:
    """K residual-block heads over the final hidden state (the standard
    Medusa head: h + SiLU(W·h), then an LM head per head)."""

    hidden_size: int
    vocab_size: int
    num_heads: int = 3
    dtype: Any = jnp.float32

    def _res(self) -> ColumnParallelLinear:
        return ColumnParallelLinear(
            self.hidden_size, self.hidden_size, use_bias=True,
            gather_output=True, dtype=self.dtype,
        )

    def _lm(self) -> ColumnParallelLinear:
        return ColumnParallelLinear(
            self.hidden_size, self.vocab_size, dtype=self.dtype
        )

    def init(self, key: jax.Array) -> Params:
        keys = jax.random.split(key, 2 * self.num_heads)
        return {
            "heads": [
                {
                    "res": self._res().init(keys[2 * i]),
                    "lm": self._lm().init(keys[2 * i + 1]),
                }
                for i in range(self.num_heads)
            ]
        }

    def specs(self) -> Params:
        return {
            "heads": [
                {"res": self._res().specs(), "lm": self._lm().specs()}
                for _ in range(self.num_heads)
            ]
        }

    def __call__(self, params: Params, hidden: jax.Array) -> jax.Array:
        """hidden (..., H) → medusa logits (K, ..., V)."""
        outs = []
        for hp in params["heads"]:
            h = hidden + jax.nn.silu(self._res()(hp["res"], hidden))
            outs.append(self._lm()(hp["lm"], h))
        return jnp.stack(outs, axis=0)


# same shape as draft-speculation results — one result type for both
# speculation flavors
from neuronx_distributed_llama3_2_tpu.inference.speculative import (
    SpeculativeResult as MedusaResult,
)


class MedusaDecoder:
    """Greedy Medusa decode of one sequence through an
    :class:`..inference.engine.InferenceEngine`'s model + cache."""

    def __init__(
        self,
        engine: InferenceEngine,
        medusa_params: Params,
        buffers: MedusaBuffers = None,
        num_heads: int = 3,
    ) -> None:
        refuse_unless_positional(engine.model, "Medusa tree verification")
        self.engine = engine
        self.heads = MedusaHeads(
            engine.config.hidden_size, engine.config.vocab_size,
            num_heads=num_heads, dtype=engine.config.dtype,
        )
        self.medusa_params = medusa_params
        self.buffers = buffers or generate_medusa_buffers()
        if int(self.buffers.depths.max()) > num_heads:
            raise ValueError("tree deeper than the number of medusa heads")
        self._verify = None
        self._commit = None
        self._prefill_fn = None
        self._heads_fn = None

    # -- jitted programs ---------------------------------------------------

    def _prefill(self, prompt: Sequence[int]) -> Tuple[int, jax.Array]:
        eng = self.engine
        bucket = pick_bucket(eng.buckets, len(prompt))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(prompt)] = prompt
        if self._prefill_fn is None:
            def f(p, cache, t):
                logits, hidden, cache = self._fwd_hidden(
                    p, cache, t, jnp.zeros((1,), jnp.int32), context_encode=True
                )
                return jnp.argmax(logits, axis=-1), hidden, cache

            self._prefill_fn = jax.jit(f)
        greedy, hidden, eng.cache = self._prefill_fn(
            eng.params, eng.cache, jnp.asarray(toks)
        )
        last = len(prompt) - 1
        return int(greedy[0, last]), hidden, last

    def _fwd_hidden(self, p, cache, toks, pos, *, context_encode=False, tree=None):
        # every Medusa program funnels through here: dequantize inside jit
        # like the engine's own programs (int8-resident serving support)
        p = self.engine._live_params(p)
        hidden, cache = self.engine.model.forward(
            p, cache, toks, pos,
            context_encode=context_encode, return_hidden=True, tree=tree,
        )
        logits = self.engine.model._model()._logits(p, hidden)
        return logits, hidden, cache

    # -- one round ---------------------------------------------------------

    def _heads_topk(self, hidden, slot):
        """Jitted medusa-head top-k at one hidden slot: (Kh, topk) ids.
        Head matmuls + top_k run inside ONE program (review finding: the
        eager per-op dispatch of K LM-head-sized matmuls per round)."""
        if self._heads_fn is None:
            topk = self.buffers.topk

            def f(mp, hidden, slot):
                med = self.heads(mp, hidden[:, slot])[:, 0]  # (Kh, V)
                return jax.lax.top_k(med, topk)[1]

            self._heads_fn = jax.jit(f)
        return self._heads_fn(self.medusa_params, hidden, slot)

    def _candidates(self, base_token: int, topk_ids) -> np.ndarray:
        """Flat candidate pool [base, head0 topk..., head1 topk...] → tree
        slots (reference generate_candidates :120)."""
        bufs = self.buffers
        flat = np.concatenate([[base_token], np.asarray(topk_ids).reshape(-1)])
        return flat[bufs.tree_indices].astype(np.int32)

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 64) -> MedusaResult:
        """Round protocol (mirrors speculative.py's frontier convention —
        the newest emitted token is the *uncommitted root* of the next
        round's tree):

        - verify: forward [root, candidates...] in tree mode at positions
          ``pos + depth``. Slot 0 (the root, depth 0) is thereby committed
          at its true cache row ``pos``; candidate rows beyond are
          branch-interleaved garbage.
        - accept: longest path whose every candidate equals the greedy
          continuation of its parent slot; bonus = greedy of the last
          accepted slot. Next round's medusa logits come from the verify
          pass's hidden at that same slot — no extra forward.
        - commit: only when tokens were accepted, rewrite them at rows
          ``pos+1..`` (fixed K-token program; pad rows land beyond the new
          frontier where the prefix mask hides them until overwritten).
        """
        eng = self.engine
        bufs = self.buffers
        L = bufs.tree_len
        K = int(bufs.depths.max())  # max acceptable tokens per round
        base, hidden, last = self._prefill(prompt)
        topk_ids = self._heads_topk(hidden, last)  # (Kh, topk)
        out: List[int] = [base]
        accepted_hist: List[int] = []
        pos = len(prompt)  # committed rows; out[-1] is the uncommitted root

        # capacity: every round's verify needs L rows past the frontier;
        # refuse over-capacity requests upfront rather than silently
        # truncating (same contract as SpeculativeDecoder, speculative.py:72)
        if len(prompt) + max_new_tokens - 1 + L > eng.cache.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} + "
                f"tree {L} exceeds cache capacity {eng.cache.max_len}"
            )

        depths = jnp.asarray(bufs.depths)
        anc = jnp.asarray(bufs.ancestor_mask)
        retrieve = np.asarray(bufs.retrieve_indices)

        if self._verify is None:
            def vf(p, cache, t, pos, d=depths, a=anc):
                logits, hidden, cache = self._fwd_hidden(
                    p, cache, t, pos, tree=(d, a)
                )
                return jnp.argmax(logits, axis=-1), hidden, cache

            self._verify = jax.jit(vf)
            self._commit = jax.jit(self._fwd_hidden)
        verify, commit = self._verify, self._commit

        while len(out) < max_new_tokens:
            # capacity guard: the verify scatter must fit the cache rows
            # (out-of-bounds scatter is silently dropped — wrong tokens, no
            # error; same guard as speculative.py:72-85)
            if pos + L > eng.cache.max_len:
                break
            tree_tokens = self._candidates(out[-1], topk_ids)
            greedy_dev, hidden, eng.cache = verify(
                eng.params, eng.cache, jnp.asarray(tree_tokens[None, :]),
                jnp.asarray([pos], jnp.int32),
            )
            greedy = np.asarray(greedy_dev[0])  # (L,)

            # greedy acceptance over root→leaf paths (evaluate_posterior
            # :151): candidate at depth d survives iff it equals the model's
            # greedy continuation of its parent slot, consecutively
            best_len, best_path = 0, 0
            for pi in range(retrieve.shape[0]):
                path = retrieve[pi]
                n = 0
                for d in range(1, path.shape[0]):
                    slot = int(path[d])
                    if slot < 0:
                        break
                    if int(tree_tokens[slot]) == int(greedy[int(path[d - 1])]):
                        n += 1
                    else:
                        break
                if n > best_len:
                    best_len, best_path = n, pi

            path = retrieve[best_path]
            accepted = [int(tree_tokens[path[d]]) for d in range(1, best_len + 1)]
            last_slot = int(path[best_len])
            bonus = int(greedy[last_slot])
            accepted_hist.append(best_len)

            if best_len > 0:
                # fixed-shape commit: K tokens, padded by repeating the last
                # accepted token; pad rows fall at/after the new frontier and
                # are masked (j < position) until overwritten by later writes
                block = accepted + [accepted[-1]] * (K - best_len)
                _, _, eng.cache = commit(
                    eng.params, eng.cache, jnp.asarray([block], jnp.int32),
                    jnp.asarray([pos + 1], jnp.int32),
                )
            out.extend(accepted + [bonus])
            pos += 1 + best_len  # root + accepted committed; bonus = new root
            topk_ids = self._heads_topk(hidden, last_slot)

        return MedusaResult(tokens=out[:max_new_tokens], accepted_per_round=accepted_hist)
