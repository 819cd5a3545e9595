"""Routing counters of a traced serving engine.

A serving program traced while a tap is open (``PagedServingEngine`` opens
one around ``pctx``, ``psfx`` and ``pdecode`` when ``trace_enabled``) returns,
beside its own outputs, how many of its *live* tokens it routed to each
expert, summed over the layers — a row of bucket padding or an idle lane
routes like any other row and all of them alike, so the tap is told which
rows carry a request's token and counts those — and remembers two static
facts of its trace: which no-drop dispatch path the expert block took
(``selective`` or ``all``) and how many (token, expert) pairs that path
*computes* — ``T·k`` a layer for the selective gather, ``T·E`` where every
expert sees every token. Routed against computed pairs is the share of the
expert FLOPs that a request's token asked for.

With no tap open nothing here runs and every traced program is what it was:
the tap is consulted at trace time only, by :class:`.experts.ExpertMLPs`
(which records) and by the decode model's layer loop (which carries each
layer's counts out of the ``lax.scan`` body that traced them).
"""

from __future__ import annotations

import contextlib
from typing import List, Optional, Set, Tuple

import jax
import jax.numpy as jnp

_OPEN: List["RoutingTap"] = []


class RoutingTap:
    def __init__(self, live: jax.Array) -> None:
        self.live = live.reshape(-1)          # (T,) bool: the row is a request's token
        self._pending: List[jax.Array] = []   # (E,) counts traced since take_layer()
        self._pending_pairs = 0
        self._layer_pairs = 0
        self.tokens_per_expert: Optional[jax.Array] = None   # (E,) int32 over all layers
        # (first id, count) of the experts the blocks hold: all of the
        # router's, unless the model holds a share of them (MoEConfig.experts_held)
        self.held: Optional[Tuple[int, int]] = None
        self.pairs_computed = 0
        self.paths: Set[str] = set()

    def record(self, idx: jax.Array, num_experts: int, path: str, pairs_computed: int,
               held: Optional[Tuple[int, int]] = None) -> None:
        """One expert block routed ``idx`` (T, k) over ``num_experts`` and
        computes ``pairs_computed`` (token, expert) pairs on ``path``;
        ``held`` = (first id, count) of the experts it holds (all, if None)."""
        self.held = held or (0, num_experts)
        # compare-to-iota, not a scatter-add: see ExpertMLPs.forward_all_experts
        hit = idx[:, :, None] == jnp.arange(num_experts, dtype=idx.dtype)
        hit = hit & self.live[:, None, None]
        self._pending.append(jnp.sum(hit, axis=(0, 1), dtype=jnp.int32))
        self._pending_pairs += int(pairs_computed)
        self.paths.add(path)

    def take_layer(self) -> Optional[jax.Array]:
        """Counts of the expert blocks traced since the last call, summed
        ((E,) int32; None where there was none) — to be returned from the
        body that traced them."""
        counts = sum(self._pending[1:], self._pending[0]) if self._pending else None
        self._layer_pairs = self._pending_pairs
        self._pending, self._pending_pairs = [], 0
        return counts

    def commit(self, per_layer: Optional[jax.Array], num_layers: int) -> None:
        """``per_layer`` (L, E): what :meth:`take_layer` returned for each
        layer, stacked by the loop that ran them."""
        if per_layer is None:
            return
        total = jnp.sum(per_layer, axis=0)
        self.tokens_per_expert = (
            total if self.tokens_per_expert is None else self.tokens_per_expert + total
        )
        self.pairs_computed += self._layer_pairs * num_layers


def current() -> Optional[RoutingTap]:
    return _OPEN[-1] if _OPEN else None


@contextlib.contextmanager
def open_tap(live: jax.Array):
    """``live``: the program's (lanes, rows) — flattened as the expert block
    sees them — that carry a request's token."""
    tap = RoutingTap(live)
    _OPEN.append(tap)
    try:
        yield tap
    finally:
        _OPEN.pop()
