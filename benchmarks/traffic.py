"""The one general traffic generator. A traffic mix is a data file of
parameters (``traffic/<mix>.json``); this module turns it and ``--seed`` into
arrival times, prompts and training batches. Nothing here touches JAX.

Steadiness comes from fixing the *work*: prompt lengths are a fixed multiset
(the quantiles of the stated distribution), and an open loop has a fixed number
of arrivals in its horizon — a Poisson process conditioned on its count, whose
arrival times are then uniform order statistics. An open loop replays one
schedule, drawn from the mix's ``schedule_seed`` (which request is due when, how
long it is, which prefix it shares), in every run, as a recorded trace would be
replayed, and ``--seed`` decides the token ids: a median over some tens of
requests then varies with the system and not with the draw (with the schedule
drawn from ``--seed``, ``ttft_p50_ms`` over 72 requests spread 1.0 % and 4.1 %
in two sets of six runs on the v5e; pinned, 1.0 % and 1.8 %). A closed loop has
no schedule: its clients take the pool in turn, in the seed's order.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: List[int]
    prefix_id: Optional[int]   # which shared system prompt it starts with
    due_s: Optional[float] = None   # open loop: seconds after traffic start
    measured: bool = True      # False: lead-in or traced tail, not counted


def length_multiset(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``dist`` — the same
    multiset for every seed."""
    kind = dist["dist"]
    qs = (np.arange(n) + 0.5) / n
    if kind == "log_uniform":
        vals = dist["low"] * (dist["high"] / dist["low"]) ** qs
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [int(round(v)) for v in vals]


def arrival_times(arrivals: Dict[str, Any], horizon_s: float, seed: int, stream: int = 0) -> List[float]:
    """Due times in ``[0, horizon_s)``: ``round(rate * horizon)`` of them, with
    gamma-distributed gaps of coefficient of variation ``cv`` scaled to fill
    the horizon. ``cv = 1`` is the Poisson process conditioned on its count;
    ``cv > 1`` is burstier."""
    n = int(round(float(arrivals["rate_rps"]) * horizon_s))
    if n <= 0:
        return []
    cv = float(arrivals.get("cv", 1.0))
    rng = np.random.default_rng([int(seed), 0xA221, int(stream)])
    gaps = rng.gamma(shape=1.0 / (cv * cv), scale=1.0, size=n + 1)
    times = np.cumsum(gaps)[:-1] / gaps.sum() * horizon_s
    return [float(t) for t in times]


def build_requests(traffic: Dict[str, Any], n: int, vocab: int, seed: int, stream: int = 0) -> List[Request]:
    """``n`` requests: lengths from the fixed multiset, token ids from the
    seed, their order from the mix's ``schedule_seed`` (an open loop's) or else
    the seed (and ``stream``, so that a run's segments differ). With
    ``"sharing"``, a ``share`` of the requests (spread evenly over the lengths)
    start with one of ``prefixes`` shared system prompts of ``prefix_tokens``
    tokens — the same ones in every stream of a seed — and are at least
    ``min_own_tokens`` longer than it."""
    lengths = length_multiset(traffic["prompt_tokens"], n)
    sharing = traffic.get("sharing") or {}
    share = float(sharing.get("share", 0.0))
    n_prefix = int(sharing.get("prefixes", 0))
    prefix_tokens = int(sharing.get("prefix_tokens", 0))
    min_own = int(sharing.get("min_own_tokens", 16))
    shared_rng = np.random.default_rng([int(seed), 0x5EED])
    prefixes = [
        shared_rng.integers(1, vocab, prefix_tokens).tolist() for _ in range(n_prefix)
    ]
    rng = np.random.default_rng([int(seed), 0x7E57, int(stream)])
    plan = []
    credit, next_prefix = 0.0, 0
    for length in lengths:          # ascending: sharers spread over all sizes
        credit += share
        pid = None
        if n_prefix and credit >= 1.0:
            credit -= 1.0
            pid = next_prefix
            next_prefix = (next_prefix + 1) % n_prefix
            length = max(length, prefix_tokens + min_own)
        plan.append((length, pid))
    order = np.random.default_rng(
        [int(traffic.get("schedule_seed", seed)), 0x04DE, int(stream)]
    ).permutation(n)
    out = []
    for i, j in enumerate(order):
        length, pid = plan[j]
        own = length - (prefix_tokens if pid is not None else 0)
        body = rng.integers(1, vocab, own).tolist()
        out.append(Request(
            index=i, prompt=(prefixes[pid] + body) if pid is not None else body,
            prefix_id=pid,
        ))
    return out


def open_loop(traffic: Dict[str, Any], seconds: float, vocab: int, seed: int,
              tail_s: float = 0.0) -> List[Request]:
    """Requests with due times over three segments: the lead-in, the measured
    window of ``seconds``, and an optional tail (the traced segment). Each
    segment has its own fixed count (rate x its length) and its own fixed
    length multiset; the mix's ``schedule_seed`` decides their instants and
    their order, so every run puts the same requests at the same instants
    *inside the window*, and the seed decides their tokens."""
    lead = float(traffic.get("lead_s", 0.0))
    out: List[Request] = []
    segments = ((0.0, lead, False), (lead, float(seconds), True), (lead + seconds, float(tail_s), False))
    for stream, (start, length, measured) in enumerate(segments):
        if length <= 0:
            continue
        times = arrival_times(traffic["arrivals"], length, traffic["schedule_seed"], stream)
        reqs = build_requests(traffic, len(times), vocab, seed, stream)
        out += [
            dataclasses.replace(r, index=len(out) + k, due_s=start + t, measured=measured)
            for k, (r, t) in enumerate(zip(reqs, times))
        ]
    return out


def closed_loop(traffic: Dict[str, Any], vocab: int, seed: int) -> List[Request]:
    """The fixed multiset of ``requests`` prompts the clients draw from in
    turn (the run cycles through it if the window outlasts it)."""
    return build_requests(traffic, int(traffic["requests"]), vocab, seed)


def train_batch(global_batch: int, seq_len: int, vocab: int, seed: int, step: int) -> np.ndarray:
    """Token ids of one training step, uniform over the vocabulary: a fresh
    batch for every step, the same for the same ``(seed, step)``."""
    rng = np.random.default_rng([int(seed), 0xB47C, int(step)])
    return rng.integers(0, vocab, (global_batch, seq_len), dtype=np.int32)
