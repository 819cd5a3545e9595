"""Operation and byte counts of a sparse feed-forward's two no-drop dispatch
paths, from shapes (``arith.py``'s conventions: a matmul of (m, k) by (k, n) is
``2*m*k*n`` FLOPs). The program's paths are ``moe/experts.py``
``forward_all_experts`` and ``forward_selective``.

Two rules keep a roofline share built from these under 100 %:

- the compute share counts the work the all-experts path *executes* — every
  expert over every token, chosen or not — so the time it is divided by
  cannot have done less;
- the bandwidth share counts the weight bytes a step *needs*: one read of each
  expert the step can route to. The selective path moves ``T*k`` expert slices
  whatever the routing, at least as many, so its time cannot be shorter than
  these bytes at the peak.
"""

from __future__ import annotations


def expert_pair_flops(hidden: int, width: int, glu: bool = True) -> float:
    """FLOPs of one token through one expert: gate and up (or up alone)
    ``hidden -> width``, then down ``width -> hidden``."""
    n_up = 2 if glu else 1
    return 2.0 * hidden * width * n_up + 2.0 * width * hidden


def all_experts_flops(tokens: int, hidden: int, width: int, num_experts: int,
                      layers: int, glu: bool = True) -> float:
    """FLOPs the all-experts path executes for one call over ``tokens``
    tokens: every expert's MLP over every token, and the combine
    ``(T, E) x (E, T, H) -> (T, H)`` that weights them by the gates."""
    mlp = tokens * num_experts * expert_pair_flops(hidden, width, glu)
    combine = 2.0 * tokens * num_experts * hidden
    return layers * (mlp + combine)


def useful_flop_share(top_k: int, num_experts: int) -> float:
    """The share of the all-experts MLP FLOPs that a token's router asked
    for: ``k`` of ``E`` experts."""
    return top_k / num_experts


def expert_weight_bytes(hidden: int, width: int, itemsize: int = 2, glu: bool = True) -> float:
    """Bytes of one expert's weights (gate, up, down)."""
    return float((3 if glu else 2) * hidden * width * itemsize)


def decode_needed_weight_bytes(tokens: int, top_k: int, num_experts: int, hidden: int,
                               width: int, layers: int, itemsize: int = 2,
                               glu: bool = True) -> float:
    """Expert-weight bytes one decode call over ``tokens`` lanes has to read:
    in every layer, each expert it can route to once — ``min(T*k, E)`` of
    them (routing that repeats an expert needs fewer; none needs more)."""
    experts = min(tokens * top_k, num_experts)
    return layers * experts * expert_weight_bytes(hidden, width, itemsize, glu)
