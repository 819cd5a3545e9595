"""The same reading as ``rag_expert_useful_flop_share``, under this cell's own
name because a per-layer metric names the one end-to-end metric it moves: on a
full bucket the all-experts path computes 256 experts for the 8 a token asked
for, 3.1 %."""
from benchmarks.layer_metrics.rag_expert_useful_flop_share import read  # noqa: F401
