"""The benchmark of this repository: one command (``run.py``), its data
files (``configs/``, ``traffic/``), one reader per per-layer metric
(``layer_metrics/``), a plain reference per model family (``reference/``),
and the yardstick itself — traffic generation, percentile arithmetic, the
table of peaks, FLOP and byte counts, and the reduction from a profiler
trace to metrics. ``README.md`` says how to add to it."""
