"""Plain references: each family's forward pass in straightforward
``jax.numpy`` and float32 — no kernels, no cache, no batching tricks — following
the published description. Weights arrive in the program's stacked layout (a
leading layer axis) in their serving dtype and are upcast one layer (one
expert) at a time, so the reference fits beside the system under test. Callers
trace these under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs in bf16 passes."""
