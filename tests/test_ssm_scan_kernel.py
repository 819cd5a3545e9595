"""The chunk scan kernel (``kernels/ssm_scan_pallas.py``), interpreted on the
CPU: against ``selective_scan``'s loop a row on the rows that are real — a
padded block, a carried state, whole trips past the last live row, a pool in
bfloat16 — then which form a prefill program holds in each kernel mode, and the
paged engine's tokens through the kernels on the cases only the CPU tests see
(``PERF.md`` section 7): a carry across chunks, a padded last chunk, a reused
slot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.kernels.ssm_scan_pallas import chunk_scan_fits, ssm_chunk_scan
from neuronx_distributed_llama3_2_tpu.models.jamba import selective_scan
# the family and the scaled weights are that file's fixtures
from tests.test_jamba_serving import (  # noqa: F401
    TINY, clean, fam, params, prompts_of, reference_tokens, serving,
)

# (sequences, rows, N, D, the pool's dtype, live rows a sequence)
CASES = {
    "tiny-padded": (2, 24, 8, 128, jnp.float32, (24, 13)),
    "two-channel-blocks-a-dead-trip": (2, 32, 16, 2048, jnp.float32, (32, 9)),
    "narrow-block-bf16-pool": (1, 16, 16, 640, jnp.bfloat16, (16,)),
}


def rows_of(name):
    b, t, n, d, pool_dtype, live = CASES[name]
    keys = jax.random.split(jax.random.key(len(name)), 5)
    h = jax.random.normal(keys[0], (b, n, d), jnp.float32).astype(pool_dtype)
    delta = jax.nn.softplus(jax.random.normal(keys[1], (b, t, d)) - 3.0)
    c = jax.random.normal(keys[2], (b, t, d)).astype(jnp.bfloat16)
    b_t, c_t = jax.random.normal(keys[3], (b, t, n)), jax.random.normal(keys[4], (b, t, n))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, d))
    return (h, delta, c, b_t, c_t, a, jnp.ones((d,), jnp.float32), jnp.asarray(live, jnp.int32))


@pytest.mark.parametrize("name", CASES)
def test_the_kernel_is_the_loop_a_row_on_every_live_row(name):
    """y of the live rows and the state after them, to float32 round-off (the
    sum over N runs in another order); the state's dtype is the pool's."""
    args = rows_of(name)
    want_y, want_h = jax.jit(selective_scan)(*args)
    got_y, got_h = jax.jit(lambda *a: selective_scan(*a, kernel=True))(*args)
    assert got_h.dtype == args[0].dtype and got_y.dtype == jnp.float32
    alive = np.arange(args[1].shape[1])[None, :] < np.asarray(args[-1])[:, None]
    scale = float(jnp.max(jnp.abs(want_y)))
    assert np.abs(np.asarray(got_y) - np.asarray(want_y))[alive].max() <= 2e-6 * scale
    np.testing.assert_allclose(
        np.asarray(got_h, np.float32), np.asarray(want_h, np.float32), rtol=0, atol=2e-6 * scale)


def test_a_block_that_is_not_whole_trips_of_whole_lanes_keeps_the_loop():
    assert chunk_scan_fits(512, 5120) and chunk_scan_fits(8, 128)
    assert not chunk_scan_fits(12, 128) and not chunk_scan_fits(16, 64)
    h, delta, c, b_t, c_t, a, d_skip, live = rows_of("tiny-padded")
    with pytest.raises(ValueError, match="whole trips"):
        ssm_chunk_scan(h, delta[:, :12], delta[:, :12], b_t[:, :12], c_t[:, :12], a, live)
    # selective_scan falls back by itself
    jaxpr = jax.make_jaxpr(lambda *x: selective_scan(*x, kernel=True))(
        h, delta[:, :12], c[:, :12], b_t[:, :12], c_t[:, :12], a, d_skip, live)
    assert "pallas_call" not in str(jaxpr)


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_the_kernel_mode_decides_which_scan_a_prefill_program_holds(params, mode, monkeypatch):
    """``reference`` keeps the ``lax.scan`` (the CPU tier's twin), ``interpret``
    holds one ``ssm_chunk_scan`` in each run of state-space layers; a decode step
    holds none (its own kernel is ``tests/test_ssm_step_kernel.py``'s)."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    assert model.chunk_scan() == ("kernel" if mode == "interpret" else "loop")
    pool = model.init_paged_cache(8, 16, state_blocks=3)
    tables = jnp.asarray([[2, 3], [0, 0]], jnp.int32)
    chunk = str(jax.make_jaxpr(lambda p, c: model.forward(
        p, c, jnp.ones((1, 16), jnp.int32), jnp.zeros((1,), jnp.int32), context_encode=True,
        block_tables=tables[:1], state_tables=jnp.asarray([[1]], jnp.int32), kv_limit=32))(params, pool))
    assert chunk.count("ssm_chunk_scan") == (2 if mode == "interpret" else 0)
    step = str(jax.make_jaxpr(lambda p, c: model.decode_step(
        p, c, jnp.asarray([5, 0], jnp.int32), jnp.asarray([17, 0], jnp.int32), tables, kv_limit=32,
        state_tables=jnp.asarray([[1], [0]], jnp.int32)))(params, pool))
    assert "ssm_chunk_scan" not in step


def test_the_engine_through_the_kernel_gives_the_references_tokens(fam, params, monkeypatch):
    """Six requests on four lanes, chunks of 16: 50 = 16 + 16 + 16 + 2 carries
    a state three times and pads its last chunk, the fifth and sixth requests
    run through used slots; every token the float32 reference's."""
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    prompts = prompts_of(np.random.default_rng(3), (37, 21, 5, 50, 16, 33))
    srv = serving(params, new_tokens=8, trace_enabled=True)
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == reference_tokens(fam, params, prompt, 8), (rid, len(prompt))
    assert srv.metrics.state_resets == len(prompts)
    assert srv.metrics.state_kernel_steps == srv.metrics.decode_steps > 0      # every pdecode held the step kernel
    clean(srv)
