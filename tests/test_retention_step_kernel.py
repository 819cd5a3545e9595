"""The one-pass state kernel (``kernels/retention_step_pallas.py``), interpreted
on the CPU in float32: against ``retention_step`` lane by lane, and what "in
place through the block table" has to mean — a permuted table, idle lanes on
the null block, every block no lane names bit for bit, the layer's offset into
the run of ``L · num_blocks`` states. Then the paged engine in both kernel
modes on the three cases only the CPU tests see (``PERF.md`` section 7): a
chunked prefill's carry, a reused block, a bucket-padded last chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.kernels.retention_step_pallas import (
    retention_state_pass,
    retention_step_paged,
)
from neuronx_distributed_llama3_2_tpu.models.brumby import feature_width, retention_step
# the family and the long-memory weights (gates near 0.95) are that file's fixtures
from tests.test_brumby_serving import (  # noqa: F401
    TINY, clean, fam, params, prompts_of, reference_tokens, serving,
)

# (head width, kv heads, query heads a kv head): tiny-brumby, and the published
# head of 128 (φ 9,216, a GQA group of 5) with 2 kv heads
GEOMETRY = {"tiny-brumby": (32, 2, 2), "head128-phi9216": (128, 2, 5)}
LAYERS, BLOCKS = 3, 6
EPS = 1e-6
MODES = ("reference", "interpret")


def make(name, lanes, dtype=jnp.float32, pool_dtype=jnp.float32):
    d, kh, groups = GEOMETRY[name]
    keys = jax.random.split(jax.random.key(len(name)), 6)
    feat = feature_width(d)
    pool = (jax.random.normal(keys[0], (LAYERS, BLOCKS, kh, feat, d), jnp.float32).astype(pool_dtype),
            (jax.random.uniform(keys[1], (LAYERS, BLOCKS, kh, feat), jnp.float32) * 8).astype(pool_dtype))
    rows = (jax.random.normal(keys[2], (lanes, kh, groups, d), dtype),
            jax.random.normal(keys[3], (lanes, kh, d), dtype),
            jax.random.normal(keys[4], (lanes, kh, d), dtype),
            jax.nn.log_sigmoid(jax.random.normal(keys[5], (lanes, kh)) + 2.0))
    return pool, rows


def run(pool, rows, index, layer):
    return jax.jit(retention_step_paged)(*pool, jnp.asarray(index, jnp.int32), jnp.int32(layer), *rows, EPS)


def close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))) <= tol * scale


@pytest.mark.parametrize("name", GEOMETRY)
def test_the_kernel_is_retention_step_lane_by_lane_through_a_permuted_table(name):
    """Blocks 4, 1, 5, 2 for lanes 0-3: y, S and z of each lane to float32
    round-off (the two sum φ's 768 / 9,216 terms in another order)."""
    index, layer = (4, 1, 5, 2), 1
    (s, z), rows = make(name, len(index))
    y, s_new, z_new = run((s, z), rows, index, layer)
    for lane, block in enumerate(index):
        want_y, want_s, want_z = retention_step(
            s[layer, block], z[layer, block], *(a[lane] for a in rows), EPS)
        close(y[lane], want_y)
        close(s_new[layer, block], want_s, 1e-6)
        close(z_new[layer, block], want_z, 1e-6)


@pytest.mark.parametrize("name", GEOMETRY)
def test_every_block_no_lane_names_comes_back_bit_for_bit(name):
    index, layer = (3, 5), 2
    (s, z), rows = make(name, len(index))
    _, s_new, z_new = run((s, z), rows, index, layer)
    named = np.zeros((LAYERS, BLOCKS), bool)
    named[layer, list(index)] = True
    for before, after in ((s, s_new), (z, z_new)):
        assert bool((after[~named] == before[~named]).all())        # in place, nothing else written
        assert not bool((after[named] == before[named]).all())


@pytest.mark.parametrize("name", GEOMETRY)
def test_two_idle_lanes_on_the_null_block_leave_the_live_lanes_alone(name):
    """Lanes 1 and 3 hold no table: both name block 0 and rewrite it in turn.
    The live lanes read what they read without them."""
    index, layer = (2, 0, 4, 0), 0
    (s, z), rows = make(name, len(index))
    y, s_new, z_new = run((s, z), rows, index, layer)
    live = [0, 2]
    alone = run((s, z), tuple(a[jnp.asarray(live)] for a in rows), (2, 4), layer)
    assert bool((y[jnp.asarray(live)] == alone[0]).all())
    for block in (2, 4):
        assert bool((s_new[layer, block] == alone[1][layer, block]).all())
        assert bool((z_new[layer, block] == alone[2][layer, block]).all())
    assert bool(jnp.isfinite(s_new[layer, 0]).all())                  # garbage by definition, but numbers
    assert bool((s_new[layer, 1] == s[layer, 1]).all()) and bool((s_new[1:] == s[1:]).all())


@pytest.mark.parametrize("layer", range(LAYERS))
def test_a_layers_states_are_found_at_index_plus_layer_times_blocks(layer):
    """The pool goes in as one run of L · num_blocks states: layer 0 is no
    offset, a later layer must not land on layer 0's block of the same index."""
    index = (1, 3)
    (s, z), rows = make("tiny-brumby", len(index))
    y, s_new, _ = run((s, z), rows, index, layer)
    for lane, block in enumerate(index):
        want_y, want_s, _ = retention_step(s[layer, block], z[layer, block], *(a[lane] for a in rows), EPS)
        close(y[lane], want_y)
        close(s_new[layer, block], want_s, 1e-6)
    others = [other for other in range(LAYERS) if other != layer]
    assert bool((s_new[jnp.asarray(others)] == s[jnp.asarray(others)]).all())


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16], ids=["f32-pool", "bf16-pool"])
def test_bfloat16_rows_over_either_pool_stay_near_retention_step(pool_dtype):
    """The published dtypes: bf16 q, k, v. The kernel's φ is the float32
    product of their values where ``retention_step`` rounds φ to bf16 first, so
    the two differ by that rounding and no more; a bf16 pool (the check's
    variant that has to fail by ``cache_tolerance``) is read and written in its
    own dtype."""
    index, layer = (2, 4, 1), 1
    (s, z), rows = make("tiny-brumby", len(index), jnp.bfloat16, pool_dtype)
    y, s_new, z_new = run((s, z), rows, index, layer)
    assert y.dtype == jnp.bfloat16 and s_new.dtype == z_new.dtype == pool_dtype
    for lane, block in enumerate(index):
        want_y, want_s, want_z = retention_step(s[layer, block], z[layer, block], *(a[lane] for a in rows), EPS)
        close(y[lane], want_y, 0.02)
        close(s_new[layer, block], want_s, 0.02)
        close(z_new[layer, block], want_z, 0.02)


def test_states_that_do_not_fit_the_rows_are_refused():
    rows = jnp.zeros((2, 2, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="do not fit rows"):
        retention_state_pass(jnp.zeros((2,), jnp.int32), rows, jnp.zeros((4, 2, 768, 16), jnp.float32),
                             groups=2, phi_block=16)
    (s, z), (q, k, v, g) = make("tiny-brumby", 2)
    with pytest.raises(ValueError, match="one width"):
        retention_step_paged(s, z, jnp.zeros((2,), jnp.int32), 0, q, k, v[..., :16], g, EPS)


# ---------------------------------------------------------------------------
# which path a program holds, and the engine on both
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_the_kernel_mode_decides_which_step_a_decode_program_holds(params, mode, monkeypatch):
    """``reference`` keeps ``retention_step`` (the CPU tier's twin), ``interpret``
    holds one ``pallas_call`` in the layer scan's body; a prefill holds none."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    assert model.uses_state_kernel() == (mode == "interpret")
    assert not LlamaDecode(TINY).uses_state_kernel()                 # a KV model never asks
    pool = model.init_paged_cache(4, 96)
    tables = jnp.asarray([[2, 0], [0, 0]], jnp.int32)
    step = jax.make_jaxpr(lambda p, c: model.decode_step(
        p, c, jnp.asarray([5, 0], jnp.int32), jnp.asarray([17, 0], jnp.int32), tables, kv_limit=96))(params, pool)
    assert str(step).count("pallas_call") == (1 if mode == "interpret" else 0)
    chunk = jax.make_jaxpr(lambda p, c: model.forward(
        p, c, jnp.ones((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32), context_encode=True,
        block_tables=tables[:1]))(params, pool)
    assert "pallas_call" not in str(chunk)


def engine_tokens(params, mode, monkeypatch, prompts, **paged):
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    srv = serving(params, new_tokens=8, **paged)
    rids = [srv.submit(p) for p in prompts]
    out = srv.run_to_completion()
    snap = srv.metrics.snapshot()
    # every pdecode of an engine whose programs hold the kernel is counted, none elsewhere
    assert snap["state_kernel_steps"] == (snap["decode_steps"] if mode == "interpret" else 0)
    clean(srv)
    return [out[r] for r in rids]


@pytest.mark.parametrize("mode", MODES)
def test_a_chunked_prefills_carry_reaches_the_decode_steps(fam, params, mode, monkeypatch):
    """50 = 16 + 16 + 16 + 2 and 33 = 16 + 16 + 1: three and two carries before
    the first decode step reads the state."""
    prompts = prompts_of(np.random.default_rng(51), (50, 33))
    got = engine_tokens(params, mode, monkeypatch, prompts)
    assert got == [reference_tokens(fam, params, p, 8) for p in prompts]


@pytest.mark.parametrize("mode", MODES)
def test_a_reused_block_decodes_from_its_new_owners_state(fam, params, mode, monkeypatch):
    """Two lanes' worth of pool and four requests: the later two decode on
    blocks the first two's decode steps wrote."""
    prompts = prompts_of(np.random.default_rng(52), (30, 44, 40, 9))
    got = engine_tokens(params, mode, monkeypatch, prompts, num_blocks=4, decode_reserve_blocks=1)
    assert got == [reference_tokens(fam, params, p, 8) for p in prompts]


@pytest.mark.parametrize("mode", MODES)
def test_a_bucket_padded_last_chunk_hands_decode_the_unpadded_state(fam, params, mode, monkeypatch):
    """21 = 16 + 5 in a bucket of 8, 5 in a bucket of 8, 37 = 16 + 16 + 5: the
    padding rows of the last chunk must not be in the state decode reads."""
    prompts = prompts_of(np.random.default_rng(53), (21, 5, 37))
    got = engine_tokens(params, mode, monkeypatch, prompts)
    assert got == [reference_tokens(fam, params, p, 8) for p in prompts]
