"""The state-space step kernel (``kernels/ssm_step_pallas.py``), interpreted on
the CPU in float32: against ``selective_step`` lane by lane at the published
widths and a small shape, and what "a visit a live lane's slot, in place" has
to mean — a lane on the null slot (idle, or mid-prefill beside the batch)
moves nothing, every slot no live lane names bit for bit, the layer's offset
into the run of ``layers · state_slots`` states, the pool aliased and never
copied, 1, 55 and 128 live lanes of 128. Then which form a decode program
holds in each kernel mode. (The engine's tokens through the kernel are
``tests/test_jamba_serving.py``'s and ``tests/test_ssm_scan_kernel.py``'s; the
compiled call on a described v5e is ``tests/test_weight_placement.py``'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode, decode_model_for
from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.kernels.ssm_step_pallas import (
    ssm_state_step, ssm_step_paged, state_step_fits, visits,
)
from neuronx_distributed_llama3_2_tpu.models.jamba import selective_step
from tests.test_jamba_serving import TINY, params  # noqa: F401

# (lanes, N, D, layers, slots a layer): Jamba2-3B's state at a few lanes, and a small one at the cell's 128
SHAPES = {"published-5120x16": (6, 16, 5120, 2, 7), "small-128-lanes": (128, 8, 128, 2, 129)}


def make(name, pool_dtype=jnp.float32):
    b, n, d, layers, slots = SHAPES[name]
    keys = jax.random.split(jax.random.key(len(name)), 5)
    pool = jax.random.normal(keys[0], (layers, slots, n, d), jnp.float32).astype(pool_dtype)
    delta = jax.nn.softplus(jax.random.normal(keys[1], (b, d)) - 3.0)
    c = jax.random.normal(keys[2], (b, d)).astype(jnp.bfloat16)
    b_t, c_t = jax.random.normal(keys[3], (b, n)), jax.random.normal(keys[4], (b, n))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, d))
    return pool, (delta, c, b_t, c_t, a, jnp.ones((d,), jnp.float32))


def index_of(name, live, seed=0):
    """``live`` lanes scattered over the batch, each on a slot of its own (a
    permuted table); every other lane on the null slot."""
    b, slots = SHAPES[name][0], SHAPES[name][4]
    rng = np.random.default_rng(seed)
    index = np.zeros((b,), np.int32)
    index[rng.permutation(b)[:live]] = 1 + rng.permutation(slots - 1)[:live]
    return index


@jax.jit
def run(pool, step, index, layer):
    """As ``JambaDecode.forward`` calls it: the walk made once, the layer's offset added."""
    live = index != 0
    lane, count = visits(live)
    y, flat = ssm_step_paged(
        pool.reshape((-1,) + pool.shape[2:]), layer * pool.shape[1] + index[lane], lane, count, live, *step)
    return y, flat.reshape(pool.shape)


def close(got, want, tol=2e-6):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))) <= tol * scale


def check(name, index, layer, pool_dtype=jnp.float32, tol=2e-6):
    """y and h' of every live lane against ``selective_step`` on its slot, 0
    for the others' y, and every slot no live lane names as it was."""
    pool, step = make(name, pool_dtype)
    y, after = run(pool, step, jnp.asarray(index), layer)
    assert y.dtype == jnp.float32 and after.dtype == pool.dtype
    alive = index != 0
    want_y, want_h = selective_step(pool[layer, index], *step)
    if alive.any():
        close(y[alive], want_y[alive], tol)
        close(after[layer, index[alive]], want_h[alive], tol)
    assert bool((y[~alive] == 0).all())
    named = np.zeros(pool.shape[:2], bool)
    named[layer, index[alive]] = True
    assert bool((after[~named] == pool[~named]).all())            # the null slot too: in place, nothing else written
    assert not bool((after[named] == pool[named]).all()) or not alive.any()


@pytest.mark.parametrize("name", SHAPES)
def test_the_kernel_is_selective_step_lane_by_lane_through_a_permuted_table(name):
    """To float32 round-off (the sum over N runs in another order, the
    multiply-adds fuse otherwise); half the lanes live."""
    check(name, index_of(name, SHAPES[name][0] // 2), layer=1)


@pytest.mark.parametrize("live", [1, 55, 128])
def test_one_fifty_five_and_every_lane_of_128_live(live):
    check("small-128-lanes", index_of("small-128-lanes", live, seed=live), layer=0)


@pytest.mark.parametrize("lanes", ["none-live", "first-idle", "last-idle", "only-the-last"])
@pytest.mark.parametrize("name", SHAPES)
def test_lanes_on_the_null_slot_leave_every_slot_bit_for_bit(name, lanes):
    """An idle lane and a lane mid-prefill beside the batch both name the
    null slot: no state moves for them — not the null slot's, not the slot
    the lane's own table would name — wherever in the batch they sit."""
    b = SHAPES[name][0]
    index = np.arange(1, b + 1, dtype=np.int32) % SHAPES[name][4]
    index[{"none-live": slice(None), "first-idle": slice(0, 2), "last-idle": slice(b - 2, b),
           "only-the-last": slice(0, b - 1)}[lanes]] = 0
    check(name, index, layer=1)


@pytest.mark.parametrize("layer", range(2))
def test_a_layers_slots_are_found_at_index_plus_layer_times_slots(layer):
    """The pool goes in as one run of layers · state_slots states: the other
    layer's slot of the same index is not the one read, and is not written."""
    pool, step = make("published-5120x16")
    index = np.asarray([3, 0, 1, 6, 0, 2], np.int32)
    _, after = run(pool, step, jnp.asarray(index), layer)
    other = 1 - layer
    assert bool((after[other] == pool[other]).all())
    _, want_h = selective_step(pool[layer, index], *step)
    close(after[layer, 3], want_h[0])
    check("published-5120x16", index, layer)


def test_a_pool_in_bfloat16_is_read_and_written_in_its_own_dtype():
    """The check's variant that has to fail by ``cache_tolerance``: computed in
    float32, handed on rounded, ``y`` taken from the rounded state."""
    check("small-128-lanes", index_of("small-128-lanes", 9), layer=1, pool_dtype=jnp.bfloat16, tol=1e-2)


def test_the_pool_is_aliased_and_nothing_of_its_shape_is_made():
    """A donated pool is updated in place: the call aliases its last operand
    (after the three prefetched walks, Δ, Δ ⊙ c, B, C and A) to output
    1, and no other equation of the program yields an array of the pool's
    size."""
    pool, step = make("published-5120x16")
    flat = pool.reshape((-1,) + pool.shape[2:])
    index = jnp.asarray(index_of("published-5120x16", 3))
    live = index != 0
    lane, count = visits(live)
    jaxpr = jax.make_jaxpr(lambda f: ssm_step_paged(f, index[lane], lane, count, live, *step))(flat)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and tuple(calls[0].params["input_output_aliases"]) == ((8, 1),)
    assert calls[0].invars[8] is jaxpr.jaxpr.invars[0] and calls[0].outvars[1] is jaxpr.jaxpr.outvars[1]
    big = [e.primitive.name for e in jaxpr.jaxpr.eqns for v in e.outvars if v.aval.size >= flat.size]
    assert big == ["pallas_call"], big


def test_states_that_are_not_whole_lanes_or_do_not_fit_the_rows_are_refused():
    assert state_step_fits(5120) and state_step_fits(128) and not state_step_fits(64)
    i32 = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="do not fit rows"):
        ssm_state_step(i32, i32, i32[:1], jnp.zeros((4, 8, 128)), jnp.zeros((2, 256)), jnp.zeros((2, 256)),
                       jnp.zeros((2, 8)), jnp.zeros((2, 8)), jnp.zeros((8, 128)))
    with pytest.raises(ValueError, match="whole lanes"):
        ssm_state_step(i32, i32, i32[:1], jnp.zeros((4, 8, 64)), jnp.zeros((2, 64)), jnp.zeros((2, 64)),
                       jnp.zeros((2, 8)), jnp.zeros((2, 8)), jnp.zeros((8, 64)))


def test_the_walk_lists_the_live_lanes_first_in_lane_order():
    def walk(live):
        lane, count = visits(jnp.asarray(live, bool))
        return lane.tolist()[:int(count[0])], count.tolist()

    assert walk([0, 0, 1, 0, 1, 1, 0, 0]) == ([2, 4, 5], [3])
    assert walk([0, 0, 0, 0]) == ([], [0])
    assert walk([1, 1, 1]) == ([0, 1, 2], [3])


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_the_kernel_mode_decides_which_step_a_decode_program_holds(params, mode, monkeypatch):  # noqa: F811
    """``reference`` keeps ``selective_step`` over every slot (the CPU tier's
    twin), ``interpret`` holds one ``pallas_call`` in each run of state-space
    layers — the engine's batch and ``benchmarks/check.py``'s one lane over a
    large pool alike; a KV model never asks."""
    monkeypatch.setenv(KERNEL_MODE_ENV, mode)
    model = decode_model_for(TINY)
    kernel = mode == "interpret"
    assert model.uses_state_kernel() == kernel and not LlamaDecode(TINY).uses_state_kernel()
    state = next(kind for kind in model.cache_kinds if kind.state)
    assert model.decode_read(state) == ("kernel" if kernel else "pass")
    pool = model.init_paged_cache(8, 16, state_blocks=3)
    tables = jnp.asarray([[2, 3], [0, 0]], jnp.int32)
    step = str(jax.make_jaxpr(lambda p, c: model.decode_step(
        p, c, jnp.asarray([5, 0], jnp.int32), jnp.asarray([17, 0], jnp.int32), tables, kv_limit=32,
        state_tables=jnp.asarray([[1], [0]], jnp.int32)))(params, pool))
    assert step.count("pallas_call") == (2 if kernel else 0)           # tiny-jamba: M A M M A, two runs
    one_lane = str(jax.make_jaxpr(lambda p, c: model.decode_step(
        p, c, jnp.asarray([5], jnp.int32), jnp.asarray([17], jnp.int32), tables[:1], kv_limit=32))(params, pool))
    assert one_lane.count("pallas_call") == (2 if kernel else 0)
