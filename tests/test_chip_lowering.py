"""Cross-lowering: every ``pallas_call`` in the package, traced in the
``"compiled"`` kernel mode on this CPU host and lowered for a TPU.

Lowering only — no execution, no Mosaic — so it is cheap and runs in
tier-1, and it is the earliest place a kernel that cannot reach the chip
fails: the Pallas TPU lowering refuses illegal block shapes, unsupported
primitives and bad index maps before any compiler is involved. The same
case matrix, compiled all the way through Mosaic for a v5e without a chip,
is ``python scripts/tpu_aot_compile.py``; numerics are the chip's
``scripts/tpu_kernel_gate.py``.

Geometry is Llama-3.2-1B's attention: 32 query heads over 8 kv heads of 64,
pool blocks of 16 rows, bench sequence 2048 — and, for the retention state
kernel, Brumby-14B's published widths as ``brumby-longgen-batch`` runs them.
"""

import itertools

import jax
import jax.numpy as jnp
import pytest

from neuronx_distributed_llama3_2_tpu.kernels.mode import KERNEL_MODE_ENV
from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (
    KV_SCALE_DTYPE,
    kv_cache_jax_dtype,
)

N, NKV, D, BS = 32, 8, 64, 16
LANES, KV_LIMIT, POOL_BLOCKS = 8, 2048, 256


def flash_case(block, segmented, backward):
    """(fn, avals) for the flash kernels at bench geometry: forward, or the
    dq + dkv backward pair through the custom VJP."""
    from neuronx_distributed_llama3_2_tpu.kernels.pallas_flash_attention import (
        pallas_flash_attention,
    )

    b, s = 2, 2048

    def loss(q, k, v, *seg):
        o = pallas_flash_attention(
            q, k, v, causal=True, segment_ids=seg[0] if seg else None,
            block_q=block, block_kv=block,
        )
        return jnp.sum(o.astype(jnp.float32) ** 2)

    avals = [
        jax.ShapeDtypeStruct((b, s, N, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, s, NKV, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, s, NKV, D), jnp.bfloat16),
    ]
    if segmented:
        avals.append(jax.ShapeDtypeStruct((b, s), jnp.int32))
    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else loss
    return fn, avals


def paged_case(kv_dtype, t, quant_mxu=False, row_live=False, tree=False,
               mesh=None):
    """(fn, avals) for the paged decode kernel; ``mesh`` wraps it in the tp
    ``shard_map`` (``paged_flash_decode_tp``)."""
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode,
        paged_flash_decode_tp,
    )

    quantized = kv_dtype != "bf16"
    pool = jax.ShapeDtypeStruct(
        (POOL_BLOCKS, BS, NKV, D), kv_cache_jax_dtype(kv_dtype)
    )
    scale = jax.ShapeDtypeStruct((POOL_BLOCKS, BS, NKV), KV_SCALE_DTYPE)
    qshape = (LANES, N, D) if t == 1 else (LANES, t, N, D)
    avals = [
        jax.ShapeDtypeStruct(qshape, jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((LANES, KV_LIMIT // BS + 8), jnp.int32),
        jax.ShapeDtypeStruct((LANES,), jnp.int32),
    ]
    avals += [scale, scale] if quantized else []
    avals += [jax.ShapeDtypeStruct((LANES,), jnp.int32)] if row_live else []
    avals += [jax.ShapeDtypeStruct((LANES, t), jnp.int32)] if tree else []

    def fn(q, kp, vp, tables, positions, *rest):
        rest = list(rest)
        kw = dict(kv_limit=KV_LIMIT)
        if quantized:
            kw.update(k_scale=rest.pop(0), v_scale=rest.pop(0),
                      quant_mxu=quant_mxu)
        if row_live:
            kw["row_live"] = rest.pop(0)
        if tree:
            kw["tree_bits"] = rest.pop(0)
        if mesh is not None:
            return paged_flash_decode_tp(
                q, kp, vp, tables, positions, mesh=mesh, **kw
            )
        return paged_flash_decode(q, kp, vp, tables, positions, **kw)

    return fn, avals


def ring_case(mesh, impl):
    """(fn, avals) for the Pallas ring executors (fwd + custom-VJP bwd) over
    ``mesh``'s cp axis, 2048 tokens."""
    from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import (
        ring_attention_sharded,
    )

    def loss(q, k, v):
        o = ring_attention_sharded(q, k, v, mesh, "cp", causal=True, impl=impl)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    avals = [
        jax.ShapeDtypeStruct((2, 2048, n, D), jnp.bfloat16)
        for n in (N, NKV, NKV)
    ]
    return jax.grad(loss, argnums=(0, 1, 2)), avals


QUANT = ("int8", "fp8_e4m3", "fp8_e5m2")

FLASH_CASES = {
    f"flash-{'bwd' if bwd else 'fwd'}-{blk}{'-seg' if seg else ''}":
        (blk, seg, bwd)
    for blk, seg, bwd in itertools.product(
        (1024, 512), (False, True), (False, True)
    )
}
# kwargs of paged_case, single chip
PAGED_CASES = {
    **{f"paged-{dt}-t{t}": dict(kv_dtype=dt, t=t)
       for dt in ("bf16",) + QUANT for t in (1, 4, 8)},
    **{f"paged-mxu-{dt}-t{t}": dict(kv_dtype=dt, t=t, quant_mxu=True)
       for dt in QUANT for t in (1, 4, 8)},
    **{f"paged-live-{dt}-t{t}": dict(kv_dtype=dt, t=t, row_live=True)
       for dt in ("bf16", "int8") for t in (4, 8)},
    **{f"paged-tree-{dt}-t{t}": dict(kv_dtype=dt, t=t, tree=True)
       for dt in ("bf16", "int8") for t in (4, 8)},
    "paged-tree-mxu-int8-t4": dict(
        kv_dtype="int8", t=4, tree=True, quant_mxu=True),
    "paged-tree-mxu-int8-t8": dict(
        kv_dtype="int8", t=8, tree=True, quant_mxu=True),
}
# the same kernel under the tp=4 shard_map wrapper (NKV/tp = 2 heads a rank)
TP_CASES = {
    "tp4-bf16-t1": dict(kv_dtype="bf16", t=1),
    "tp4-bf16-t4": dict(kv_dtype="bf16", t=4),
    "tp4-int8-t1": dict(kv_dtype="int8", t=1),
    "tp4-mxu-int8-t4": dict(kv_dtype="int8", t=4, quant_mxu=True),
    "tp4-tree-bf16-t8": dict(kv_dtype="bf16", t=8, tree=True),
    "tp4-live-bf16-t8": dict(kv_dtype="bf16", t=8, row_live=True),
}
# (initialize_model_parallel kwargs over four devices, ring impl)
RING_CASES = {
    "ring-pallas-cp4": (dict(context_parallel_size=4), "pallas"),
    "ring-zigzag-cp4": (dict(context_parallel_size=4), "zigzag"),
    "ring-zigzag-cp2-tp2": (
        dict(context_parallel_size=2, tensor_model_parallel_size=2), "zigzag"),
}


def retention_case(pool_dtype):
    """(fn, avals) for the one-pass state kernel at Brumby-14B's widths: 24
    lanes, 8 kv heads of 128 with 5 query heads each, φ 9,216, a pool of 6
    layers x 27 states."""
    from neuronx_distributed_llama3_2_tpu.kernels.retention_step_pallas import (
        retention_step_paged,
    )

    lanes, nkv, group, d, feat, layers, blocks = 24, 8, 5, 128, 9216, 6, 27
    avals = [
        jax.ShapeDtypeStruct((layers, blocks, nkv, feat, d), pool_dtype),
        jax.ShapeDtypeStruct((layers, blocks, nkv, feat), pool_dtype),
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((lanes, nkv, group, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((lanes, nkv, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((lanes, nkv, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((lanes, nkv), jnp.float32),
    ]

    def fn(s_pool, z_pool, index, layer, q, k, v, log_g):
        return retention_step_paged(s_pool, z_pool, index, layer, q, k, v, log_g, 1e-6)

    return fn, avals


# the pool's dtype: float32 as served; bfloat16 is the check's failing variant
RETENTION_CASES = {"retention-step-f32": jnp.float32, "retention-step-bf16": jnp.bfloat16}


def ssm_scan_case(pool_dtype, rows):
    """(fn, avals) for the chunk scan at Jamba2-3B's widths as
    ``jamba-smallchat-bursty`` runs them: one lane's bucket of ``rows`` rows
    over 5,120 channels of 16 states."""
    from neuronx_distributed_llama3_2_tpu.kernels.ssm_scan_pallas import ssm_chunk_scan

    n, d = 16, 5120
    f32 = jnp.float32
    shapes = [((1, n, d), pool_dtype), ((1, rows, d), f32), ((1, rows, d), f32),
              ((1, rows, n), f32), ((1, rows, n), f32), ((n, d), f32), ((1,), jnp.int32)]
    return ssm_chunk_scan, [jax.ShapeDtypeStruct(*s) for s in shapes]


SSM_SCAN_CASES = {
    "ssm-scan-f32-512": (jnp.float32, 512), "ssm-scan-f32-128": (jnp.float32, 128),
    "ssm-scan-bf16-512": (jnp.bfloat16, 512),
}


def ssm_step_case(pool_dtype, lanes):
    """(fn, avals) for the step kernel at Jamba2-3B's widths as
    ``jamba-smallchat-bursty`` runs them: ``lanes`` lanes over a pool of
    26 layers x 129 slots of 16 states x 5,120 channels, in place."""
    from neuronx_distributed_llama3_2_tpu.kernels.ssm_step_pallas import ssm_step_paged, visits

    n, d, layers, slots = 16, 5120, 26, 129
    f32 = jnp.float32
    shapes = [((layers * slots, n, d), pool_dtype), ((lanes,), jnp.int32), ((), jnp.int32),
              ((lanes, d), f32), ((lanes, d), jnp.bfloat16), ((lanes, n), f32), ((lanes, n), f32),
              ((n, d), f32), ((d,), f32)]

    def fn(h_flat, index, layer, *step):
        live = index != 0
        lane, count = visits(live)
        return ssm_step_paged(h_flat, layer * slots + index[lane], lane, count, live, *step)

    return fn, [jax.ShapeDtypeStruct(*s) for s in shapes]


# the engine's batch, ``benchmarks/check.py``'s one lane, and the check's failing pool
SSM_STEP_CASES = {
    "ssm-step-f32-128": (jnp.float32, 128), "ssm-step-f32-1": (jnp.float32, 1),
    "ssm-step-bf16-128": (jnp.bfloat16, 128),
}


# (lanes, query heads, kv heads, the kind's layers, its pool's blocks, the rung,
# the window) of the cells whose decode read is the walk: laguna-mixedlen-batch's
# full kind, mixtral-chat-steady, olmoe-rag-batch; and the two window kinds, whose
# table is the lane's ring (window - 1 + the 512-row chunk, in blocks) and whose
# pool is a null block + a ring a lane: smallthinker-longchat-steady's and
# laguna-mixedlen-batch's
WALK_SHAPES = {
    "laguna": (32, 48, 8, 2, 17920, 8704, None),
    "mixtral": (16, 32, 8, 3, 3072, 2304, None),
    "olmoe": (8, 16, 16, 8, 1152, 2176, None),
    "smallthinker-window": (32, 28, 4, 3, 1 + 32 * 288, 288 * 16, 4096),
    "laguna-window": (32, 64, 8, 3, 1 + 32 * 64, 64 * 16, 512),
}


def walk_case(pool_dtype, shape="laguna", group=None):
    """(fn, avals) for the decode block walk at a cell's shape (heads of 128,
    blocks of 16 rows), ``group`` blocks a loop trip (None: what the pool's
    shape, or the window's blocks, gives). A window kind says which lanes are
    null beside its table, as ``LagunaDecode._attend`` does."""
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_decode_walk,
    )

    lanes, n, nkv, layers, blocks, rung, window = WALK_SHAPES[shape]
    d, bs = 128, 16
    pool = jax.ShapeDtypeStruct((layers, blocks, bs, nkv, d), pool_dtype)
    avals = [
        jax.ShapeDtypeStruct((lanes, n, d), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((lanes, rung // bs), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    ]

    def fn(q, k_pool, v_pool, tables, positions, layer):
        return paged_decode_walk(
            q, k_pool, v_pool, tables, positions, layer, kv_limit=rung, group=group,
            window=window, null_lanes=None if window is None else positions < 0)

    return fn, avals


# the pool's dtype: bfloat16 as served; float32 is the CPU tests' pool
WALK_CASES = {
    "decode-walk-bf16": (jnp.bfloat16, "laguna"), "decode-walk-f32": (jnp.float32, "laguna"),
    "decode-walk-mixtral-bf16": (jnp.bfloat16, "mixtral"), "decode-walk-olmoe-bf16": (jnp.bfloat16, "olmoe"),
    "decode-walk-smallthinker-window-bf16": (jnp.bfloat16, "smallthinker-window"),
    "decode-walk-laguna-window-bf16": (jnp.bfloat16, "laguna-window"),
}


def latent_walk_case(pool_dtype, shape, group=None):
    """(fn, avals) for the latent decode walk at a latent cell's shape —
    ``xing-longdoc-batch``: 32 lanes of 32 heads over the 16,384-row rung of a
    20,480-block pool; ``sarvam-docqa-batch``: 64 lanes of 64 heads over the
    3,072-row rung of a 12,288-block one — 5 layers of blocks of 16 rows of 640,
    the query the row's 576 values wide."""
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        LATENT_WALK_GROUP,
        latent_decode_walk,
    )

    lanes, n, blocks, rung = LATENT_SHAPES[shape]
    layers, bs, w, r, dr = 5, 16, 640, 512, 64
    avals = [
        jax.ShapeDtypeStruct((lanes, n, r + dr), jnp.bfloat16),
        jax.ShapeDtypeStruct((layers, blocks, bs, w), pool_dtype),
        jax.ShapeDtypeStruct((lanes, rung // bs), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    ]

    def fn(q_abs, pool, tables, positions, layer):
        return latent_decode_walk(
            q_abs, pool, tables, positions, layer, rank=r, sm_scale=192 ** -0.5,
            kv_limit=rung, group=group or LATENT_WALK_GROUP)

    return fn, avals


# (lanes, heads, pool blocks, rung) of the two cells that run the latent walk
LATENT_SHAPES = {"longdoc": (32, 32, 20480, 16384), "docqa": (64, 64, 12288, 3072)}
# the pool's dtype: bfloat16 as served; float32 is the CPU tests' pool
LATENT_WALK_CASES = {
    f"latent-walk-{shape}-{name}": (dtype, shape)
    for shape in LATENT_SHAPES for name, dtype in (("bf16", jnp.bfloat16), ("f32", jnp.float32))
}


def sparse_chunk_case(rows, rung, dtype=jnp.bfloat16):
    """(fn, avals) for the sparse layers' chunk read at MiniCPM-SALA's widths
    as ``sala-longctx-steady`` runs them: one lane's bucket of ``rows`` rows,
    32 query over 2 kv heads of 128, over the ``rung``'s rows in blocks of 64."""
    from neuronx_distributed_llama3_2_tpu.kernels.sparse_chunk_pallas import sparse_chunk_attend

    n, nkv, d, bs = 32, 2, 128, 64
    kv = jax.ShapeDtypeStruct((1, nkv, rung, d), dtype)
    avals = [jax.ShapeDtypeStruct((1, rows, n, d), dtype), kv, kv,
             jax.ShapeDtypeStruct((1, rows, nkv, rung // bs), jnp.bool_),
             jax.ShapeDtypeStruct((1,), jnp.int32)]
    return lambda q, k, v, mask, start: sparse_chunk_attend(q, k, v, mask, start, bs), avals


# both prefill buckets, the ladder's first and last rungs, and pctx's own rows
SPARSE_CHUNK_CASES = {
    "sparse-chunk-512x33280": (512, 33280), "sparse-chunk-128x2048": (128, 2048),
    "sparse-chunk-512x512": (512, 512), "sparse-chunk-128x128": (128, 128),
}


def lower_for_tpu(fn, avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def assert_mosaic_call(lowered, kernel_name):
    text = lowered.as_text()
    assert "tpu_custom_call" in text, "no Mosaic custom call in the lowering"
    assert kernel_name in text, f"kernel {kernel_name!r} not in the lowering"


@pytest.fixture
def compiled_mode(monkeypatch):
    monkeypatch.setenv(KERNEL_MODE_ENV, "compiled")


@pytest.mark.parametrize("name", FLASH_CASES)
def test_flash_kernels_lower_for_tpu(compiled_mode, name):
    blk, seg, bwd = FLASH_CASES[name]
    lowered = lower_for_tpu(*flash_case(blk, seg, bwd))
    assert_mosaic_call(lowered, "flash_fwd")
    if bwd:
        assert_mosaic_call(lowered, "flash_bwd_dq")
        assert_mosaic_call(lowered, "flash_bwd_dkv")


@pytest.mark.parametrize("name", PAGED_CASES)
def test_paged_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*paged_case(**PAGED_CASES[name]))
    assert_mosaic_call(lowered, "paged_flash_decode")


@pytest.mark.parametrize("name", RETENTION_CASES)
def test_retention_step_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*retention_case(RETENTION_CASES[name]))
    assert_mosaic_call(lowered, "retention_state_pass")
    # the pool is the call's operand 2 and its result 1: updated in place
    assert "output_tuple_indices = [1], operand_index = 2" in lowered.as_text()


@pytest.mark.parametrize("name", SSM_SCAN_CASES)
def test_ssm_chunk_scan_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*ssm_scan_case(*SSM_SCAN_CASES[name]))
    assert_mosaic_call(lowered, "ssm_chunk_scan")
    # the state is the call's operand 1 and its result 1: updated in place
    assert "output_tuple_indices = [1], operand_index = 1" in lowered.as_text()


@pytest.mark.parametrize("name", SSM_STEP_CASES)
def test_ssm_state_step_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*ssm_step_case(*SSM_STEP_CASES[name]))
    assert_mosaic_call(lowered, "ssm_state_step")
    # the pool is the call's operand 8 and its result 1: updated in place
    assert "output_tuple_indices = [1], operand_index = 8" in lowered.as_text()


@pytest.mark.parametrize("name", WALK_CASES)
def test_decode_walk_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*walk_case(*WALK_CASES[name]))
    assert_mosaic_call(lowered, "paged_decode_walk")


@pytest.mark.parametrize("name", LATENT_WALK_CASES)
def test_latent_walk_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*latent_walk_case(*LATENT_WALK_CASES[name]))
    assert_mosaic_call(lowered, "latent_decode_walk")


@pytest.mark.parametrize("name", SPARSE_CHUNK_CASES)
def test_sparse_chunk_kernel_lowers_for_tpu(compiled_mode, name):
    lowered = lower_for_tpu(*sparse_chunk_case(*SPARSE_CHUNK_CASES[name]))
    assert_mosaic_call(lowered, "sparse_chunk_attend")


@pytest.mark.parametrize("name", TP_CASES)
def test_tp_paged_kernel_lowers_for_tpu(compiled_mode, name):
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        initialize_model_parallel,
    )

    st = initialize_model_parallel(
        tensor_model_parallel_size=4, devices=jax.devices()[:4]
    )
    lowered = lower_for_tpu(*paged_case(mesh=st.mesh, **TP_CASES[name]))
    assert_mosaic_call(lowered, "paged_flash_decode")


@pytest.mark.parametrize("name", RING_CASES)
def test_ring_kernels_lower_for_tpu(compiled_mode, name):
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        initialize_model_parallel,
    )

    mesh_kw, impl = RING_CASES[name]
    st = initialize_model_parallel(devices=jax.devices()[:4], **mesh_kw)
    lowered = lower_for_tpu(*ring_case(st.mesh, impl))
    assert_mosaic_call(lowered, "flash_fwd")


@pytest.mark.parametrize("mesh_kw", [
    dict(tensor_model_parallel_size=2),                       # tp2 x dp2
    dict(tensor_model_parallel_size=2, pipeline_model_parallel_size=2),
], ids=["tp2-dp2", "tp2-pp2"])
def test_flash_dispatch_lowers_on_a_mesh(compiled_mode, mesh_kw):
    """``flash_attention`` on a multi-device mesh: the partitioner refuses a
    bare Mosaic call ("cannot be automatically partitioned"), so the
    dispatcher must put the kernel in a manual region."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_llama3_2_tpu.kernels.flash_attention import (
        flash_attention,
    )
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        initialize_model_parallel,
    )

    st = initialize_model_parallel(devices=jax.devices()[:4], **mesh_kw)
    sharding = NamedSharding(st.mesh, P(("dp", "ep"), None, "tp", None))

    def loss(q, k, v):
        o = flash_attention(q, k, v, block_q=512, block_kv=512)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    avals = [
        jax.ShapeDtypeStruct((4, 1024, n, D), jnp.bfloat16, sharding=sharding)
        for n in (N, NKV, NKV)
    ]
    lowered = lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), avals)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert_mosaic_call(lowered, kernel)


def test_interpreted_kernels_never_reach_a_tpu_lowering(monkeypatch):
    """The other two modes keep the kernel off Mosaic — which is why no
    device-bound entry point may run in them unasked."""
    for mode in ("interpret", "reference"):
        monkeypatch.setenv(KERNEL_MODE_ENV, mode)
        lowered = lower_for_tpu(*paged_case("bf16", 1))
        assert "tpu_custom_call" not in lowered.as_text()
