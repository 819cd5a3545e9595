"""``metrics.pool_bytes_total`` is read off the pool's own leaves: what it is
for each decode model at the tiny presets (those the benchmark already ran
must not move), and that the dense engine holds no slot cache
until something reads it."""

import dataclasses

import jax
import pytest

from neuronx_distributed_llama3_2_tpu.inference import GenerationConfig, InferenceEngine
from neuronx_distributed_llama3_2_tpu.models import model_registry
from neuronx_distributed_llama3_2_tpu.serving import PagedConfig, PagedServingEngine
from neuronx_distributed_llama3_2_tpu.serving.accounting import EngineDims, analytic_profile
from neuronx_distributed_llama3_2_tpu.serving.block_allocator import kv_pool_bytes_per_rank

# preset -> (bytes of a pool of 12 blocks of 16 rows, the formula's dims or None)
POOLS = {
    # k and v: layers x blocks x rows x kv heads x head x float32
    "tiny": (2 * 4 * 12 * 16 * 4 * 8 * 4, dict(num_layers=4, num_kv_heads=4, head_dim=8, arrays=2)),
    "tiny-moe": (2 * 2 * 12 * 16 * 4 * 8 * 4, dict(num_layers=2, num_kv_heads=4, head_dim=8, arrays=2)),
    "tiny-neox": (2 * 4 * 12 * 16 * 8 * 8 * 4, dict(num_layers=4, num_kv_heads=8, head_dim=8, arrays=2)),
    "tiny-olmoe": (2 * 2 * 12 * 16 * 4 * 16 * 4, dict(num_layers=2, num_kv_heads=4, head_dim=16, arrays=2)),
    # one latent row of 40 values in a lane of 128
    "tiny-sarvam": (3 * 12 * 16 * 128 * 4, dict(num_layers=3, num_kv_heads=1, head_dim=128, arrays=1)),
    # twelve states: layers x kv heads x phi x (head + 1); the rows of a block are no dimension
    "tiny-brumby": (12 * 2 * 2 * 768 * 33 * 4, None),
}


@pytest.mark.parametrize("preset", sorted(POOLS))
def test_pool_bytes_are_the_pools_own(preset):
    entry = model_registry()[preset]
    cfg = dataclasses.replace(entry["config"], max_seq_len=64)
    params = jax.jit(entry["model_cls"](cfg).init)(jax.random.key(0))
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq_len=64)
    srv = PagedServingEngine(eng, GenerationConfig(max_new_tokens=2), PagedConfig(
        block_size=16, num_blocks=12, prefill_buckets=(16,), kv_buckets=(64,)))
    assert eng._cache is None                        # no dense slot cache beside the pool
    want, dims = POOLS[preset]
    leaves = jax.tree.leaves(srv.cache)
    total = sum(a.size * a.dtype.itemsize for a in leaves)
    assert srv.metrics.pool_bytes_total == srv.metrics.pool_bytes_per_rank == total
    assert total == want
    if dims is not None:         # rows by position: the shape arithmetic agrees
        assert total == kv_pool_bytes_per_rank(num_blocks=12, block_size=16, dtype_bytes=4, **dims)
    # the analytic cost table reads the same bytes: a program's arguments are the weights and this pool
    seen = EngineDims.from_engine(srv)
    assert seen.pool_bytes_local() == total and seen.block_bytes == total // 12
    profile = analytic_profile(("pdecode", 2, 64), seen)
    assert profile.argument_bytes == seen.param_bytes + total
    moved = profile.bytes_accessed - seen.param_bytes - 2 * cfg.vocab_size * 4
    if dims is not None:         # every lane's 64 rows once
        assert moved == 2 * 64 * total // (12 * 16)
    else:                        # every lane's state there and back, and no row
        assert moved == 2 * 2 * total // 12 and seen.kv_row_bytes() == 0


def test_a_quantized_pool_counts_its_scale_tiles():
    entry = model_registry()["tiny"]
    cfg = dataclasses.replace(entry["config"], max_seq_len=64)
    params = jax.jit(entry["model_cls"](cfg).init)(jax.random.key(0))
    srv = PagedServingEngine(
        InferenceEngine(cfg, params, max_batch=2, max_seq_len=64), GenerationConfig(max_new_tokens=2),
        PagedConfig(block_size=16, num_blocks=12, prefill_buckets=(16,), kv_buckets=(64,),
                    kv_cache_dtype="int8"))
    assert srv.metrics.pool_bytes_total == kv_pool_bytes_per_rank(
        num_layers=4, num_blocks=12, block_size=16, num_kv_heads=4, head_dim=8, dtype_bytes=1,
        scale_bytes=2, arrays=2)
