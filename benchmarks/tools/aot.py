"""Compile a cell's programs at their real size for a described v5e, with no
chip attached (section 2 of the on-chip-measurement guide), and print what the
compiler says each needs:

    python3 benchmarks/tools/aot.py <workload> [pool_blocks]

A serving cell compiles the forwards its paged programs trace (``pctx``,
``psfx``, ``pdecode`` at the cell's ladders) on one described chip; a training
cell compiles its whole train step on the cell's mesh. A compile that passes
is not a chip run; it shows what the compiler refuses and how much memory each
program needs beside its arguments."""

import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["NXDT_KERNEL_MODE"] = "compiled"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from benchmarks import spec  # noqa: E402

GB = 1e9


def report(name, compiled):
    m = compiled.memory_analysis()
    print(
        f"{name}: arguments {m.argument_size_in_bytes / GB:.2f} GB, "
        f"outputs {m.output_size_in_bytes / GB:.2f} GB, aliased "
        f"{m.alias_size_in_bytes / GB:.2f} GB, temporaries "
        f"{m.temp_size_in_bytes / GB:.2f} GB, total "
        f"{(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB:.2f} GB",
        flush=True,
    )


def serving(cell, family, devices, pool_blocks):
    from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
    from neuronx_distributed_llama3_2_tpu.serving.catalog import BucketLadder, complete_ladder

    sizes = cell.traffic["engine"]
    one = SingleDeviceSharding(devices[0])
    cfg = family.model_config(cell.config, False, max_seq_len=sizes["max_seq_len"])
    model = decode_model_for(cfg)
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = on(jax.eval_shape(family.train_model(cfg).init, jax.random.key(0)))
    bs, lanes = sizes["block_size"], sizes["lanes"]
    cache = on(jax.eval_shape(lambda: model.init_paged_cache(pool_blocks, bs)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    print(f"weights {held / GB:.2f} GB, pool of {pool_blocks} blocks {pool / GB:.2f} GB")
    init = jax.jit(family.train_model(cfg).init).lower(
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)).compile()
    report("weights init", init)
    ladder = BucketLadder(
        decode_batch=lanes, max_seq_len=sizes["max_seq_len"],
        prefill_buckets=tuple(complete_ladder(sizes["prefill_buckets"], sizes["max_seq_len"])),
        kv_buckets=tuple(complete_ladder(sizes["kv_buckets"], sizes["max_seq_len"])),
    )
    width = -(-sizes["max_seq_len"] // bs) + -(-ladder.prefill_buckets[-1] // bs)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    head = model._model()._logits

    def last(hidden, length, params):
        return head(params, jnp.take_along_axis(hidden, (length - 1)[:, None, None], axis=1))[:, 0]

    for b in ladder.prefill_buckets:
        def pctx(params, cache, ids, length, table):
            hidden, cache = model.forward(
                params, cache, ids, jnp.zeros((1,), jnp.int32), None,
                context_encode=True, return_hidden=True, block_tables=table)
            return jnp.argmax(last(hidden, length, params), -1), cache
        report(f"pctx[{b}]", jax.jit(pctx, donate_argnums=1).lower(
            params, cache, i32(1, b), i32(1), i32(1, width)).compile())
    for b, kv in ladder.suffix_pairs():
        def psfx(params, cache, ids, start, length, table, kv=kv):
            hidden, cache = model.forward(
                params, cache, ids, start, None, return_hidden=True,
                block_tables=table, kv_limit=kv)
            return jnp.argmax(last(hidden, length, params), -1), cache
        report(f"psfx[{b},kv={kv}]", jax.jit(psfx, donate_argnums=1).lower(
            params, cache, i32(1, b), i32(1), i32(1), i32(1, width)).compile())
    for kv in ladder.kv_buckets:
        def pdecode(params, cache, tokens, positions, tables, kv=kv):
            logits, positions, cache = model.decode_step(
                params, cache, tokens, positions, tables, kv_limit=kv)
            return jnp.argmax(logits, -1), positions, cache
        report(f"pdecode[kv={kv}]", jax.jit(pdecode, donate_argnums=(1, 3)).lower(
            params, cache, i32(lanes), i32(lanes), i32(lanes, width)).compile())
    check_reference(cell, family, cfg, params, i32)


def check_reference(cell, family, cfg, params, i32):
    """The correctness check's float32 reference runs beside the weights, the
    pool and the dense cache: its temporaries have to fit in what they leave."""
    test = cell.traffic["check"]
    n = int(test["prompt_tokens"]) + int(test["decode_steps"])
    ref_cfg = family.reference_config(cfg)
    ref = getattr(family.reference, "forward_with_margin", family.reference.forward_logits)
    with jax.default_matmul_precision("highest"):
        report(f"check reference[{n}]", jax.jit(lambda p, i: ref(p, ref_cfg, i)).lower(
            params, i32(1, n)).compile())


def training(cell, family, devices):
    from benchmarks.training import build_trainer
    from neuronx_distributed_llama3_2_tpu.parallel import state as ps
    from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
        init_optimizer_state, optimizer_state_specs,
    )
    from neuronx_distributed_llama3_2_tpu.trainer.trainer import TrainState

    model, config, step, cfg = build_trainer(cell, family, devices[: cell.chips], False)
    mesh = ps.get_parallel_state().mesh

    def init_fn(key):
        params = model.init(key)
        return TrainState(params=params, opt=init_optimizer_state(params, config.optimizer))

    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    specs = TrainState(
        params=model.specs(),
        opt=optimizer_state_specs(model.specs(), abstract.params, config.optimizer),
    )
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        abstract, specs,
    )
    job = cell.traffic
    ids = jax.ShapeDtypeStruct(
        (job["global_batch"], job["seq_len"]), jnp.int32, sharding=NamedSharding(mesh, P()))
    print(f"mesh {dict(mesh.shape)}, remat {cfg.remat!r}, flash {cfg.use_flash_attention}, "
          f"loss chunk {cfg.loss_chunk_size}")
    compiled = step.lower(state, {"input_ids": ids, "labels": ids}).compile()
    report("train step (per device)", compiled)
    text = compiled.as_text()
    for word in ("all-reduce", "all-gather", "reduce-scatter", "collective-permute"):
        print(f"  {word}: {text.count(word + '(') + text.count(word + '-start(')} call sites")
    print(f"  Mosaic kernels: {text.count('tpu_custom_call')} call sites")
    # the reference loss the check runs beside the live state
    canonical = getattr(model, "from_pipeline", lambda p: p)
    ref_cfg = family.reference_config(cfg)
    small = jax.ShapeDtypeStruct(
        (job["check_sequences"], job["seq_len"]), jnp.int32, sharding=NamedSharding(mesh, P()))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, i: family.reference.loss(canonical(p), ref_cfg, i)).lower(
            state.params, small).compile()
    report("reference loss (per device)", ref)


def main():
    cell = spec.load_cell(sys.argv[1])
    family = spec.load_family(cell.config["family"])
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    print(f"compiling {cell.name} for {devices[0].device_kind} (described, not attached)")
    if cell.traffic["kind"] == "train_job":
        training(cell, family, devices)
    else:
        serving(cell, family, devices, int(sys.argv[2]) if len(sys.argv) > 2 else int(cell.traffic["engine"]["pool_blocks"]))


if __name__ == "__main__":
    main()
