"""``aot_kinds.py`` for any cell whose model lays a kind of cache out a lane
beside the allocator's pool (``CacheKind.rows`` a count: a ring of blocks, or
— ``state`` — one slot): the programs as the engine builds them, the lane
kind's pool sized by the engine's rule (1 null block + lanes x blocks a lane;
a state's is 1) under the kind's own names (``<name>_blocks`` at
``init_paged_cache``, ``<name>_tables`` at ``forward``), the prefill ladder
stopping at the chunk, ``row_live`` handed on where the model keeps a state —
compiled at real size for a described v5e, no chip attached:

    python3 benchmarks/tools/aot_lane_kinds.py <workload> [lanes] [--hlo-hash] [--layouts]

``--layouts`` prints how the compiler lays each cache array out as a
parameter of ``pdecode`` and the bytes that takes (tile padding included)."""

import hashlib
import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location("aot", os.path.join(HERE, "aot.py"))
aot = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(aot)       # sets the environment for a described v5e

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import spec  # noqa: E402


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    cell = spec.load_cell(args[0])
    family = spec.load_family(cell.config["family"])
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    print(f"compiling {cell.name} for {devices[0].device_kind} (described, not attached)")
    from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for

    sizes = cell.traffic["engine"]
    lanes = int(args[1]) if len(args) > 1 else sizes["lanes"]
    bs, top, chunk = sizes["block_size"], sizes["max_seq_len"], sizes["prefill_chunk_tokens"]
    one = SingleDeviceSharding(devices[0])
    cfg = family.model_config(cell.config, False, max_seq_len=top)
    model = decode_model_for(cfg)
    kind = next(kind for kind in model.cache_kinds if kind.rows is not None)
    buckets = [b for b in sizes["prefill_buckets"] if b <= chunk]
    a_lane = 1 if kind.state else -(-(kind.rows - 1 + buckets[-1]) // bs)
    lane_blocks = 1 + lanes * a_lane
    tables = f"{kind.name}_tables"
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = on(jax.eval_shape(family.train_model(cfg).init, jax.random.key(0)))
    cache = on(jax.eval_shape(lambda: model.init_paged_cache(
        sizes["pool_blocks"], bs, **{f"{kind.name}_blocks": lane_blocks})))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))  # noqa: E731
    print(f"weights {nbytes(params) / aot.GB:.2f} GB, allocator's pool {sizes['pool_blocks']} blocks; "
          f"cache by field: " + ", ".join(
              f"{name} {nbytes(part) / aot.GB:.3f} GB" for name, part in cache._asdict().items())
          + f"; {kind.name} kind {lane_blocks} blocks ({lanes} lanes x {a_lane})")
    width = -(-top // bs) + -(-buckets[-1] // bs)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    head = model._model()._logits
    live = (lambda length: length) if model.keeps_state else (lambda length: None)

    def last(hidden, length, params):
        return head(params, jnp.take_along_axis(hidden, (length - 1)[:, None, None], axis=1))[:, 0]

    def show(name, compiled):
        aot.report(name, compiled)
        if "--hlo-hash" in sys.argv:
            text = re.sub(r"metadata=\{[^}]*\}", "", compiled.as_text())
            print(f"  hlo {hashlib.sha256(text.encode()).hexdigest()[:16]}")

    for b in buckets:
        def pctx(params, cache, ids, length, table, lane):
            hidden, cache = model.forward(
                params, cache, ids, jnp.zeros((1,), jnp.int32), None, context_encode=True,
                return_hidden=True, block_tables=table, row_live=live(length), **{tables: lane})
            return jnp.argmax(last(hidden, length, params), -1), cache
        show(f"pctx[{b}]", jax.jit(pctx, donate_argnums=1).lower(
            params, cache, i32(1, b), i32(1), i32(1, width), i32(1, a_lane)).compile())
    for kv in sizes["kv_buckets"]:
        for b in buckets:
            def psfx(params, cache, ids, start, length, table, lane, kv=kv):
                hidden, cache = model.forward(
                    params, cache, ids, start, None, return_hidden=True, block_tables=table,
                    kv_limit=kv, row_live=live(length), **{tables: lane})
                return jnp.argmax(last(hidden, length, params), -1), cache
            show(f"psfx[{b},kv={kv}]", jax.jit(psfx, donate_argnums=1).lower(
                params, cache, i32(1, b), i32(1), i32(1), i32(1, width), i32(1, a_lane)).compile())

        def pdecode(params, cache, tokens, positions, table, lane, kv=kv):
            logits, positions, cache = model.decode_step(
                params, cache, tokens, positions, table, kv_limit=kv, **{tables: lane})
            return jnp.argmax(logits, -1), positions, cache
        compiled = jax.jit(pdecode, donate_argnums=(1, 3)).lower(
            params, cache, i32(lanes), i32(lanes), i32(lanes, width), i32(lanes, a_lane)).compile()
        show(f"pdecode[kv={kv}]", compiled)
        if "--layouts" in sys.argv:
            for leaf in jax.tree.leaves(cache):
                shape = ",".join(map(str, leaf.shape))
                found = re.search(rf"(bf16|f32)\[{shape}\]\{{[^}}]*\}}", compiled.as_text())
                alone = jax.jit(lambda a: a).lower(leaf).compile().memory_analysis()
                print(f"  {found.group(0) if found else shape}: {alone.argument_size_in_bytes} B laid out, "
                      f"{leaf.size * leaf.dtype.itemsize} B of values")
    aot.check_reference(cell, family, cfg, params, i32)


if __name__ == "__main__":
    main()
