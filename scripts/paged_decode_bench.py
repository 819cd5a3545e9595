"""Paged decode-attention bench: gather vs Pallas kernel, one BENCH JSON line.

Three measurements for the gather-free paged decode path (docs/serving.md):

1. **Decode-step latency** across ``--kv-limits`` buckets: the same tiny
   decode step (batch ``--batch``, one token per lane) run with
   ``use_paged_kernel`` off (dense block-table gather then attention) and
   on (``kernels/paged_attention_pallas`` reads the pool in place).  On a
   real chip the kernel column is the Mosaic kernel; on CPU it runs in
   interpret mode, so the timing columns are only meaningful on TPU — the
   *parity* gate (greedy argmax identical per bucket) holds everywhere.

2. **Decode-stall A/B** for chunked prefill: short prompts decode while a
   long prompt is admitted, once with ``prefill_chunk_tokens`` unset (the
   whole suffix prefills in one program call, stalling that step) and once
   chunked.  The record carries the max/mean per-step wall time of both
   runs plus the chunk count; the gate is greedy-output parity between the
   two runs (timing is reported, not gated — CPU jitter would flake).

3. **Serving-loop leg**: a mixed workload run to completion by the
   look-ahead step loop, reporting steps/sec, the decode steps dispatched
   ahead of the device and the host-schedule vs device-wait per-step split
   from ``ServingMetrics``.  Reported, not gated (its parity with the
   drained sequence is tests/test_async_serving.py's).

4. **tp=1 vs tp=N A/B** for multi-chip serving: the same workload on the
   single-chip engine and on a pure-tp mesh (kv-head-sharded pool,
   shard_map-wrapped kernel), reporting steps/sec for both plus a
   max-resident-lanes capacity sweep — lanes per ``kv_limit`` bucket at
   the tp=1 pool's per-chip HBM budget, which the NKV/tp head slice grows
   ~tp×.  Skipped (recorded, not failed) below ``--tp`` devices.

5. **Quantized-pool A/B** for ``PagedConfig.kv_cache_dtype``: steps/sec
   with the pool at bf16 vs int8 (+per-row fp16 scales) plus a
   max-resident-lanes capacity sweep at fixed per-chip bytes and
   llama-class geometry (head_dim 64).  Gates: the int8 kernel engine is
   token-identical to the int8 gather engine, and the sweep shows int8
   fitting ≥1.9× the bf16 lanes; steps/sec and the int8-vs-fp token
   agreement are reported, not gated.

6. **Sampled-traffic A/B** for ``PagedConfig.on_device_sampling``: the
   same temperature+top-k+top-p workload with the host draw (per-step key
   upload) vs the fused on-device draw, reporting steps/sec and
   ``h2d_uploads`` for both.  Gates: greedy outputs under the fused
   program are identical to the host greedy engine, the fused sampled
   run is seed-deterministic, and a decode-only steady-state window
   records zero host->device uploads (the GC003 twin for sampled
   traffic); the speedup column is meaningful only on a real chip.

7. **Tree-speculation A/B** for ``PagedConfig.spec_tree``: linear chain
   verify vs packed-tree verify at *equal* draft budget on repetitive
   small-alphabet traffic (the regime where the branching prompt-lookup
   drafter has alternates worth scoring).  Gates: tree outputs are
   token-identical to the linear-spec engine (both transitively match
   plain greedy via the spec A/B), and tree tokens/step strictly beats
   linear — the packed tree always contains the linear chain as its
   leftmost path, so at equal budget it can only meet or beat it; wall
   time is reported, not gated (the one-forward branch win needs a real
   chip).

8. **Fused mixed-mode A/B** for ``PagedConfig.fused_step``: the same
   chunked-prefill-against-decode workload with the fused step off (one
   psfx per chunk plus a decode per step) and on (one ``pmixed`` program
   per step), reporting steps/sec and ``dispatches_per_step`` for both.
   Gates: greedy-output parity, a nonzero pmixed dispatch count, and the
   fused leg's ``dispatches_per_step`` strictly below the unfused one;
   steps/sec is reported, not gated.

9. **The block walk alone** (``--walk``, and nothing else runs): one
   layer's decode read of a ``(k, v)`` pool at the shapes of the cells that
   walk it (``WALK_SHAPES``; heads of 128, blocks of 16 rows, contexts drawn
   as the cell holds them through a permuted table, an idle lane on the null
   block): ``laguna-mixedlen-batch`` — 32 lanes, 48 query over 8 kv heads,
   a log-uniform prompt of 512-8,192 and up to 256 rows of reply over the
   8,704-row rung; ``mixtral-chat-steady`` — 16 lanes of 32 over 8 on the
   2,304-row rung, 2 live and 16 live; ``olmoe-rag-batch`` — 8 lanes of 16
   over 16 on the 2,176-row rung; and the two window kinds, whose table is a
   ring a lane (never the null block: the null lanes are said beside it) and
   whose contexts pass it: ``smallthinker-longchat-steady`` — 32 lanes, 10
   live, 28 query over 4 kv heads, a window of 4,096 in a 288-block ring,
   prompts of 2,048-12,288 and up to 512 rows of reply;
   ``laguna-mixedlen-batch``'s window kind — 32 lanes of 64 over 8, a window
   of 512 in a 64-block ring —
   ``kernels.paged_attention_pallas.paged_decode_walk`` at each of
   ``--walk-groups`` blocks a loop trip (a window kind: its blocks over one
   to six trips, and the group it would take with no window) beside the
   gather of the whole rung, or ring, and the attention over it that it
   replaced (``LagunaDecode._attend``'s block-wise gather and
   ``masked_attention``; ``LlamaDecode._attend_paged``'s gather of rows and
   ``_cache_attention``). Prints ms a layer, GB/s of *live* bytes (the K and V
   blocks the live lanes' contexts, or windows, reach) and their share of the
   chip's bandwidth peak for each, and the group the pool's shape — or the
   window's blocks — derives (``walk_group``, ``window_walk_group``); the gate
   is the kernel's distance from the gather. ``PERF.md`` section 6 (PR 43,
   PR 56, PR 62) holds the sweeps this was written for.

10. **The latent walk alone** (``--latent``, and nothing else runs): one
   latent layer's absorbed decode read at the two latent cells' shapes —
   ``xing-longdoc-batch``: 32 lanes of 32 heads, each one of 16 shared
   14,336-row documents + its own question and reply up to the 16,384-row
   rung, a pool of 5 x 20,480 blocks; ``sarvam-docqa-batch``: 64 lanes of 64
   heads, one of 24 shared 2,560-row documents + question and reply up to the
   3,072-row rung, 5 x 12,288 blocks; bf16 rows of 640, blocks of 16 —
   ``kernels.paged_attention_pallas.latent_decode_walk`` at each of
   ``--walk-groups`` blocks a loop trip beside the block-wise gather of the
   whole rung and the absorbed scores over it
   (``SarvamDecode._latent_attention``'s read as it was, and the kernel's
   plain twin). Prints ms a layer and GB/s of *live* pool rows (1,280 B a
   row) for each; the gate is the kernel's distance from the gather.
   ``PERF.md`` section 6 (PR 45) holds the sweep this was written for.

Gates (record still prints on failure, like kv_block_bench.py):

- per-``kv_limit`` greedy argmax parity, kernel vs gather
- token-identical greedy outputs, chunked vs unchunked admission
- token-identical greedy outputs, tp=N mesh vs tp=1, with the paged
  kernel still eligible (no dense-gather fallback) under the mesh

Usage::

    python scripts/paged_decode_bench.py            # kv_limits 64,128,256
    python scripts/paged_decode_bench.py --smoke    # seconds-scale CPU check
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def build_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale workload (CI); overrides the "
                    "workload knobs below")
    ap.add_argument("--kv-limits", default="64,128,256",
                    help="comma-separated kv_limit buckets for the "
                    "decode-step timing sweep")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    # stall A/B workload
    ap.add_argument("--short-prompts", type=int, default=3)
    ap.add_argument("--short-tokens", type=int, default=12)
    ap.add_argument("--long-tokens", type=int, default=96)
    ap.add_argument("--prefill-chunk-tokens", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--spec-draft-tokens", type=int, default=4,
                    help="draft width for the speculative on/off A/B")
    ap.add_argument("--tp", type=int, default=2,
                    help="mesh size for the tp=1 vs tp=N serving A/B "
                    "(skipped with a record note when fewer devices exist)")
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default=os.environ.get("SERVING_TRACE_DIR"),
                    help="directory for graftscope artifacts (Chrome trace "
                    "JSON + prometheus text from the traced serving-loop leg); "
                    "defaults to $SERVING_TRACE_DIR; unset = no artifacts")
    ap.add_argument("--walk", action="store_true",
                    help="time the decode block walk against the gather + "
                    "scores it replaced, at the shapes of the cells that walk "
                    "(with --smoke: tiny ones), and nothing else")
    ap.add_argument("--walk-groups", default=None,
                    help="blocks a loop trip of the walk, comma-separated "
                    "(8,16,32,64; with --latent powers of two: 16,32,64,128,256)")
    ap.add_argument("--walk-cells", default="",
                    help="with --walk: only the shapes whose name holds this")
    ap.add_argument("--latent", action="store_true",
                    help="time the latent decode walk against the gather + "
                    "absorbed scores it replaces, at xing-longdoc-batch's and "
                    "sarvam-docqa-batch's shapes (with --smoke: a tiny one), "
                    "and nothing else; --walk-groups gives the group sizes")
    args = ap.parse_args(argv)
    if args.walk_groups is None:
        args.walk_groups = "16,32,64,128,256" if args.latent else "8,16,32,64"
    if args.smoke:
        args.kv_limits = "32"
        args.block_size = 8
        args.iters = 3
        args.warmup = 1
        args.short_tokens = 5
        args.long_tokens = 30
        args.prefill_chunk_tokens = 8
        args.max_new_tokens = 6
        args.max_seq_len = 64
    args.kv_limit_list = [int(x) for x in args.kv_limits.split(",") if x]
    return args


def _decode_case(config, params, kv_limit, args):
    """Time one decode step at ``kv_limit``, gather vs kernel; check parity."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    b, bs = args.batch, args.block_size
    nblk = -(-kv_limit // bs)
    num_blocks = b * nblk + 1  # +1 for the NULL block at slot 0
    rng = np.random.default_rng(args.seed)

    tables = np.zeros((b, nblk), np.int32)
    ids = iter(range(1, num_blocks))
    for i in range(b):
        for j in range(nblk):
            tables[i, j] = next(ids)
    tables = jnp.asarray(tables)
    positions = jnp.full((b,), kv_limit - 1, jnp.int32)
    hist = jnp.asarray(
        rng.integers(0, config.vocab_size, (b, kv_limit - 1)), jnp.int32
    )
    toks = jnp.asarray(rng.integers(0, config.vocab_size, (b, 1)), jnp.int32)

    out = {}
    for flag in (False, True):
        cfg = dataclasses.replace(config, use_paged_kernel=flag)
        model = LlamaDecode(cfg)
        cache = model.init_paged_cache(num_blocks, bs)
        # fill the first kv_limit-1 rows via the gather path (identical
        # cache contents for both flags), then time the single-token step
        base = LlamaDecode(config)
        _, cache = base.forward(
            params, cache, hist, jnp.zeros((b,), jnp.int32), None,
            block_tables=tables, kv_limit=kv_limit,
        )

        def step(params, cache, toks, positions, tables, model=model):
            logits, _ = model.forward(
                params, cache, toks, positions, None,
                block_tables=tables, kv_limit=kv_limit,
            )
            return logits

        step = jax.jit(step)
        for _ in range(args.warmup):
            logits = step(params, cache, toks, positions, tables)
        logits.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            logits = step(params, cache, toks, positions, tables)
        logits.block_until_ready()
        dt = (time.perf_counter() - t0) / args.iters
        out[flag] = {
            "ms": dt * 1e3,
            "argmax": np.asarray(jnp.argmax(logits[:, -1], axis=-1)),
            "logits": np.asarray(logits, np.float32),
        }

    parity = bool((out[True]["argmax"] == out[False]["argmax"]).all())
    max_err = float(np.abs(out[True]["logits"] - out[False]["logits"]).max())
    return {
        "kv_limit": kv_limit,
        "gather_ms": round(out[False]["ms"], 3),
        "kernel_ms": round(out[True]["ms"], 3),
        "argmax_parity": parity,
        "max_abs_logit_err": round(max_err, 6),
    }


def _stall_ab(config, params, args):
    """Per-step wall time around a long-prompt admission, chunked vs not."""
    import jax
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    shorts = [
        rng.integers(0, config.vocab_size, size=(args.short_tokens,)).tolist()
        for _ in range(args.short_prompts)
    ]
    long_prompt = rng.integers(
        0, config.vocab_size, size=(args.long_tokens,)
    ).tolist()
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [8, 16, 32, 64, 128]
    buckets = [x for x in buckets if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def run(chunk):
        eng = InferenceEngine(
            config, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        paged = PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
                prefill_chunk_tokens=chunk,
            ),
        )
        for p in shorts:
            paged.submit(p)
        # one step so the shorts are decoding before the long prompt lands
        step_s = []
        t0 = time.perf_counter()
        alive = paged.step()
        step_s.append(time.perf_counter() - t0)
        paged.submit(long_prompt)
        while alive:
            t0 = time.perf_counter()
            alive = paged.step()
            step_s.append(time.perf_counter() - t0)
        # alive is False, so this returns the finished map without stepping
        return paged.run_to_completion(), step_s, paged.metrics

    out_plain, steps_plain, _ = run(None)
    out_chunk, steps_chunk, m_chunk = run(args.prefill_chunk_tokens)
    return {
        "stall_unchunked_max_step_ms": round(max(steps_plain) * 1e3, 3),
        "stall_unchunked_mean_step_ms": round(
            sum(steps_plain) / len(steps_plain) * 1e3, 3),
        "stall_chunked_max_step_ms": round(max(steps_chunk) * 1e3, 3),
        "stall_chunked_mean_step_ms": round(
            sum(steps_chunk) / len(steps_chunk) * 1e3, 3),
        "prefill_chunks": m_chunk.prefill_chunks,
        "chunked_parity": out_plain == out_chunk,
    }


def _loop_leg(config, params, args):
    """The serving loop's steps/sec on a mixed decode workload
    (docs/serving.md "How the engine steps"), with how many decode steps
    were dispatched ahead of the device and the host-schedule vs
    device-wait split from ``ServingMetrics``. Reported, not gated: the
    look-ahead's parity with the drained sequence is the tests'
    (tests/test_async_serving.py), and its worth is only visible on a real
    chip, where dispatching ahead overlaps host scheduling with device
    compute. The leg runs traced, so ``--trace-dir`` gets its artifacts."""
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, config.vocab_size, size=(args.short_tokens,)).tolist()
        for _ in range(args.max_batch)
    ]
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [x for x in (8, 16, 32, 64, 128) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)
    eng = InferenceEngine(
        config, params,
        max_batch=args.max_batch, max_seq_len=args.max_seq_len,
        buckets=buckets,
    )
    paged = PagedServingEngine(
        eng, gen,
        PagedConfig(
            block_size=args.block_size, num_blocks=num_blocks,
            trace_enabled=True, prewarm=True,
        ),
    )
    for p in prompts:
        paged.submit(p)
    t0 = time.perf_counter()
    paged.run_to_completion()
    wall = time.perf_counter() - t0
    snap = paged.metrics.snapshot()
    rec = {
        "loop_steps_per_s": round(paged.metrics.decode_steps / wall, 2),
        "lookahead_steps": snap["decode_steps_async"],
        "lame_duck_tokens": snap["lame_duck_tokens"],
        "mfu_est": snap["mfu_est"],
        "pad_waste_frac": snap["pad_waste_frac"],
        "hbm_headroom_bytes": snap["hbm_headroom_bytes"],
        "host_schedule_ms_per_step": snap["host_schedule_ms_per_step"],
        "device_wait_ms_per_step": snap["device_wait_ms_per_step"],
    }
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        rec["trace_artifact"] = paged.export_trace(
            os.path.join(args.trace_dir, "paged_decode_async_trace.json")
        )
        prom_path = os.path.join(args.trace_dir, "paged_decode_metrics.prom")
        with open(prom_path, "w") as f:
            f.write(paged.metrics.prometheus(paged.allocator, paged.index))
        rec["prometheus_artifact"] = prom_path
    return rec


def _spec_ab(config, params, args):
    """Speculative decoding on/off A/B on a repetitive workload
    (docs/serving.md "Speculative decoding"). Prompts are short repeated
    n-gram patterns — the regime prompt-lookup drafting is built for — so
    the n-gram drafter should push tokens/step well above 1.0 while the
    accept rule keeps the greedy outputs token-identical. Both the parity
    and the tokens/step > 1.0 claim are gated; wall time is reported, not
    gated (on CPU the multi-token verify forward is not cheaper than t
    single-token steps — the win needs a real chip, where a t<=8 query
    block rides the same kernel grid as t=1)."""
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    n_tok = max(args.short_tokens, 6)
    prompts = []
    for _ in range(args.max_batch):
        pat = rng.integers(1, config.vocab_size, size=3).tolist()
        prompts.append((pat * (n_tok // 3 + 1))[:n_tok])
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [x for x in (8, 16, 32, 64, 128) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def run(spec_k):
        eng = InferenceEngine(
            config, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        paged = PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
                spec_draft_tokens=spec_k,
            ),
        )
        for p in prompts:
            paged.submit(p)
        t0 = time.perf_counter()
        out = paged.run_to_completion()
        wall = time.perf_counter() - t0
        m = paged.metrics
        # per-lane decode-only tokens/step (each lane's first token comes
        # from prefill, not a decode step): plain greedy pins this at 1.0,
        # speculation must beat it. Lanes are homogeneous here, so dividing
        # by the lane count is exact.
        toks = sum(len(t) for t in out.values()) - len(prompts)
        tps = toks / (max(m.decode_steps, 1) * len(prompts))
        return out, tps, wall, m

    out_plain, tps_plain, wall_plain, _ = run(0)
    out_spec, tps_spec, wall_spec, m = run(args.spec_draft_tokens)
    return {
        "spec_draft_tokens": args.spec_draft_tokens,
        "spec_parity": out_plain == out_spec,
        "plain_tokens_per_step": round(tps_plain, 3),
        "spec_tokens_per_step": round(tps_spec, 3),
        "spec_accept_rate": round(m.accept_rate(), 4),
        "spec_verify_steps": m.verify_steps,
        "spec_disabled_lanes": m.spec_disabled_lanes,
        "plain_wall_s": round(wall_plain, 3),
        "spec_wall_s": round(wall_spec, 3),
    }


def _tree_ab(config, params, args):
    """Tree vs linear speculation A/B at equal draft budget
    (docs/serving.md "Tree speculation").  The workload is pinned rather
    than driven by the smoke knobs: small-alphabet period-3 prompts (the
    repeated-token runs create the ambiguous tails where the trie
    drafter's alternates pay off — large-alphabet patterns draft
    perfectly linearly and the tree can only tie) and enough new tokens
    that the run tails recur.  Both engines see identical prompts and
    k = ``--spec-draft-tokens`` draft slots; the tree leg just spends
    them as a packed trie instead of one chain.  tokens/step here is
    emitted-per-decode-step, deterministic and backend-independent, so
    the >1.0x gate holds on CPU smoke and chip alike."""
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    lengths = (12, 22, 9, 17)[: args.max_batch]
    prompts = []
    for n in lengths:
        pat = rng.integers(1, 9, size=3).tolist()
        prompts.append((pat * (n // 3 + 1))[:n])
    max_new = min(24, args.max_seq_len - max(lengths) - 1)
    gen = GenerationConfig(max_new_tokens=max_new)
    buckets = [x for x in (8, 16, 32) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def run(spec_tree):
        eng = InferenceEngine(
            config, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        paged = PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
                spec_draft_tokens=args.spec_draft_tokens,
                spec_tree=spec_tree,
            ),
        )
        for p in prompts:
            paged.submit(p)
        t0 = time.perf_counter()
        out = paged.run_to_completion()
        wall = time.perf_counter() - t0
        m = paged.metrics
        toks = sum(len(t) for t in out.values()) - len(prompts)
        tps = toks / (max(m.decode_steps, 1) * len(prompts))
        return out, tps, wall, m

    out_lin, tps_lin, wall_lin, _ = run(False)
    out_tree, tps_tree, wall_tree, m = run(True)
    shapes = {
        s: round(v["accepted"] / max(v["lanes"], 1), 3)
        for s, v in sorted(m.tree_accept_by_shape.items())
    }
    return {
        "tree_parity": out_lin == out_tree,
        "tree_tokens_per_step": round(tps_tree, 3),
        "tree_linear_tokens_per_step": round(tps_lin, 3),
        "tree_vs_linear": round(tps_tree / max(tps_lin, 1e-9), 3),
        "tree_verify_steps": m.tree_verify_steps,
        "tree_draft_nodes": m.tree_draft_tokens,
        "tree_mean_accept_by_shape": shapes,
        "tree_wall_s": round(wall_tree, 3),
        "tree_linear_wall_s": round(wall_lin, 3),
    }


def _tp_ab(config, params, args):
    """tp=1 vs tp=N serving-loop A/B plus the max-resident-lanes capacity
    sweep (docs/serving.md "Multi-chip serving").

    The same decode workload runs to completion on the single-chip engine
    and on a pure-tp mesh (kv-head-sharded pool + shard_map-wrapped kernel,
    replicated tables). Gates: greedy-output parity and kernel eligibility
    at tp=N (the sharded path must not have fallen back to the gather).
    Steps/sec is reported, not gated — on CPU the per-rank head slice buys
    nothing; on a real chip the win is HBM *capacity*, which the sweep
    states exactly: max resident lanes per kv_limit bucket at the tp=1
    pool's per-chip byte budget, where per-lane per-rank bytes shrink by
    tp. Skips (with a record note) when the host has < tp devices or the
    model's heads don't divide tp."""
    import jax
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        destroy_model_parallel,
        initialize_model_parallel,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving.block_allocator import (
        kv_pool_bytes_per_rank,
    )

    tp = args.tp
    if tp < 2:
        return {"tp_ab_skipped": "tp < 2"}
    if len(jax.devices()) < tp:
        return {
            "tp_ab_skipped":
            f"needs {tp} devices, have {len(jax.devices())}"
        }
    if config.num_kv_heads % tp or config.num_heads % tp:
        return {
            "tp_ab_skipped":
            f"heads n={config.num_heads}/nkv={config.num_kv_heads} "
            f"do not divide tp={tp}"
        }

    cfg = dataclasses.replace(config, use_paged_kernel=True)
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, config.vocab_size, size=(args.short_tokens,)).tolist()
        for _ in range(args.max_batch)
    ]
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [x for x in (8, 16, 32, 64, 128) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def run():
        eng = InferenceEngine(
            cfg, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        paged = PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
            ),
        )
        eligible = paged.model._paged_kernel_eligible(1, None)
        for p in prompts:
            paged.submit(p)
        t0 = time.perf_counter()
        out = paged.run_to_completion()
        wall = time.perf_counter() - t0
        snap = paged.metrics.snapshot()
        return out, paged.metrics.decode_steps / wall, eligible, snap

    out_1, sps_1, _, snap_1 = run()
    initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=jax.devices()[:tp]
    )
    try:
        out_n, sps_n, eligible_n, snap_n = run()
    finally:
        destroy_model_parallel()

    # capacity sweep: at the tp=1 pool's per-chip byte budget, how many
    # lanes fit per kv_limit bucket when the per-lane per-rank bytes shrink
    # to NKV/tp heads (pure pool arithmetic — the steps/sec columns above
    # are the latency side, this is the HBM side of the multi-chip win)
    itemsize = np.dtype(cfg.dtype).itemsize  # ml_dtypes registers bf16
    shared = dict(
        num_layers=cfg.num_layers, block_size=args.block_size,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        dtype_bytes=itemsize,
    )
    budget = kv_pool_bytes_per_rank(**shared, num_blocks=num_blocks)
    capacity = []
    for limit in args.kv_limit_list:
        nblk = -(-limit // args.block_size)
        lanes_1 = budget // kv_pool_bytes_per_rank(**shared, num_blocks=nblk)
        lanes_n = budget // kv_pool_bytes_per_rank(
            **shared, num_blocks=nblk, tp_size=tp
        )
        capacity.append({
            "kv_limit": limit,
            "max_lanes_tp1": int(lanes_1),
            "max_lanes_tpN": int(lanes_n),
        })
    return {
        "tp": tp,
        "tp1_steps_per_s": round(sps_1, 2),
        "tpN_steps_per_s": round(sps_n, 2),
        "tp_parity": out_1 == out_n,
        "tp_kernel_eligible": bool(eligible_n),
        "tp_pool_bytes_per_rank": snap_n["pool_bytes_per_rank"],
        "tp1_pool_bytes_per_rank": snap_1["pool_bytes_per_rank"],
        "tp_capacity_cases": capacity,
    }


def _quant_ab(config, params, args):
    """Quantized KV pool on/off A/B (docs/serving.md "Quantized KV pool").

    Steps/sec for the same decode workload with ``kv_cache_dtype`` bf16 vs
    int8, both on the paged kernel. Two gates:

    - **parity**: the int8 kernel engine must be token-identical to the
      int8 *gather* engine — the documented cross-path exactness of the
      append-local scales (int8 vs bf16 only carries a tolerance band, so
      the quantized gather is the right reference, not the fp run).
    - **capacity**: at a fixed per-chip byte budget and llama-class
      geometry (head_dim 64), the max-resident-lanes sweep must show int8
      (+fp16 scales) fitting >= 1.9x the bf16 lanes per kv_limit bucket —
      the HBM side of the quantization win; steps/sec is reported, not
      gated (on CPU the int8 round-trip adds work; the bandwidth win needs
      a real chip).
    """
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        kv_head_shard_size,
    )
    from neuronx_distributed_llama3_2_tpu.quantization import (
        kv_scale_itemsize,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving.block_allocator import (
        kv_pool_bytes_per_rank,
    )

    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, config.vocab_size, size=(args.short_tokens,)).tolist()
        for _ in range(args.max_batch)
    ]
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [x for x in (8, 16, 32, 64, 128) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def run(kv_dtype, kernel=True):
        cfg = dataclasses.replace(config, use_paged_kernel=kernel)
        eng = InferenceEngine(
            cfg, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        paged = PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
                kv_cache_dtype=kv_dtype,
            ),
        )
        for p in prompts:
            paged.submit(p)
        t0 = time.perf_counter()
        out = paged.run_to_completion()
        wall = time.perf_counter() - t0
        return out, paged.metrics.decode_steps / wall, paged.metrics.snapshot()

    out_fp, sps_fp, snap_fp = run("bf16")
    out_q, sps_q, snap_q = run("int8")
    out_qg, _, _ = run("int8", kernel=False)

    # capacity sweep at llama-class geometry (head_dim 64 — the regime the
    # >= 1.9x acceptance targets; tiny's head_dim 8 would understate the
    # ratio since the 2-byte scale amortizes over the row). Pure pool
    # arithmetic at a fixed per-chip byte budget; per-rank kv heads go
    # through the kv_head_shard_size layout reader so a surrounding mesh
    # (none in this bench) would be reflected.
    geom = dict(
        num_layers=32, block_size=args.block_size,
        num_kv_heads=kv_head_shard_size(8), head_dim=64,
    )
    budget = kv_pool_bytes_per_rank(
        **geom, num_blocks=1024, dtype_bytes=2
    )
    capacity = []
    for limit in args.kv_limit_list:
        nblk = -(-limit // args.block_size)
        lanes_fp = budget // kv_pool_bytes_per_rank(
            **geom, num_blocks=nblk, dtype_bytes=2
        )
        lanes_q = budget // kv_pool_bytes_per_rank(
            **geom, num_blocks=nblk, dtype_bytes=1,
            scale_bytes=kv_scale_itemsize("int8"),
        )
        capacity.append({
            "kv_limit": limit,
            "max_lanes_bf16": int(lanes_fp),
            "max_lanes_int8": int(lanes_q),
            "lanes_ratio": round(lanes_q / max(lanes_fp, 1), 3),
        })
    return {
        "quant_bf16_steps_per_s": round(sps_fp, 2),
        "quant_int8_steps_per_s": round(sps_q, 2),
        "quant_parity": out_q == out_qg,
        "quant_token_agreement_vs_fp": round(
            sum(
                sum(a == b for a, b in zip(out_fp[r], out_q[r]))
                / max(len(out_fp[r]), 1)
                for r in out_fp
            ) / max(len(out_fp), 1), 3),
        "quant_pool_bytes_per_rank": snap_q["pool_bytes_per_rank"],
        "fp_pool_bytes_per_rank": snap_fp["pool_bytes_per_rank"],
        "quant_capacity_cases": capacity,
    }


def _sampling_ab(config, params, args):
    """Sampled-traffic A/B (docs/serving.md "On-device sampling").

    The same sampled workload (temperature + top-k + top-p) run with
    ``PagedConfig.on_device_sampling`` off (host draw: per-step PRNG-key
    upload + logits download) and on (the draw fuses into the decode
    program against the lane-resident params/key data). Reported:
    steps/sec for both legs plus their ``h2d_uploads`` totals. Gates:

    - **greedy identity**: a *greedy* run under the fused engine must be
      token-identical to the plain greedy engine (the sentinel-params
      argmax contract);
    - **zero-upload steady state**: once every lane is decoding, the
      fused sampled leg must record ZERO further host->device uploads
      across a decode-only window (the GC003 twin for sampled traffic);
    - **determinism**: the fused sampled run repeated with the same seed
      must reproduce the identical token streams.
    """
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.inference.sampling import (
        SamplingConfig,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, config.vocab_size, size=(args.short_tokens,)).tolist()
        for _ in range(args.max_batch)
    ]
    sampled = GenerationConfig(
        max_new_tokens=args.max_new_tokens,
        sampling=SamplingConfig(
            greedy=False, temperature=0.8, top_k=40, top_p=0.9
        ),
    )
    greedy = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [x for x in (8, 16, 32, 64, 128) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def engine(gen, fused):
        eng = InferenceEngine(
            config, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        return PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
                on_device_sampling=fused,
            ),
        )

    def run(gen, fused):
        paged = engine(gen, fused)
        for p in prompts:
            paged.submit(p)
        t0 = time.perf_counter()
        out = paged.run_to_completion()
        wall = time.perf_counter() - t0
        return out, paged.metrics.decode_steps / wall, paged.metrics

    out_host, sps_host, m_host = run(sampled, fused=False)
    out_dev, sps_dev, m_dev = run(sampled, fused=True)
    out_dev2, _, _ = run(sampled, fused=True)

    # greedy identity under the fused program (sentinel params -> argmax)
    out_g, _, _ = run(greedy, fused=False)
    out_gf, _, _ = run(greedy, fused=True)

    # zero-upload steady state: admit, drain prefills, then count uploads
    # across a decode-only window
    steady = engine(sampled, fused=True)
    for p in prompts:
        steady.submit(p)
    for _ in range(len(prompts) + 2):
        steady.step()
    before = steady.metrics.h2d_uploads
    for _ in range(3):
        steady.step()
    steady_uploads = steady.metrics.h2d_uploads - before

    return {
        "sampling_host_steps_per_s": round(sps_host, 2),
        "sampling_fused_steps_per_s": round(sps_dev, 2),
        "sampling_host_h2d_uploads": int(m_host.h2d_uploads),
        "sampling_fused_h2d_uploads": int(m_dev.h2d_uploads),
        "sampling_host_fallback_steps": int(m_host.host_sample_fallbacks),
        "sampling_fused_sampled_steps": int(m_dev.sampled_steps),
        "sampling_fused_greedy_parity": out_g == out_gf,
        "sampling_fused_deterministic": out_dev == out_dev2,
        "sampling_steady_decode_uploads": int(steady_uploads),
    }


def _fused_ab(config, params, args):
    """Fused mixed-mode step on/off A/B (docs/serving.md "Fused
    mixed-mode step").

    The same mixed workload — short prompts decoding while a long prompt
    chunk-prefills through the middle of the run — with
    ``PagedConfig.fused_step`` off (one psfx per chunk plus a decode per
    step) and on (one pmixed program per step). Gates: greedy-output
    parity and a strictly lower ``dispatches_per_step`` on the fused leg
    with a nonzero pmixed count; steps/sec is reported, not gated (on
    CPU the packed grid is not cheaper — the win is host dispatch
    latency and pad waste on a real chip)."""
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    shorts = [
        rng.integers(0, config.vocab_size, size=(args.short_tokens,)).tolist()
        for _ in range(args.short_prompts)
    ]
    long_prompt = rng.integers(
        0, config.vocab_size, size=(args.long_tokens,)
    ).tolist()
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)
    buckets = [x for x in (8, 16, 32, 64, 128) if x <= args.max_seq_len]
    num_blocks = 4 * (args.max_seq_len // args.block_size)

    def run(fused):
        eng = InferenceEngine(
            config, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            buckets=buckets,
        )
        paged = PagedServingEngine(
            eng, gen,
            PagedConfig(
                block_size=args.block_size, num_blocks=num_blocks,
                prewarm=True,
                prefill_chunk_tokens=args.prefill_chunk_tokens,
                fused_step=fused,
            ),
        )
        for p in shorts:
            paged.submit(p)
        t0 = time.perf_counter()
        alive = paged.step()
        paged.submit(long_prompt)  # chunk-prefills against live decode
        while alive:
            alive = paged.step()
        wall = time.perf_counter() - t0
        m = paged.metrics
        return (
            paged.run_to_completion(),
            m.engine_steps / wall,
            round(m.compute_dispatches / max(m.engine_steps, 1), 4),
            m,
        )

    out_plain, sps_plain, dps_plain, _ = run(False)
    out_fused, sps_fused, dps_fused, m = run(True)
    return {
        "fused_steps_per_s": round(sps_fused, 2),
        "unfused_steps_per_s": round(sps_plain, 2),
        "fused_parity": out_plain == out_fused,
        "fused_dispatches_per_step": dps_fused,
        "unfused_dispatches_per_step": dps_plain,
        "fused_mixed_dispatches": int(m.mixed_dispatches),
    }


def _time_a_layer(read, operands, layers, live_bytes, args):
    """(the summed output, {ms a layer, GB/s of live bytes}) of ``read(*operands,
    layer)`` over ``layers`` layers in one jitted program, ``args.iters`` calls
    after the compile and ``args.warmup`` more."""
    import jax
    import jax.numpy as jnp

    def every_layer(*operands):
        return sum(
            read(*operands, jnp.int32(layer)).astype(jnp.float32)
            for layer in range(layers))

    fn = jax.jit(every_layer)
    out = fn(*operands).block_until_ready()
    for _ in range(args.warmup):
        fn(*operands).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = fn(*operands)
    out.block_until_ready()
    ms = (time.perf_counter() - t0) * 1e3 / args.iters / layers
    return out, {"ms_a_layer": round(ms, 4),
                 "live_gb_s": round(live_bytes / ms / 1e6, 1)}


# a (k, v) pool's decode read at the shapes of the cells that walk it: (lanes,
# of them live, query heads, kv heads, the layers timed in one program, pool
# blocks a layer, rung, lowest and highest prompt, most reply rows, which
# gather the walk replaced). ``laguna``: its block-wise gather and
# ``masked_attention``; ``llama``: ``LlamaDecode._attend_paged``'s gather of
# rows and ``_cache_attention``. The pools are the cells' own a layer; the
# layers are as many as make one program's time a device time, not a dispatch.
# A last entry is a window: the "rung" is then the lane's ring (its table),
# the pool a null block and a ring a lane, and a context is not cut to it
WALK_SHAPES = {
    "laguna-mixedlen-batch": (32, 32, 48, 8, 2, 17920, 8704, 512, 8192, 256, "laguna", None),
    "mixtral-chat-steady-2-live": (16, 2, 32, 8, 12, 3072, 2304, 64, 2048, 128, "llama", None),
    "mixtral-chat-steady-16-live": (16, 16, 32, 8, 12, 3072, 2304, 64, 2048, 128, "llama", None),
    "olmoe-rag-batch": (8, 8, 16, 16, 16, 1152, 2176, 512, 2048, 32, "llama", None),
    "smallthinker-longchat-steady-window": (
        32, 10, 28, 4, 3, 1 + 32 * 288, 288 * 16, 2048, 12288, 512, "laguna", 4096),
    "laguna-mixedlen-batch-window": (32, 32, 64, 8, 3, 1 + 32 * 64, 64 * 16, 512, 8192, 256, "laguna", 512),
}


def window_sweep_groups(window: int, bs: int, nkv: int) -> list:
    """The groups a window kind's walk is timed (and compiled,
    ``scripts/tpu_aot_compile.py``) at: its blocks over one to six even trips,
    and the group it would take with no window."""
    reach = (window - 1) // bs + 2
    return sorted({-(-reach // trips) for trips in range(1, 7)} | {max(1, 4096 // (bs * nkv))})


def _walk_sweep(args) -> dict:
    """Measurement 9 of the module's list."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.flops import chip_peaks
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_decode_walk,
        walk_group,
        window_walk_group,
    )
    from neuronx_distributed_llama3_2_tpu.models.laguna import (
        masked_attention,
        visible,
    )
    from neuronx_distributed_llama3_2_tpu.models.llama import LlamaConfig

    if args.smoke:
        bs, d, dtype = 4, 128, jnp.float32
        shapes = {"smoke-laguna": (4, 4, 4, 2, 2, 40, 32, 6, 24, 8, "laguna", None),
                  "smoke-llama": (4, 2, 4, 4, 2, 40, 32, 6, 24, 8, "llama", None),
                  "smoke-window": (4, 3, 4, 2, 2, 1 + 4 * 8, 32, 6, 90, 8, "laguna", 22)}
    else:
        bs, d, dtype = 16, 128, jnp.bfloat16
        shapes = WALK_SHAPES
    peak = chip_peaks().hbm_bw
    groups = [int(x) for x in args.walk_groups.split(",") if x]
    record = {"walk": True, "seed": args.seed, "platform": jax.default_backend(), "cells": {}}
    worst = 0.0
    for cell, (lanes, live, n, nkv, layers, nb, rung, low, high, reply, twin, window) in shapes.items():
        if args.walk_cells not in cell:
            continue
        rng = np.random.default_rng(args.seed)
        width = rung // bs
        contexts = (np.exp(rng.uniform(np.log(low), np.log(high), lanes)).astype(np.int64)
                    + rng.integers(0, reply + 1, lanes))
        free = rng.permutation(np.arange(1, nb))
        if window is None:
            contexts = np.minimum(contexts, rung)
            # every live lane its own blocks, scattered over the pool; past its
            # frontier — and all of an idle lane's row — the null block, as the
            # engine's table has it (an idle lane keeps a position all the same)
            tables = np.zeros((lanes, width), np.int32)
            for lane, rows in enumerate(contexts[:live]):
                blocks = -(-int(rows) // bs)
                tables[lane, :blocks], free = free[:blocks], free[blocks:]
            null = None
            walked = -(-contexts[:live] // bs)
            sweep = groups
        else:
            # every lane a ring of its own, scattered; which lanes are null is
            # said beside the table
            tables = free[:lanes * width].reshape(lanes, width).astype(np.int32)
            null = jnp.arange(lanes) >= live
            last = contexts[:live] - 1
            walked = last // bs - np.maximum(last - window + 1, 0) // bs + 1
            sweep = window_sweep_groups(window, bs, nkv)
        tables, positions = jnp.asarray(tables), jnp.asarray(contexts - 1, jnp.int32)
        keys = jax.random.split(jax.random.key(args.seed), 3)
        k_pool = jax.random.normal(keys[0], (layers, nb, bs, nkv, d), dtype)
        v_pool = jax.random.normal(keys[1], (layers, nb, bs, nkv, d), dtype)
        q = jax.random.normal(keys[2], (lanes, n, d), dtype)
        live_bytes = int(2 * np.sum(walked) * bs * nkv * d * k_pool.dtype.itemsize)

        model = LlamaDecode(LlamaConfig(num_heads=n, num_kv_heads=nkv, head_dim=d))

        def gather(q, k_pool, v_pool, tables, positions, layer):
            """What the walk replaced at t == 1: ``LagunaDecode._attend``'s
            read of a full layer, or ``LlamaDecode._attend_paged``'s."""
            if twin == "laguna":
                at = layer * nb + tables

                def read(a):
                    got = a.reshape((layers * nb,) + a.shape[2:])[at]
                    return got.reshape((lanes, rung) + got.shape[3:])

                # the position a row of the table holds, as a query reads it:
                # the row's own where the table is as wide as the context
                pos = positions[:, None, None]
                k_pos = pos - (pos - jnp.arange(rung, dtype=jnp.int32)) % rung
                return masked_attention(
                    q[:, None], read(k_pool), read(v_pool),
                    visible(positions[:, None], k_pos, window))[:, 0]
            j = jnp.arange(rung, dtype=jnp.int32)
            at = layer * nb * bs + tables[:, j // bs] * bs + (j % bs)[None, :]

            def read(a):
                return a.reshape((layers * nb * bs,) + a.shape[3:])[at]

            return model._cache_attention(
                q[:, None], read(k_pool), read(v_pool), positions[:, None], None)[:, 0]

        def timed(read):
            got, entry = _time_a_layer(
                read, (q, k_pool, v_pool, tables, positions), layers, live_bytes, args)
            entry["share_of_hbm_peak"] = round(entry["live_gb_s"] * 1e9 / peak, 4)
            return got[:live], entry

        want, gathered = timed(gather)
        entry = {
            "lanes": lanes, "live_lanes": live, "heads": [n, nkv], "rung": rung, "layers": layers,
            "mean_context": float(contexts[:live].mean()),
            "live_mb_a_layer": round(live_bytes / 1e6, 2),
            "derived_group": (walk_group(bs, nkv) if window is None
                              else window_walk_group((window - 1) // bs + 2, bs, nkv)),
            "gather_and_scores": gathered, "walk_by_group": {},
        }
        scale = float(jnp.max(jnp.abs(want)))
        for group in sweep:
            got, walked = timed(
                lambda *a, group=group: paged_decode_walk(
                    *a, kv_limit=rung, group=group, window=window, null_lanes=null))
            walked["distance"] = float(jnp.max(jnp.abs(got - want))) / scale
            worst = max(worst, walked["distance"])
            entry["walk_by_group"][str(group)] = walked
        record["cells"][cell] = entry
    # a bf16 pool: p and the scores are rounded at other places in the two
    if worst > (2e-2 if dtype == jnp.bfloat16 else 1e-5):
        record["gate_failure"] = f"walk is {worst:.3g} of the output's scale from the gather"
    return record


# (lanes, heads, pool blocks, rung, shared documents, a document's rows, the
# lowest and highest question + reply rows) of the cells that run the latent walk
LATENT_SHAPES = {
    "xing-longdoc-batch": (32, 32, 20480, 16384, 16, 14336, 64, 2048),
    "sarvam-docqa-batch": (64, 64, 12288, 3072, 24, 2560, 32, 512),
}


def _latent_sweep(args) -> dict:
    """Measurement 10 of the module's list."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        latent_decode_walk,
    )
    from neuronx_distributed_llama3_2_tpu.models.sarvam import (
        _blocked_softmax_attention,
    )

    if args.smoke:
        layers, bs, w, r, dr, dtype = 2, 4, 128, 32, 8, jnp.float32
        shapes = {"smoke": (4, 4, 48, 32, 2, 12, 2, 16)}
    else:
        layers, bs, w, r, dr, dtype = 5, 16, 640, 512, 64, jnp.bfloat16
        shapes = LATENT_SHAPES
    scale = (128 + dr) ** -0.5
    groups = [int(x) for x in args.walk_groups.split(",") if x]
    record = {"latent": True, "seed": args.seed, "platform": jax.default_backend(), "cells": {}}
    worst = 0.0
    for cell, (lanes, n, nb, rung, docs, doc_rows, low, high) in shapes.items():
        rng = np.random.default_rng(args.seed)
        width = rung // bs
        contexts = np.minimum(doc_rows + rng.integers(low, high + 1, lanes), rung)
        positions = jnp.asarray(contexts - 1, jnp.int32)
        # a document's blocks are shared by the lanes that drew it, a lane's
        # own rows are its own blocks, all scattered over the pool; past a
        # lane's frontier the null block, as the engine's table has it
        free = rng.permutation(np.arange(1, nb))
        doc_blocks = doc_rows // bs
        shared, free = free[:docs * doc_blocks].reshape(docs, doc_blocks), free[docs * doc_blocks:]
        tables = np.zeros((lanes, width), np.int32)
        for lane, rows in enumerate(contexts):
            blocks = -(-int(rows) // bs)
            tables[lane, :doc_blocks] = shared[rng.integers(docs)]
            own = blocks - doc_blocks
            tables[lane, doc_blocks:blocks], free = free[:own], free[own:]
        tables = jnp.asarray(tables)
        keys = jax.random.split(jax.random.key(args.seed), 2)
        pool = jax.random.normal(keys[0], (layers, nb, bs, w), dtype)
        q_abs = jax.random.normal(keys[1], (lanes, n, r + dr), dtype)
        live_bytes = int(np.sum(-(-contexts // bs)) * bs * w * pool.dtype.itemsize)

        def gather(q_abs, pool, tables, positions, layer):
            """``SarvamDecode._latent_attention``'s read at t == 1 before the
            walk: the rung's blocks gathered, the absorbed scores over them."""
            seen = pool.reshape(layers * nb, bs, w)[layer * nb + tables]
            seen = seen.reshape(lanes, rung, w)[..., :r + dr]
            return _blocked_softmax_attention(
                q_abs[:, None], seen, seen[..., :r], positions[:, None], scale,
                ("btnd,bsd->bnts", "bnts,bsr->btnr"))[:, 0]

        def timed(read):
            return _time_a_layer(
                read, (q_abs, pool, tables, positions), layers, live_bytes, args)

        want, gathered = timed(gather)
        entry = {
            "lanes": lanes, "heads": n, "rung": rung,
            "mean_context": float(contexts.mean()),
            "live_mb_a_layer": round(live_bytes / 1e6, 2),
            "gather_and_scores": gathered, "walk_by_group": {},
        }
        size = float(jnp.max(jnp.abs(want)))
        for group in groups:
            got, walked = timed(
                lambda *a, group=group: latent_decode_walk(
                    *a, rank=r, sm_scale=scale, kv_limit=rung, group=group))
            walked["distance"] = float(jnp.max(jnp.abs(got - want))) / size
            worst = max(worst, walked["distance"])
            entry["walk_by_group"][str(group)] = walked
        record["cells"][cell] = entry
    # a bf16 pool: p and the scores are rounded at other places in the two
    if worst > (2e-2 if dtype == jnp.bfloat16 else 1e-5):
        record["gate_failure"] = f"walk is {worst:.3g} of the output's scale from the gather"
    return record


def run_bench(args: argparse.Namespace) -> dict:
    import jax

    from neuronx_distributed_llama3_2_tpu.models import resolve_model

    entry = resolve_model(args.model)
    config = dataclasses.replace(entry["config"], max_seq_len=args.max_seq_len)
    params = entry["model_cls"](config).init(jax.random.key(args.seed))

    cases = [
        _decode_case(config, params, limit, args)
        for limit in args.kv_limit_list
    ]
    stall = _stall_ab(config, params, args)
    loop_leg = _loop_leg(config, params, args)
    spec = _spec_ab(config, params, args)
    tree = _tree_ab(config, params, args)
    tp_ab = _tp_ab(config, params, args)
    quant = _quant_ab(config, params, args)
    samp = _sampling_ab(config, params, args)
    fused = _fused_ab(config, params, args)

    record = {
        "bench": "paged_decode",
        "model": args.model,
        "chip": str(jax.devices()[0]),
        "smoke": bool(args.smoke),
        "batch": args.batch,
        "block_size": args.block_size,
        "iters": args.iters,
        "decode_cases": cases,
        **stall,
        **loop_leg,
        **spec,
        **tree,
        **tp_ab,
        **quant,
        **samp,
        **fused,
    }
    failures = []
    for c in cases:
        if not c["argmax_parity"]:
            failures.append(
                f"kernel/gather greedy argmax diverges at kv_limit={c['kv_limit']}"
            )
    if not stall["chunked_parity"]:
        failures.append("chunked-prefill outputs diverge from unchunked")
    if not spec["spec_parity"]:
        failures.append("speculative outputs diverge from plain greedy loop")
    if spec["spec_tokens_per_step"] <= 1.0:
        failures.append(
            "speculation failed to beat 1 token/step on repetitive prompts "
            f"({spec['spec_tokens_per_step']})"
        )
    if not tree["tree_parity"]:
        failures.append(
            "tree-speculation outputs diverge from the linear-spec engine"
        )
    if tree["tree_verify_steps"] < 1:
        failures.append("tree leg dispatched no packed-tree verify")
    if tree["tree_vs_linear"] <= 1.0:
        failures.append(
            "packed-tree speculation failed to beat linear tokens/step at "
            f"equal draft budget ({tree['tree_tokens_per_step']} vs "
            f"{tree['tree_linear_tokens_per_step']} linear)"
        )
    if "tp_ab_skipped" not in tp_ab:
        if not tp_ab["tp_parity"]:
            failures.append("tp-sharded serving outputs diverge from tp=1")
        if not tp_ab["tp_kernel_eligible"]:
            failures.append(
                "tp-sharded engine fell back to the dense gather "
                "(paged kernel not eligible under the mesh)"
            )
    if not quant["quant_parity"]:
        failures.append(
            "int8 kernel outputs diverge from the int8 gather engine"
        )
    bad_ratio = [
        c for c in quant["quant_capacity_cases"] if c["lanes_ratio"] < 1.9
    ]
    if bad_ratio:
        failures.append(
            "int8 capacity ratio below 1.9x at kv_limit "
            + ",".join(str(c["kv_limit"]) for c in bad_ratio)
        )
    if not samp["sampling_fused_greedy_parity"]:
        failures.append(
            "fused-sampling greedy outputs diverge from the host greedy "
            "engine (sentinel-params argmax contract broken)"
        )
    if not samp["sampling_fused_deterministic"]:
        failures.append("fused sampled outputs are not seed-deterministic")
    if samp["sampling_steady_decode_uploads"] != 0:
        failures.append(
            "fused sampled decode paid "
            f"{samp['sampling_steady_decode_uploads']} steady-state "
            "h2d upload(s) (zero-upload contract broken)"
        )
    if not fused["fused_parity"]:
        failures.append(
            "fused mixed-mode outputs diverge from the unfused engine"
        )
    if fused["fused_mixed_dispatches"] < 1:
        failures.append("fused leg dispatched no pmixed program")
    if (fused["fused_dispatches_per_step"]
            >= fused["unfused_dispatches_per_step"]):
        failures.append(
            "fused_step failed to reduce dispatches/step "
            f"({fused['fused_dispatches_per_step']} vs "
            f"{fused['unfused_dispatches_per_step']} unfused)"
        )
    if failures:
        record["gate_failure"] = "; ".join(failures)
    return record


def main() -> None:
    args = build_args()
    if args.smoke:
        # the smoke tier is the CPU CI check; a 2-device virtual backend
        # lets the tp A/B run there too (must precede backend init)
        from neuronx_distributed_llama3_2_tpu.utils.runtime import (
            set_cpu_devices,
        )

        set_cpu_devices(max(2, args.tp))
    if args.latent:
        record = _latent_sweep(args)
    else:
        record = _walk_sweep(args) if args.walk else run_bench(args)
    # the record prints even when a gate fails: a regression must still
    # yield the measured numbers, not just an exception tail
    print(json.dumps(record), flush=True)
    if record.get("gate_failure"):
        raise SystemExit(record["gate_failure"])


if __name__ == "__main__":
    main()
