"""The serving check at a prompt the cell's own check cannot hold: one request
as long as the cell's traffic (15k rows where ``longdoc-batch``'s check stops
at 3,072), the engine's tokens and its programs' logits against the float32
reference — on a sample of rows, because a 131,072-wide float32 row is 0.5 MB
and ``check.py`` holds every row of the sequence twice on the device. One
process, one engine, the cell's sizes but for the pool (one request's blocks
and the check's own copy are all it needs; the reference's temporaries take
the room):

    chiprun -- python3 benchmarks/tools/check_long_rows.py xing-longdoc-batch --seed 0 --prompt-tokens 15360

What it compares is ``check.serving_engine``'s parts A and B (the cell's
``router_sharpen`` has to be 1): the tokens the engine emits through ``submit``
and ``step`` sit at the reference's largest logit, and the rows kept — every
``--every``-th, sixteen around each multiple of the rotary tables' original
range, the last prompt row and every decode row — lie within the cell's
``tolerance`` of the reference's, read by band of positions. Part C (the
pool's precision) is ``check_paged_variant.py``'s. The loop over chunks is
``check.paged_logits``'s with the rows kept pulled to the host chunk by chunk;
a ``keep`` argument there would make this its caller (PERF.md section 7)."""

import argparse
import functools
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks import check, serving, spec  # noqa: E402


def rows_kept(n_prompt: int, steps: int, every: int, original: int) -> np.ndarray:
    """Positions of ``prompt + fed`` (``steps`` tokens fed back) to compare."""
    n = n_prompt + steps
    keep = set(range(0, n_prompt, every)) | set(range(n_prompt - 1, n))
    for edge in range(original, n, original):
        keep |= set(range(max(edge - 8, 0), min(edge + 8, n)))
    return np.asarray(sorted(keep), np.int64)


def paged_rows(srv, params, pool, prompt, fed, sizes, keep: np.ndarray) -> np.ndarray:
    """``check.paged_logits`` — the engine's model with the calls its paged
    programs make, over ``pool`` — returning the rows ``keep`` names alone."""
    import jax
    import jax.numpy as jnp

    model, live = srv.model, srv.engine._live_params
    bs, lanes, max_len = int(sizes["block_size"]), int(sizes["lanes"]), int(sizes["max_seq_len"])
    chunk = int(sizes["prefill_chunk_tokens"])
    prefill_rungs = sorted({*sizes["prefill_buckets"], max_len})
    kv_rungs = sorted({*sizes["kv_buckets"], max_len})
    blocks = -(-(len(prompt) + len(fed)) // bs)
    table = np.zeros((lanes, srv.table_width), np.int32)      # block 0 is the null block
    table[0, :blocks] = 1 + np.arange(blocks)
    table = jnp.asarray(table)
    head = model._model()._logits

    @functools.partial(jax.jit, donate_argnums=(1,))
    def ctx(params, pool, ids, rows):
        params = live(params)
        hidden, pool = model.forward(
            params, pool, ids, jnp.zeros((1,), jnp.int32), None,
            context_encode=True, return_hidden=True, block_tables=table[:1])
        return head(params, hidden[:, rows])[0], pool

    @functools.partial(jax.jit, static_argnames=("kv_limit",), donate_argnums=(1,))
    def sfx(params, pool, ids, start, rows, *, kv_limit):
        params = live(params)
        hidden, pool = model.forward(
            params, pool, ids, start, None,
            return_hidden=True, block_tables=table[:1], kv_limit=kv_limit)
        return head(params, hidden[:, rows])[0], pool

    @functools.partial(jax.jit, static_argnames=("kv_limit",), donate_argnums=(1,))
    def dec(params, pool, tokens, positions, *, kv_limit):
        logits, _, pool = model.decode_step(
            live(params), pool, tokens, positions, table, kv_limit=kv_limit)
        return logits[:1], pool

    out = []
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        bucket = check._rung(prefill_rungs, len(piece))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(piece)] = piece
        here = keep[(keep >= start) & (keep < start + len(piece))] - start
        # the head is row-wise (the final norm and one matrix): it reads the kept rows first,
        # padded with row 0 to the bucket's one shape, and the host cuts the padding off
        rows = np.zeros((bucket,), np.int32)
        rows[:len(here)] = here
        if start == 0:
            logits, pool = ctx(params, pool, jnp.asarray(ids), jnp.asarray(rows))
        else:
            logits, pool = sfx(
                params, pool, jnp.asarray(ids), jnp.full((1,), start, jnp.int32), jnp.asarray(rows),
                kv_limit=check._rung(kv_rungs, min(start + bucket, max_len)))
        out.append(np.asarray(logits[:len(here)], np.float32))
    lane0 = jnp.zeros((lanes,), jnp.int32).at[0].set(1)
    for i, token in enumerate(fed):
        position = len(prompt) + i
        logits, pool = dec(params, pool, lane0 * token, lane0 * position,
                           kv_limit=check._rung(kv_rungs, position + 1))
        if position in keep:
            out.append(np.asarray(logits, np.float32))
    return np.concatenate(out, axis=0)


def long_rows(n_prompt: int, every: int):
    """A stand-in for ``check.serving_engine`` with ``serving.build``'s call."""

    def run(srv, family, model_cfg, spec_, sizes, *, seed, service_class="batch"):
        import jax
        import jax.numpy as jnp

        steps, tol = int(spec_["decode_steps"]), float(spec_["tolerance"])
        assert float(spec_.get("router_sharpen", 1.0)) == 1.0, "part B here runs the engine's own weights"
        assert n_prompt + steps < int(sizes["max_seq_len"]), (n_prompt, steps, sizes["max_seq_len"])
        vocab = int(model_cfg.vocab_size)
        # the band edges: the positions the rotary tables were trained on, where the model says
        original = int(model_cfg.yarn[1]) if getattr(model_cfg, "yarn", None) else n_prompt
        prompt = np.random.default_rng([seed, 0xC4EC]).integers(1, vocab, n_prompt).tolist()
        tokens_run = check.engine_tokens(
            srv, prompt, min(steps + 1, srv.gen.max_new_tokens), service_class)
        tokens = tokens_run["tokens"]
        if tokens_run["error"] is not None or not tokens:
            return {"ok": False, "engine": tokens_run}
        fed = tokens[:-1]
        keep = rows_kept(n_prompt, len(fed), every, original)
        ref_cfg = family.reference_config(model_cfg)
        with jax.default_matmul_precision("highest"):
            want, margin = jax.jit(
                lambda p, i, r: family.reference.forward_with_margin(p, ref_cfg, i, r)
            )(srv.engine.params, jnp.asarray([prompt + fed], jnp.int32), jnp.asarray(keep, jnp.int32))
        want, margin = np.asarray(want[0]), np.asarray(margin[0])
        # the reference's row that predicts tokens[i] is position n_prompt - 1 + i: the last kept rows
        deficits = check.token_deficits(want[-len(tokens):], tokens)
        near = float((deficits <= check.TOKEN_DEFICIT).mean())

        bs = int(sizes["block_size"])
        pool = srv.model.init_paged_cache(
            2 + (n_prompt + len(fed)) // bs, bs, srv.paged.cache_dtype,
            kv_cache_dtype=srv.paged.kv_cache_dtype)
        got = paged_rows(srv, srv.engine.params, pool, prompt, fed, sizes, keep)
        err = np.linalg.norm(got - want, axis=-1) / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
        clear = margin >= float(spec_.get("clear_margin", check.SERVING_CLEAR_MARGIN))

        def band(mask):
            e = err[mask]
            return {"rows": int(mask.sum()), "p10": check._q(e, 10), "p50": check._q(e, 50),
                    "p90": check._q(e, 90), "clear_share": float(clear[mask].mean()) if mask.any() else None,
                    "clear_p50": check._q(err[mask & clear], 50),
                    "argmax_agree": float((got[mask].argmax(-1) == want[mask].argmax(-1)).mean())}

        edges = list(range(0, n_prompt, original)) + [n_prompt]
        bands = {f"prompt rows {lo}-{hi - 1}": band((keep >= lo) & (keep < hi))
                 for lo, hi in zip(edges, edges[1:])}
        bands["decode rows"] = band(keep >= n_prompt)
        ok = bool(np.isfinite(got).all() and near >= check.TOKEN_SHARE and all(
            b["p50"] <= tol and b["clear_p50"] <= tol and b["p10"] <= tol / 2 for b in bands.values()))
        return {"ok": ok, "prompt_tokens": n_prompt, "rows_compared": int(len(keep)),
                "engine_tokens": {"n": len(tokens), "near_reference_max": near,
                                  "deficit_max": check._q(deficits, 100)},
                "all_rows": band(np.ones_like(keep, bool)), "bands": bands, "tolerance": tol,
                "original_max_position_embeddings": original}

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-tokens", type=int, required=True)
    ap.add_argument("--every", type=int, default=32, help="keep every n-th prompt row")
    ap.add_argument("--pool-blocks", type=int, default=2048)
    ap.add_argument("--rehearse-on-cpu", type=int, default=0)
    args = ap.parse_args()

    import jax
    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache, require_tpu, set_cpu_devices,
    )

    cell = spec.load_cell(args.workload)
    rehearsal = args.rehearse_on_cpu > 0
    if rehearsal:
        os.environ["NXDT_KERNEL_MODE"] = "interpret"
        set_cpu_devices(args.rehearse_on_cpu)
        cell = cell.for_rehearsal()
    else:
        require_tpu()
        cell.traffic["engine"]["pool_blocks"] = args.pool_blocks
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    enable_compile_cache()
    check.serving_engine = long_rows(args.prompt_tokens, args.every)
    split = {}
    _, _, checked = serving.build(
        cell, spec.load_family(cell.config["family"]), args.seed, rehearsal, False, split)
    print(f"seed {args.seed} set-up {json.dumps(split)}: {json.dumps(checked)}", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory_peak_bytes {stats.get('peak_bytes_in_use')}", flush=True)


if __name__ == "__main__":
    main()
