"""The comparisons that decide ``correct``: the system under test against the
family's plain float32 reference, on seeded inputs, outside the timed window.

Serving is checked on the engine the window measures, after it is built:
the tokens it emits, its logits and what its cache loses (below). Training is
checked on the step-0 loss.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

# The serving check has three parts, all on one seeded prompt that is longer
# than a prefill chunk, after the serving engine is built and prewarmed:
#
# A. *The engine as a request sees it.* The prompt goes through the engine's
#    own ``submit``/``step`` — its registered programs, its pool, its sampler
#    and whatever loop, fusion or speculation it ships as the default — and the
#    first tokens it emits are kept. The float32 reference then scores the
#    same sequence: each emitted token's reference logit may sit only a little
#    under the reference's largest (``deficit``, in standard deviations of the
#    row). With random weights the largest logit changes on rounding, so the
#    tokens need not be the reference's argmax, and where bf16 routes a token
#    to another expert than float32 (below) the engine's choice is, rightly,
#    far down the reference's row: 6 of 102 emitted tokens sat more than one
#    deviation down (the worst 2.8, the worst run 2 of its 9; v5e, eight runs,
#    PERF.md Findings PR 22). So half the tokens have to sit within one
#    deviation — a sampler, a fused step or a verify rule that emits something
#    else lands about four deviations down (the gap between the largest of a
#    vocabulary's worth of logits and a typical one) on every token, while 9
#    misrouted tokens of 17 at the rate measured have a chance under one in a
#    million.
# B. *Logits.* The same sequence is replayed, teacher-forced, through the
#    engine's model object with the calls its programs make (``pctx``: context
#    prefill of the first chunk; ``psfx``: the later chunks over the cached
#    prefix; ``pdecode``: single-token steps at the cell's lane count, the
#    other lanes idle on the null block), at the engine's own chunk size,
#    ladders and table width, over a small pool built the way the engine builds
#    its own (``PagedConfig.cache_dtype`` / ``kv_cache_dtype``). The programs
#    themselves return sampled tokens and never logits, so this is as close as
#    logits get. Every row (one position, the whole vocabulary) is held to the
#    reference: norm of the difference over norm of the reference's row.
# C. *What the cache loses.* B again over a plain pool in the model's dtype;
#    the rows may differ from B's by no more than ``cache_tolerance``. Where
#    the engine's pool already is that pool the two runs are one program on
#    one input, the distance is exactly 0, and the run is skipped.
#
# Why B's tolerance is wide and C exists. The system computes in bf16, the
# reference in float32 at "highest" precision; some 45 roundings to 8 bits of
# mantissa lie between the embedding and the logits of three layers, which
# alone puts a row 2-3 % off. A sparse model adds a legitimate source of large
# differences: where a token's last chosen expert and the best one left out are
# within a rounding of each other, bf16 and float32 route it differently, that
# row's logits are another function's (50-120 % off, measured on the v5e, every
# such row at a routing margin under 5 %). B's weights therefore have their
# router sharpened (`sharpen_router`, factor in the traffic file), which makes a
# flip cheap, and its criterion is robust: the median row and the median
# clearly-routed row (margin >= `clear_margin`) within `tolerance`, the
# cleanest tenth of the rows within half of it. That catches a wrong mask, a
# dropped expert, a bad rotary table or a cache row read from the wrong block
# (tens of percent on most rows), but bf16's own distance from float32 is as
# large as what an 8-bit cache adds (measured, PERF.md Findings PR 22), so no
# tolerance on B can tell them apart. C can: it holds everything but the cache
# fixed, so bf16's noise cancels, and B ties its other side to the reference.
# Measured numbers and the thresholds argued from them: PERF.md, Findings PR 22.
SERVING_ROW_REL = 0.10
SERVING_CLEAR_MARGIN = 0.1
SERVING_CACHE_REL = 0.01
TOKEN_DEFICIT = 1.0          # standard deviations of the reference's row
TOKEN_SHARE = 0.5            # of the emitted tokens within TOKEN_DEFICIT
TRAIN_LOSS_REL = 2e-4


def sharpen_router(params, factor: float):
    """``params`` with every router kernel multiplied by ``factor`` (the
    check's weights, for the system and the reference alike). Random router
    weights put a token's last chosen expert and the best one left out within
    a rounding of each other for a few percent of tokens; bf16 and float32
    then choose differently and the row is, legitimately, far off. Scaling the
    router's logits changes no code path and not how often that happens, but
    what it costs: the gate of the expert that can flip shrinks as
    ``exp(-factor * gap)``, so a flip moves the row by little."""
    import jax

    if factor == 1.0:
        return params
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * factor if "router" in jax.tree_util.keystr(path) else x, params
    )


def _rung(ladder, n: int) -> int:
    """Smallest rung >= n (the top one past the ladder), as the engine picks."""
    return next((b for b in ladder if b >= n), ladder[-1])


def engine_tokens(serving, prompt: List[int], want: int, service_class: str) -> Dict[str, Any]:
    """The first ``want`` tokens the serving engine emits for ``prompt``,
    through ``submit`` and ``step`` as the front door drives them; the request
    is cancelled once it has them, and the engine stepped until idle."""
    rid = serving.submit(prompt, service_class=service_class)
    for _ in range(16 * want + 4 * len(prompt)):
        if len(serving.request_tokens(rid)) >= want or serving.request_info(rid)["done"]:
            break
        serving.step()
    tokens = serving.request_tokens(rid)[:want]
    info = serving.request_info(rid)
    error = info["error"] if info["status"] == "failed" else None
    if not info["done"]:
        serving.cancel(rid, reason="benchmark check has its tokens")
    while serving.step():
        pass
    return {"tokens": [int(t) for t in tokens], "error": error}


def paged_logits(serving, params, pool, prompt: List[int], fed: List[int],
                 sizes: Dict[str, Any]):
    """Logits rows, one per position of ``prompt + fed``, through the engine's
    model with the calls its paged programs make, over ``pool``."""
    import jax
    import jax.numpy as jnp

    model, live = serving.model, serving.engine._live_params
    bs, lanes, max_len = int(sizes["block_size"]), int(sizes["lanes"]), int(sizes["max_seq_len"])
    chunk = int(sizes["prefill_chunk_tokens"]) or len(prompt)
    prefill_rungs = sorted({*sizes["prefill_buckets"], max_len})
    kv_rungs = sorted({*sizes["kv_buckets"], max_len})
    blocks = -(-(len(prompt) + len(fed)) // bs)
    table = np.zeros((lanes, serving.table_width), np.int32)      # block 0 is the null block
    table[0, :blocks] = 1 + np.arange(blocks)
    table = jnp.asarray(table)
    head = model._model()._logits

    @functools.partial(jax.jit, donate_argnums=(1,))
    def ctx(params, pool, ids):
        params = live(params)
        hidden, pool = model.forward(
            params, pool, ids, jnp.zeros((1,), jnp.int32), None,
            context_encode=True, return_hidden=True, block_tables=table[:1],
        )
        return head(params, hidden)[0], pool

    @functools.partial(jax.jit, static_argnames=("kv_limit",), donate_argnums=(1,))
    def sfx(params, pool, ids, start, *, kv_limit):
        params = live(params)
        hidden, pool = model.forward(
            params, pool, ids, start, None,
            return_hidden=True, block_tables=table[:1], kv_limit=kv_limit,
        )
        return head(params, hidden)[0], pool

    @functools.partial(jax.jit, static_argnames=("kv_limit",), donate_argnums=(1,))
    def dec(params, pool, tokens, positions, *, kv_limit):
        logits, _, pool = model.decode_step(
            live(params), pool, tokens, positions, table, kv_limit=kv_limit,
        )
        return logits[:1], pool

    rows = []
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        bucket = _rung(prefill_rungs, len(piece))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(piece)] = piece
        if start == 0:
            logits, pool = ctx(params, pool, jnp.asarray(ids))
        else:
            logits, pool = sfx(
                params, pool, jnp.asarray(ids), jnp.full((1,), start, jnp.int32),
                kv_limit=_rung(kv_rungs, min(start + bucket, max_len)),
            )
        rows.append(logits[:len(piece)])
    lane0 = jnp.zeros((lanes,), jnp.int32).at[0].set(1)
    for i, token in enumerate(fed):
        position = len(prompt) + i
        logits, pool = dec(
            params, pool, lane0 * token, lane0 * position, kv_limit=_rung(kv_rungs, position + 1),
        )
        rows.append(logits)
    return np.asarray(jnp.concatenate(rows, axis=0), np.float32)


def token_deficits(want_rows: np.ndarray, tokens: List[int]) -> np.ndarray:
    """How far under the reference's largest logit each emitted token's
    reference logit sits, in standard deviations of its row. ``want_rows[i]``
    is the reference's row that predicts ``tokens[i]``."""
    want_rows = np.asarray(want_rows, np.float64)
    chosen = want_rows[np.arange(len(tokens)), np.asarray(tokens, np.int64)]
    return (want_rows.max(axis=-1) - chosen) / np.maximum(want_rows.std(axis=-1), 1e-30)


def _q(xs, p) -> float:
    return float(np.percentile(xs, p)) if len(xs) else float("nan")


def serving_engine(serving, family, model_cfg, spec: Dict[str, Any], sizes: Dict[str, Any], *,
                   seed: int, service_class: str = "batch") -> Dict[str, Any]:
    """Parts A, B and C above on the built engine; ``ok`` decides ``correct``."""
    import jax
    import jax.numpy as jnp

    n_prompt, steps = int(spec["prompt_tokens"]), int(spec["decode_steps"])
    tol = float(spec.get("tolerance", SERVING_ROW_REL))
    cache_tol = float(spec.get("cache_tolerance", SERVING_CACHE_REL))
    clear_margin = float(spec.get("clear_margin", SERVING_CLEAR_MARGIN))
    sharpen = float(spec.get("router_sharpen", 1.0))
    vocab = int(model_cfg.vocab_size)
    assert n_prompt > int(sizes["prefill_chunk_tokens"]) > 0, "the prompt must span two chunks"
    prompt = np.random.default_rng([seed, 0xC4EC]).integers(1, vocab, n_prompt).tolist()

    # A: tokens out of the engine itself
    run = engine_tokens(serving, prompt, min(steps + 1, serving.gen.max_new_tokens), service_class)
    tokens = run["tokens"]
    in_range = [0 <= t < vocab for t in tokens]
    if run["error"] is not None or not tokens or not all(in_range):
        return {"ok": False, "engine": run, "tokens_in_range": in_range}
    fed = tokens[:-1]
    sequence = jnp.asarray([prompt + fed], jnp.int32)

    ref_cfg = family.reference_config(model_cfg)
    with_margin = getattr(family.reference, "forward_with_margin", None)
    if with_margin is None:
        def with_margin(p, c, i):
            logits = family.reference.forward_logits(p, c, i)
            return logits, jnp.ones(logits.shape[:2], jnp.float32)
    jitted = jax.jit(lambda p, i: with_margin(p, ref_cfg, i))

    def reference(p):
        with jax.default_matmul_precision("highest"):
            logits, margin = jitted(p, sequence)
        return np.asarray(logits[0]), np.asarray(margin[0])

    params = serving.engine.params
    want, margin = reference(params)
    deficits = token_deficits(want[n_prompt - 1:], tokens)
    near = float((deficits <= TOKEN_DEFICIT).mean())

    # B: logits through the engine's model and its kind of pool
    sharp = sharpen_router(params, sharpen)
    if sharp is not params:
        want, margin = reference(sharp)
    bs = int(sizes["block_size"])
    blocks = 1 + -(-(n_prompt + len(fed)) // bs)
    own_pool = lambda: serving.model.init_paged_cache(  # noqa: E731
        blocks, bs, serving.paged.cache_dtype, kv_cache_dtype=serving.paged.kv_cache_dtype,
    )
    plain_pool = lambda: serving.model.init_paged_cache(blocks, bs)  # noqa: E731
    got = paged_logits(serving, sharp, own_pool(), prompt, fed, sizes)
    norm = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    rows_err = np.linalg.norm(got - want, axis=-1) / norm
    clear = margin >= clear_margin

    # C: the same rows over a plain pool in the model's dtype
    def kind(make):
        leaves, treedef = jax.tree.flatten(jax.eval_shape(make))
        return treedef, [(a.shape, a.dtype) for a in leaves]

    plain_is_own = kind(own_pool) == kind(plain_pool)
    if plain_is_own:
        cache_err = np.zeros_like(rows_err)
    else:
        plain = paged_logits(serving, sharp, plain_pool(), prompt, fed, sizes)
        cache_err = np.linalg.norm(got - plain, axis=-1) / norm
    del sharp

    ok = bool(
        np.isfinite(got).all()
        and near >= TOKEN_SHARE
        and clear.mean() >= 0.25
        and _q(rows_err[clear], 50) <= tol
        and _q(rows_err, 50) <= tol
        and _q(rows_err, 10) <= tol / 2
        and _q(cache_err, 50) <= cache_tol
    )
    worst = np.argsort(-rows_err)[:5]
    return {
        "ok": ok,
        "engine_tokens": {"n": len(tokens), "near_reference_max": near,
                          "deficit_p50": _q(deficits, 50), "deficit_max": _q(deficits, 100),
                          "limit": TOKEN_DEFICIT, "share_needed": TOKEN_SHARE},
        "clear_rows": {"share": float(clear.mean()), "p50": _q(rows_err[clear], 50),
                       "p90": _q(rows_err[clear], 90), "max": _q(rows_err[clear], 100)},
        "all_rows": {"p10": _q(rows_err, 10), "p50": _q(rows_err, 50), "p90": _q(rows_err, 90),
                     "max": _q(rows_err, 100)},
        "decode_rows_p50": _q(rows_err[n_prompt:], 50),
        "cache": {"plain_pool_is_own": bool(plain_is_own), "p50": _q(cache_err, 50),
                  "p90": _q(cache_err, 90), "tolerance": cache_tol},
        "worst_rows": [[int(i), round(float(rows_err[i]), 4), round(float(margin[i]), 4)] for i in worst],
        "tolerance": tol, "clear_margin": clear_margin, "router_sharpen": sharpen,
        "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
        "rows": int(got.shape[0]),
    }


def reference_loss(family, model_cfg, params, canonical, ids) -> float:
    """The reference's next-token loss over ``ids`` with the trainer's own
    (pre-step) weights; ``canonical`` undoes a pipeline's stage layout."""
    import jax

    ref_cfg = family.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        return float(
            jax.jit(lambda p, i: family.reference.loss(canonical(p), ref_cfg, i))(params, ids)
        )


def compare_loss(got: float, want: float, tolerance=None) -> Dict[str, Any]:
    tolerance = TRAIN_LOSS_REL if tolerance is None else float(tolerance)
    err = abs(got - want) / max(abs(want), 1e-30)
    return {
        "ok": bool(np.isfinite(got) and err <= tolerance),
        "loss": got, "reference_loss": want, "rel_error": err,
        "tolerance": tolerance,
    }
