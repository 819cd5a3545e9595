"""On-TPU Pallas kernel numerics gate.

The CPU tier only ever runs the Pallas kernels through the interpreter
(tests/test_kernels.py) and lowers them (tests/test_chip_lowering.py); this
gate runs them ON the TPU, compiled by Mosaic, and asserts parity against
oracles that use no Pallas: the flash kernels fwd/bwd against the blockwise
jnp reference (causal/non-causal, GQA, segment ids, a non-multiple sequence
length), the paged decode kernel against the dense block-table gather it
replaces (fp / quantized pools, the low-precision MXU dot, multi-token
verify, packed trees, the tp shard_map wrapper), each also at
Llama-3.2-1B's head geometry.

Usage: ``chiprun -- python scripts/tpu_kernel_gate.py [substring ...]``
(substrings select cases by name; the ``sharded-`` cases need the four-chip
host). It refuses to run without a TPU. A case the compiler refuses is a FAIL carrying the compiler's
words, not an abort: every case reports. The per-case record also lands in
``chiprun_out/kernel_gate.json``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def _case(name, b, s, n, nkv, d, causal, segments, seed, block_q, block_kv):
    from neuronx_distributed_llama3_2_tpu.kernels.flash_attention import (
        flash_attention_reference,
    )
    from neuronx_distributed_llama3_2_tpu.kernels.pallas_flash_attention import (
        pallas_flash_attention,
    )

    ks = jax.random.split(jax.random.key(seed), 4)
    # moderate-magnitude bf16 inputs: parity tolerance covers bf16 rounding
    q = (jax.random.normal(ks[0], (b, s, n, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    v = (jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    seg = None
    if segments:
        # two packed documents per row
        cut = s // 2
        seg = jnp.where(
            jnp.arange(s)[None, :] < cut, 0, 1
        ).astype(jnp.int32).repeat(b, axis=0).reshape(b, s)

    def loss_pallas(q, k, v):
        o = pallas_flash_attention(
            q, k, v, causal=causal, segment_ids=seg,
            block_q=block_q, block_kv=block_kv,
        )
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        o = flash_attention_reference(q, k, v, causal=causal, segment_ids=seg)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    fwd_p, grads_p = jax.jit(jax.value_and_grad(loss_pallas, argnums=(0, 1, 2)))(q, k, v)
    fwd_r, grads_r = jax.jit(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)

    rel_fwd = abs(float(fwd_p) - float(fwd_r)) / max(abs(float(fwd_r)), 1e-9)
    errs = [rel_fwd]
    for gp, gr in zip(grads_p, grads_r):
        gp = np.asarray(gp, np.float32)
        gr = np.asarray(gr, np.float32)
        denom = max(float(np.abs(gr).max()), 1e-9)
        errs.append(float(np.abs(gp - gr).max()) / denom)
    ok = all(e < 3e-2 for e in errs)  # bf16 inputs; fp32 softmax inside both
    status = "ok" if ok else "FAIL"
    print(
        f"[{status}] {name}: rel_fwd={errs[0]:.2e} "
        f"rel_dq={errs[1]:.2e} rel_dk={errs[2]:.2e} rel_dv={errs[3]:.2e}"
    )
    return ok


def _paged_case(name, b, n, nkv, d, nb, bs, w, kv_limit, num_splits, seed, t=1):
    """Paged flash-decode kernel vs the dense block-table gather reference.

    Forward-only (the decode kernel has no backward; serving never
    differentiates through it). bf16 pool + queries, like serving decode.
    ``t == 1`` exercises the 3-dim single-token API; ``t > 1`` the 4-dim
    multi-token verify path with its block-causal mask (speculative decode).
    """
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode,
    )

    ks = jax.random.split(jax.random.key(seed), 3)
    qshape = (b, n, d) if t == 1 else (b, t, n, d)
    q = (jax.random.normal(ks[0], qshape, jnp.float32) * 0.5).astype(jnp.bfloat16)
    kp = (jax.random.normal(ks[1], (nb, bs, nkv, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    vp = (jax.random.normal(ks[2], (nb, bs, nkv, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    rng = np.random.default_rng(seed)
    nblk = -(-kv_limit // bs)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, w), np.int32)
    for i in range(b):
        tables[i, :nblk] = perm[i * nblk:(i + 1) * nblk]
    tables = jnp.asarray(tables)
    # positions = row of the FIRST fresh query; row 0 pinned to the edge so
    # the last query attends exactly kv_limit rows
    positions = jnp.asarray(
        rng.integers(0, kv_limit - t + 1, size=(b,)), jnp.int32
    ).at[0].set(kv_limit - t)

    def ref(q, kp, vp):
        # dense gather: exactly what the kernel replaces
        g = n // nkv
        q4 = q[:, None] if t == 1 else q                # (b, t, n, d)
        jlog = jnp.arange(kv_limit)
        phys = tables[:, jlog // bs] * bs + (jlog % bs)
        kf = kp.reshape(nb * bs, nkv, d)[phys]          # (b, L, nkv, d)
        vf = vp.reshape(nb * bs, nkv, d)[phys]
        qg = q4.reshape(b, t, nkv, g, d).astype(jnp.float32)
        logits = jnp.einsum("bthgd,blhd->bthgl", qg, kf.astype(jnp.float32))
        logits = logits / jnp.sqrt(jnp.float32(d))
        # block-causal: query row ti sees logical rows <= positions + ti
        mask = (
            jlog[None, None, :]
            <= positions[:, None, None] + jnp.arange(t)[None, :, None]
        )[:, :, None, None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bthgl,blhd->bthgd", p, vf.astype(jnp.float32))
        o = o.reshape(b, t, n, d)
        return o[:, 0] if t == 1 else o

    o_k = jax.jit(
        lambda q, kp, vp: paged_flash_decode(
            q, kp, vp, tables, positions,
            kv_limit=kv_limit, num_splits=num_splits,
        )
    )(q, kp, vp)
    o_r = jax.jit(ref)(q, kp, vp)
    o_k = np.asarray(o_k, np.float32)
    o_r = np.asarray(o_r, np.float32)
    denom = max(float(np.abs(o_r).max()), 1e-9)
    rel = float(np.abs(o_k - o_r).max()) / denom
    ok = rel < 3e-2  # bf16 inputs; fp32 softmax inside both
    print(f"[{'ok' if ok else 'FAIL'}] {name}: rel_fwd={rel:.2e}")
    return ok


def _quant_paged_case(
    name, b, n, nkv, d, nb, bs, w, kv_limit, num_splits, seed, t=1,
    kv_dtype="int8", quant_mxu=False,
):
    """Quantized paged decode: kernel-side dequant (scales DMAd with the
    block) vs the gather reference dequantizing OUTSIDE the kernel.

    The pool is stored at ``kv_dtype`` with per-(row, kv-head) fp16 absmax
    scales (``quantization.kv_cache``); both paths read the identical
    round-tripped values, so the comparison isolates the in-kernel dequant
    arithmetic. Tolerance is looser than the fp paged cases: the kernel
    widens the dequantized product in bf16-adjacent Mosaic arithmetic while
    the reference stays in fp32 end-to-end.
    """
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode,
    )
    from neuronx_distributed_llama3_2_tpu.quantization import (
        kv_cache_jax_dtype,
        kv_dequantize,
        kv_quantize,
    )

    qdtype = kv_cache_jax_dtype(kv_dtype)
    ks = jax.random.split(jax.random.key(seed), 3)
    qshape = (b, n, d) if t == 1 else (b, t, n, d)
    q = (jax.random.normal(ks[0], qshape, jnp.float32) * 0.5).astype(jnp.bfloat16)
    kf = jax.random.normal(ks[1], (nb, bs, nkv, d), jnp.float32) * 0.5
    vf = jax.random.normal(ks[2], (nb, bs, nkv, d), jnp.float32) * 0.5
    kp, ksc = kv_quantize(kf, qdtype)
    vp, vsc = kv_quantize(vf, qdtype)
    rng = np.random.default_rng(seed)
    nblk = -(-kv_limit // bs)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, w), np.int32)
    for i in range(b):
        tables[i, :nblk] = perm[i * nblk:(i + 1) * nblk]
    tables = jnp.asarray(tables)
    positions = jnp.asarray(
        rng.integers(0, kv_limit - t + 1, size=(b,)), jnp.int32
    ).at[0].set(kv_limit - t)

    def ref(q, kp, vp, ksc, vsc):
        # dequantize outside, then the same dense gather the fp cases use
        kd = kv_dequantize(kp, ksc, jnp.bfloat16)
        vd = kv_dequantize(vp, vsc, jnp.bfloat16)
        g = n // nkv
        q4 = q[:, None] if t == 1 else q
        jlog = jnp.arange(kv_limit)
        phys = tables[:, jlog // bs] * bs + (jlog % bs)
        kg = kd.reshape(nb * bs, nkv, d)[phys]
        vg = vd.reshape(nb * bs, nkv, d)[phys]
        qg = q4.reshape(b, t, nkv, g, d).astype(jnp.float32)
        logits = jnp.einsum("bthgd,blhd->bthgl", qg, kg.astype(jnp.float32))
        logits = logits / jnp.sqrt(jnp.float32(d))
        mask = (
            jlog[None, None, :]
            <= positions[:, None, None] + jnp.arange(t)[None, :, None]
        )[:, :, None, None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bthgl,blhd->bthgd", p, vg.astype(jnp.float32))
        o = o.reshape(b, t, n, d)
        return o[:, 0] if t == 1 else o

    o_k = jax.jit(
        lambda q, kp, vp, ksc, vsc: paged_flash_decode(
            q, kp, vp, tables, positions,
            kv_limit=kv_limit, num_splits=num_splits,
            k_scale=ksc, v_scale=vsc, quant_mxu=quant_mxu,
        )
    )(q, kp, vp, ksc, vsc)
    o_r = jax.jit(ref)(q, kp, vp, ksc, vsc)
    o_k = np.asarray(o_k, np.float32)
    o_r = np.asarray(o_r, np.float32)
    denom = max(float(np.abs(o_r).max()), 1e-9)
    rel = float(np.abs(o_k - o_r).max()) / denom
    ok = rel < 5e-2  # quantized pool: dequant arithmetic differs in width
    print(f"[{'ok' if ok else 'FAIL'}] {name}: rel_fwd={rel:.2e}")
    return ok


def _tree_paged_case(
    name, b, n, nkv, d, nb, bs, w, kv_limit, num_splits, seed, t,
    kv_dtype=None, quant_mxu=False,
):
    """Packed-tree verify (docs/serving.md "Tree speculation"): the
    ancestor-masked kernel vs the dense block-table gather oracle.

    Each lane carries its own random packed topology; the kernel gets the
    per-lane int32 ancestor bitmasks (``tree_bits``), the oracle masks
    row-by-row from the same ancestor sets: query node ``ti`` sees
    committed history (``< position``) plus exactly its root path among
    the packed rows. ``kv_dtype`` adds the quantized-pool variant
    (in-kernel dequant, optional ``quant_mxu`` int8/fp8 q·k dot) in the
    same 5e-2 band as the linear quant cases.
    """
    from neuronx_distributed_llama3_2_tpu.inference.speculative import (
        tree_topology,
    )
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode,
    )

    ks = jax.random.split(jax.random.key(seed), 3)
    q = (jax.random.normal(ks[0], (b, t, n, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    kf = jax.random.normal(ks[1], (nb, bs, nkv, d), jnp.float32) * 0.5
    vf = jax.random.normal(ks[2], (nb, bs, nkv, d), jnp.float32) * 0.5
    quant_kw = {}
    if kv_dtype is None:
        kp, vp = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
    else:
        from neuronx_distributed_llama3_2_tpu.quantization import (
            kv_cache_jax_dtype,
            kv_dequantize,
            kv_quantize,
        )

        qdtype = kv_cache_jax_dtype(kv_dtype)
        kp, ksc = kv_quantize(kf, qdtype)
        vp, vsc = kv_quantize(vf, qdtype)
        quant_kw = dict(k_scale=ksc, v_scale=vsc, quant_mxu=quant_mxu)
    rng = np.random.default_rng(seed)
    nblk = -(-kv_limit // bs)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, w), np.int32)
    for i in range(b):
        tables[i, :nblk] = perm[i * nblk:(i + 1) * nblk]
    tables = jnp.asarray(tables)
    positions = jnp.asarray(
        rng.integers(0, kv_limit - t + 1, size=(b,)), jnp.int32
    ).at[0].set(kv_limit - t)
    # per-lane random packed topology (parents[j] < j); lane 0 pinned to
    # a chain so the block-causal special case is always covered
    parents = np.zeros((b, t), np.int32)
    for j in range(1, t):
        parents[:, j] = rng.integers(0, j, size=b)
    parents[0] = np.maximum(np.arange(t) - 1, 0)
    anc = np.asarray(tree_topology(parents)[1])          # (b, t, t) bool
    tree_bits = jnp.asarray(
        (anc.astype(np.int64) << np.arange(t)[None, None, :]).sum(-1)
        .astype(np.int32)
    )

    def ref(q, kp, vp):
        if kv_dtype is not None:
            kp = kv_dequantize(kp, quant_kw["k_scale"], jnp.bfloat16)
            vp = kv_dequantize(vp, quant_kw["v_scale"], jnp.bfloat16)
        g = n // nkv
        jlog = jnp.arange(kv_limit)
        phys = tables[:, jlog // bs] * bs + (jlog % bs)
        kg = kp.reshape(nb * bs, nkv, d)[phys]
        vg = vp.reshape(nb * bs, nkv, d)[phys]
        qg = q.reshape(b, t, nkv, g, d).astype(jnp.float32)
        logits = jnp.einsum("bthgd,blhd->bthgl", qg, kg.astype(jnp.float32))
        logits = logits / jnp.sqrt(jnp.float32(d))
        # committed history, plus the query node's ancestor set among the
        # packed rows position..position+t-1
        u = jlog[None, None, :] - positions[:, None, None]   # (b, 1, L)
        hist = u < 0
        vis = (u >= 0) & (u < t) & jnp.take_along_axis(
            jnp.asarray(anc), jnp.clip(u, 0, t - 1).repeat(t, axis=1),
            axis=2,
        )
        mask = (hist | vis)[:, :, None, None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bthgl,blhd->bthgd", p, vg.astype(jnp.float32))
        return o.reshape(b, t, n, d)

    o_k = jax.jit(
        lambda q, kp, vp: paged_flash_decode(
            q, kp, vp, tables, positions,
            kv_limit=kv_limit, num_splits=num_splits,
            tree_bits=tree_bits, **quant_kw,
        )
    )(q, kp, vp)
    o_r = jax.jit(ref)(q, kp, vp)
    o_k = np.asarray(o_k, np.float32)
    o_r = np.asarray(o_r, np.float32)
    denom = max(float(np.abs(o_r).max()), 1e-9)
    rel = float(np.abs(o_k - o_r).max()) / denom
    tol = 3e-2 if kv_dtype is None else 5e-2
    ok = rel < tol
    print(f"[{'ok' if ok else 'FAIL'}] {name}: rel_fwd={rel:.2e}")
    return ok


def _sampled_decode_case(name, b, v, t, seed):
    """Fused on-device sampling parity: jitted ``sample_lanes`` over
    (B, V) decode (t=1) or (B, T, V) verify logits vs the host
    ``sample`` path called row by row with the identically folded key.
    Rows mix the greedy sentinel, plain temperature, top-k, top-p and the
    combined filter — every row must match the host draw EXACTLY (same
    fold_in key, same fp32 filter arithmetic), which is the device-side
    half of the engine's greedy-token-identity contract."""
    from neuronx_distributed_llama3_2_tpu.inference.sampling import (
        GREEDY_TEMPERATURE,
        SamplingConfig,
        sample,
        sample_lanes,
    )

    ks = jax.random.split(jax.random.key(seed), 2)
    shape = (b, v) if t == 1 else (b, t, v)
    logits = jax.random.normal(ks[0], shape, jnp.float32) * 3.0
    rng_data = jax.random.key_data(
        jax.random.split(ks[1], b)
    ).astype(jnp.uint32)
    rng = np.random.default_rng(seed)
    positions = jnp.asarray(rng.integers(0, 512, size=(b,)), jnp.int32)
    index = positions if t == 1 else positions[:, None] + jnp.arange(t)
    # per-lane params cycle through the sampling modes
    modes = [
        (GREEDY_TEMPERATURE, 0, 1.0),   # greedy sentinel -> exact argmax
        (0.7, 0, 1.0),                  # temperature only
        (1.3, 8, 1.0),                  # top-k
        (0.9, 0, 0.8),                  # top-p
        (1.1, 16, 0.9),                 # combined
    ]
    rows = [modes[i % len(modes)] for i in range(b)]
    temps = jnp.asarray([r[0] for r in rows], jnp.float32)
    topks = jnp.asarray([r[1] for r in rows], jnp.int32)
    topps = jnp.asarray([r[2] for r in rows], jnp.float32)

    got = np.asarray(jax.jit(sample_lanes)(
        logits, rng_data, index, temps, topks, topps
    ))
    want = np.zeros(shape[:-1], np.int32)
    lrows = np.asarray(logits).reshape(b, t if t > 1 else 1, v)
    idx = np.asarray(jnp.broadcast_to(index, got.shape)).reshape(b, -1)
    for i in range(b):
        temp, tk, tp = rows[i]
        base = jax.random.wrap_key_data(rng_data[i])
        for j in range(lrows.shape[1]):
            key = jax.random.fold_in(base, int(idx[i, j]))
            if temp <= 0:
                tok = int(np.argmax(lrows[i, j]))
            else:
                cfg = SamplingConfig(
                    greedy=False, temperature=temp, top_k=tk, top_p=tp
                )
                tok = int(sample(jnp.asarray(lrows[i, j]), key, cfg))
            if t == 1:
                want[i] = tok
            else:
                want[i, j] = tok
    ok = bool(np.array_equal(got, want))
    print(f"[{'ok' if ok else 'FAIL'}] {name}: "
          f"exact={'yes' if ok else 'NO'} rows={b} t={t}")
    return ok


def _sharded_paged_case(
    name, b, n, nkv, d, nb, bs, w, kv_limit, num_splits, seed, t=1, tp=2
):
    """tp-sharded paged decode (shard_map-wrapped kernel) vs the single-chip
    kernel on the same inputs.

    Exercises the real multi-chip layout of docs/serving.md "Multi-chip
    serving": q and the K/V pool head-sharded over a pure-tp mesh, block
    tables + positions replicated, each rank running the identical kernel
    on its NKV/tp head slice. The reference is the *unsharded* kernel (its
    own parity vs the dense gather is asserted by the paged-* cases above),
    so this case isolates exactly the shard_map wrapping. Forward-only,
    bf16, like serving decode. Skips (ok) below ``tp`` devices.
    """
    from neuronx_distributed_llama3_2_tpu.kernels.paged_attention_pallas import (
        paged_flash_decode,
        paged_flash_decode_tp,
    )
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        destroy_model_parallel,
        initialize_model_parallel,
    )

    if len(jax.devices()) < tp:
        print(f"[skip] {name}: needs {tp} devices, have {len(jax.devices())}")
        return None

    ks = jax.random.split(jax.random.key(seed), 3)
    qshape = (b, n, d) if t == 1 else (b, t, n, d)
    q = (jax.random.normal(ks[0], qshape, jnp.float32) * 0.5).astype(jnp.bfloat16)
    kp = (jax.random.normal(ks[1], (nb, bs, nkv, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    vp = (jax.random.normal(ks[2], (nb, bs, nkv, d), jnp.float32) * 0.5).astype(jnp.bfloat16)
    rng = np.random.default_rng(seed)
    nblk = -(-kv_limit // bs)
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, w), np.int32)
    for i in range(b):
        tables[i, :nblk] = perm[i * nblk:(i + 1) * nblk]
    tables = jnp.asarray(tables)
    positions = jnp.asarray(
        rng.integers(0, kv_limit - t + 1, size=(b,)), jnp.int32
    ).at[0].set(kv_limit - t)

    o_ref = jax.jit(
        lambda q, kp, vp: paged_flash_decode(
            q, kp, vp, tables, positions,
            kv_limit=kv_limit, num_splits=num_splits,
        )
    )(q, kp, vp)
    o_ref = np.asarray(o_ref, np.float32)
    st = initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=jax.devices()[:tp]
    )
    try:
        o_tp = jax.jit(
            lambda q, kp, vp: paged_flash_decode_tp(
                q, kp, vp, tables, positions, mesh=st.mesh,
                kv_limit=kv_limit, num_splits=num_splits,
            )
        )(q, kp, vp)
        o_tp = np.asarray(o_tp, np.float32)
    finally:
        destroy_model_parallel()
    denom = max(float(np.abs(o_ref).max()), 1e-9)
    rel = float(np.abs(o_tp - o_ref).max()) / denom
    # same kernel body on disjoint head slices: only layout/compilation
    # differences separate the two, so the tolerance is tight
    ok = rel < 1e-3
    print(f"[{'ok' if ok else 'FAIL'}] {name}: rel_tp={rel:.2e}")
    return ok


RESULTS: dict = {}
ONLY: list = []  # name substrings from the command line; empty = every case


def _run(case_fn, *args, **kwargs) -> bool:
    """One case: a compiler refusal or a crash is that case's FAIL (with the
    compiler's words), and the gate goes on to the next. A case that needs
    more devices than the host has is recorded as skipped, never as ok."""
    name = args[0]
    if ONLY and not any(s in name for s in ONLY):
        return True
    try:
        ok = case_fn(*args, **kwargs)
        if ok is None:
            RESULTS[name], ok = "skipped: too few devices", True
        else:
            RESULTS[name] = "ok" if ok else "numerics"
    except Exception as e:  # report and keep going: every case must answer
        ok = False
        RESULTS[name] = f"{type(e).__name__}: {e}"[:1500]
        print(f"[FAIL] {name}: {RESULTS[name]}")
    return ok


def main() -> int:
    import json

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache,
        require_tpu,
    )

    print(f"device: {require_tpu()}")
    enable_compile_cache()
    ONLY[:] = sys.argv[1:]
    cases = [
        ("causal-gqa", 2, 1024, 8, 4, 64, True, False, 0, 512, 512),
        ("noncausal", 2, 512, 4, 4, 64, False, False, 1, 256, 256),
        ("segment-ids", 2, 512, 4, 4, 64, True, True, 2, 256, 256),
        ("odd-seq", 1, 640, 8, 8, 64, True, False, 3, 256, 256),
        ("big-tiles", 1, 2048, 8, 4, 64, True, False, 4, 1024, 1024),
        # Llama-3.2-1B heads at the bench's sequence and tiles
        ("1b-bench-tiles", 2, 2048, 32, 8, 64, True, False, 5, 1024, 1024),
        ("1b-segment-ids", 2, 2048, 32, 8, 64, True, True, 6, 512, 512),
    ]
    ok = True
    for c in cases:
        ok &= _run(_case, *c)
    #          name            b  n  nkv d   nb  bs  w  L    splits seed  t
    paged_cases = [
        ("paged-gqa",          4, 8, 2, 64, 33, 16, 8, 128, 4, 10),
        ("paged-mha",          2, 4, 4, 64, 17, 16, 4, 64,  2, 11),
        ("paged-ragged-limit", 3, 8, 2, 64, 33, 16, 8, 100, 4, 12),
        # multi-token verify queries (speculative decoding)
        ("paged-verify-t2",    4, 8, 2, 64, 33, 16, 8, 128, 4, 13, 2),
        ("paged-verify-t4",    3, 8, 2, 64, 33, 16, 8, 100, 2, 14, 4),
        ("paged-verify-t8",    2, 4, 4, 64, 17, 16, 4, 64,  1, 15, 8),
        # Llama-3.2-1B: 32/8 heads of 64, 8 lanes, a 2,048-row context
        ("paged-1b-t1",   8, 32, 8, 64, 1025, 16, 128, 2048, 4, 16),
        ("paged-1b-t4",   8, 32, 8, 64, 1025, 16, 128, 2048, 4, 17, 4),
        ("paged-1b-t8",   8, 32, 8, 64, 1025, 16, 128, 2000, 4, 18, 8),
    ]
    for c in paged_cases:
        ok &= _run(_paged_case, *c)
    # quantized pool (PagedConfig.kv_cache_dtype): in-kernel dequant vs
    # dequant-outside gather reference, int8 + both fp8s, t in {1,2,4,8}
    #            name                 b  n  nkv d   nb  bs  w  L    spl sd  t
    quant_cases = [
        ("quant-paged-int8-t1", 4, 8, 2, 64, 33, 16, 8, 128, 4, 30, 1, "int8"),
        ("quant-paged-int8-t2", 4, 8, 2, 64, 33, 16, 8, 128, 4, 31, 2, "int8"),
        ("quant-paged-int8-t4", 3, 8, 2, 64, 33, 16, 8, 100, 2, 32, 4, "int8"),
        ("quant-paged-int8-t8", 2, 4, 4, 64, 17, 16, 4, 64,  1, 33, 8, "int8"),
        ("quant-paged-fp8e4m3-t1", 4, 8, 2, 64, 33, 16, 8, 128, 4, 34, 1, "fp8_e4m3"),
        ("quant-paged-fp8e4m3-t8", 2, 4, 4, 64, 17, 16, 4, 64,  1, 35, 8, "fp8_e4m3"),
        ("quant-paged-fp8e5m2-t1", 4, 8, 2, 64, 33, 16, 8, 128, 4, 36, 1, "fp8_e5m2"),
        ("quant-paged-fp8e5m2-t4", 3, 8, 2, 64, 33, 16, 8, 100, 2, 37, 4, "fp8_e5m2"),
        ("quant-paged-1b-int8-t1", 8, 32, 8, 64, 1025, 16, 128, 2048, 4, 38, 1, "int8"),
        ("quant-paged-1b-fp8e4m3-t4", 8, 32, 8, 64, 1025, 16, 128, 2048, 4, 39, 4, "fp8_e4m3"),
    ]
    for c in quant_cases:
        ok &= _run(_quant_paged_case, *c[:11], t=c[11], kv_dtype=c[12])
    # MXU-native low-precision dot (PagedConfig.quant_mxu): the q·k dot
    # stays int8 (int32 accumulate) / fp8, scales applied to the fp32
    # score matrix — same dequant-outside reference, same 5% band
    mxu_cases = [
        ("quant-mxu-paged-int8-t1", 4, 8, 2, 64, 33, 16, 8, 128, 4, 40, 1, "int8"),
        ("quant-mxu-paged-int8-t4", 3, 8, 2, 64, 33, 16, 8, 100, 2, 41, 4, "int8"),
        ("quant-mxu-paged-fp8e4m3-t1", 4, 8, 2, 64, 33, 16, 8, 128, 4, 42, 1, "fp8_e4m3"),
        ("quant-mxu-paged-fp8e5m2-t4", 3, 8, 2, 64, 33, 16, 8, 100, 2, 43, 4, "fp8_e5m2"),
        ("quant-mxu-paged-1b-int8-t1", 8, 32, 8, 64, 1025, 16, 128, 2048, 4, 44, 1, "int8"),
        ("quant-mxu-paged-1b-fp8e4m3-t1", 8, 32, 8, 64, 1025, 16, 128, 2048, 4, 45, 1, "fp8_e4m3"),
    ]
    for c in mxu_cases:
        ok &= _run(
            _quant_paged_case, *c[:11], t=c[11], kv_dtype=c[12], quant_mxu=True
        )
    # packed-tree verify (PagedConfig.spec_tree): ancestor-bitmask mask
    # operand vs the dense-gather oracle, per-lane random topologies,
    # fp + quantized pool + the int8 MXU dot
    #           name               b  n  nkv d   nb  bs  w  L    spl sd  t
    tree_cases = [
        ("tree-verify-t4",        3, 8, 2, 64, 33, 16, 8, 100, 2, 60, 4),
        ("tree-verify-t8",        2, 4, 4, 64, 17, 16, 4, 64,  1, 61, 8),
        ("tree-verify-int8-t4",   3, 8, 2, 64, 33, 16, 8, 100, 2, 62, 4,
         "int8", False),
        ("tree-verify-mxu-int8-t8", 2, 4, 4, 64, 17, 16, 4, 64, 1, 63, 8,
         "int8", True),
        ("tree-verify-1b-t8",     8, 32, 8, 64, 1025, 16, 128, 2000, 4, 64, 8),
    ]
    for c in tree_cases:
        ok &= _run(_tree_paged_case, *c)
    # fused on-device sampling (PagedConfig.on_device_sampling): exact
    # host-draw parity for decode- and verify-shaped logits
    sampled_cases = [
        ("sampled-decode-t1", 5, 256, 1, 50),
        ("sampled-decode-t4", 5, 256, 4, 51),
    ]
    for c in sampled_cases:
        ok &= _run(_sampled_decode_case, *c)
    # tp=2 head-sharded shard_map wrapping of the same kernel (serving's
    # multi-chip layout); nkv/n both divide tp in every case by design
    #                 name                  b  n  nkv d   nb  bs  w  L    spl sd  t
    sharded_cases = [
        ("sharded-paged-decode",    4, 8, 2, 64, 33, 16, 8, 128, 4, 20),
        ("sharded-paged-verify-t2", 4, 8, 2, 64, 33, 16, 8, 128, 4, 21, 2),
        ("sharded-paged-verify-t4", 3, 8, 2, 64, 33, 16, 8, 100, 2, 22, 4),
        ("sharded-paged-verify-t8", 2, 4, 4, 64, 17, 16, 4, 64,  1, 23, 8),
        ("sharded-paged-1b-tp4",    8, 32, 8, 64, 1025, 16, 128, 2048, 4, 24, 1, 4),
    ]
    for c in sharded_cases:
        ok &= _run(_sharded_paged_case, *c)
    out_dir = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_gate.json"), "w") as fh:
        json.dump({"ok": bool(ok), "cases": RESULTS}, fh, indent=1)
    print("tpu_kernel_gate:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
