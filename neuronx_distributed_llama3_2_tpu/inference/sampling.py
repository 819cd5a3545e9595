"""On-device sampling.

TPU-native replacement for the reference's ``Sampler``
(``src/neuronx_distributed/utils/sampling.py:6``). The reference builds its
on-device greedy argmax / top-k multinomial out of custom Neuron
TopK/Softmax/Argmax calls; on TPU the same transform is plain jax ops
(``lax.top_k``, ``jax.random.categorical``) with no custom calls, so this
module carries two entry points instead of a call registry:

- :func:`sample` — the host-loop path (``inference/engine.py``): a static
  :class:`SamplingConfig` is compiled into the program and the PRNG key is
  a per-step host argument.
- :func:`sample_lanes` — the fused serving path
  (``PagedConfig.on_device_sampling``): per-lane ``(temperature, top_k,
  top_p)`` arrays and per-lane PRNG key *data* live device-resident next to
  the tokens/positions, the key for the token at sequence index ``i`` is
  ``fold_in(lane_key, i)``, and ``temperature <= 0`` is the greedy sentinel
  (exact argmax). Everything jit-fuses into the decode/verify program so
  logits never leave the device and steady-state decode uploads nothing
  (reference on_device_sampling config, examples/inference/modules/config.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Static sampling parameters (compiled into the decode program)."""

    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0       # 0 = disabled
    top_p: float = 1.0   # 1.0 = disabled

    def __post_init__(self):
        if self.temperature <= 0.0:
            raise ValueError("temperature must be > 0; use greedy=True for argmax")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")


@jax.named_scope("sample")
def sample(
    logits: jax.Array, key: jax.Array, config: SamplingConfig
) -> jax.Array:
    """Sample token ids from (..., V) logits. Returns (...,) int32."""
    if config.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / config.temperature
    if config.top_k > 0:
        k = min(config.top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if config.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the minimal prefix whose mass reaches top_p: a token is kept
        # if the cumulative mass *before* it is < top_p. The cutoff is the
        # SMALLEST kept value (the boundary token) — everything at or above
        # it survives, ties with the boundary included
        keep = (cum - probs) < config.top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1)
        logits = jnp.where(logits < cutoff[..., None], -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


#: the per-lane greedy sentinel: SamplingConfig forbids temperature <= 0,
#: so a non-positive resident temperature can only be engine-written and
#: means "exact argmax for this lane" in :func:`sample_lanes`.
GREEDY_TEMPERATURE = 0.0


def lane_keys(rng_data: jax.Array, index: jax.Array) -> jax.Array:
    """Per-sample typed PRNG keys from resident key data.

    ``rng_data (N, 2) uint32`` is raw threefry key data (the device-resident
    representation — typed key arrays cannot ride in a donated scatter);
    ``index (N,) int32`` is each sample's absolute sequence index. The token
    landing at sequence index ``i`` of a lane is ALWAYS sampled with
    ``fold_in(lane_key, i)`` — decode, prefill, chunked prefill and
    speculative verify all key by destination index, which is what makes a
    preempt-resume replay emit the identical suffix: re-admission restores
    positions, so the same indices fold the same keys.
    """
    keys = jax.random.wrap_key_data(rng_data)
    return jax.vmap(jax.random.fold_in)(keys, index.astype(jnp.int32))


@jax.named_scope("sample")
def sample_lanes(
    logits: jax.Array,        # (B, V) or (B, T, V)
    rng_data: jax.Array,      # (B, 2) uint32 per-lane key data
    index: jax.Array,         # (B,) or (B, T) int32 absolute sequence index
    temperature: jax.Array,   # (B,) f32; <= 0 = greedy sentinel (argmax)
    top_k: jax.Array,         # (B,) int32; 0 = disabled, > V clamps to V
    top_p: jax.Array,         # (B,) f32; 1.0 = disabled
) -> jax.Array:
    """Per-lane fused sampling over (B, V) decode or (B, T, V) verify
    logits. Returns int32 tokens of shape ``logits.shape[:-1]``.

    The transform mirrors :func:`sample` exactly — same top-k value
    threshold (ties at the k-th value survive), same minimal-prefix top-p
    rule with the boundary token included, same fp32 math from fp16/bf16
    logits — but every parameter is a per-lane array and the key is derived
    from resident key data via :func:`lane_keys`. Lanes at the greedy
    sentinel (``temperature <= 0``) return the exact argmax, so one
    compiled program serves mixed greedy/sampled traffic token-identically
    to the dedicated greedy program.
    """
    shape = logits.shape[:-1]
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32).reshape(-1, v)            # (N, V)
    if logits.ndim == 3:
        t = logits.shape[1]
        rep = lambda a: jnp.repeat(a, t, axis=0)              # noqa: E731
        rng_data, temperature, top_k, top_p = (
            rep(rng_data), rep(temperature), rep(top_k), rep(top_p)
        )
    idx = jnp.broadcast_to(index, shape).reshape(-1)

    temp = temperature.astype(jnp.float32)
    safe_temp = jnp.where(temp > 0, temp, 1.0)
    x = lf / safe_temp[:, None]

    # one descending sort serves both filters (the host path's two sorts)
    sorted_x = jnp.sort(x, axis=-1)[:, ::-1]
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_x, (k_eff - 1)[:, None], axis=-1)  # (N,1)
    # value threshold (not rank mask): entries tied with the k-th value
    # survive, matching sample()'s `logits < kth` rule
    sorted_masked = jnp.where(sorted_x < kth, -jnp.inf, sorted_x)
    probs = jax.nn.softmax(sorted_masked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # the cutoff is the SMALLEST kept value (the boundary token): ties
    # with the boundary survive, and top_p=1.0 keeps every positive-prob
    # entry — a true no-op on top of the top-k mask
    keep = (cum - probs) < top_p.astype(jnp.float32)[:, None]
    cutoff = jnp.min(jnp.where(keep, sorted_masked, jnp.inf), axis=-1)
    xm = jnp.where(x < kth, -jnp.inf, x)
    xm = jnp.where(x < cutoff[:, None], -jnp.inf, xm)

    keys = lane_keys(rng_data, idx)
    sampled = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg)
    )(keys, xm).astype(jnp.int32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy).reshape(shape)
