"""Weight-only int8/fp8 quantization over parameter pytrees.

TPU-native replacement for the reference's ``quantization/`` package:
``QuantizationType`` / qconfig dicts (quantization_config.py:19-56),
``quantize.convert()`` module-swapping (quantize.py:13), per-tensor /
per-channel scale extraction (quantization_utils.py:11-51), and the
``direct_cast_quantize`` / scale math used by the quantized layers
(quantization_layers.py:98-211).

The torch version swaps ``nn.Module`` subclasses and re-registers int8
weight tensors plus scale buffers. Functionally redesigned for JAX: a
quantized weight is a :class:`QuantizedTensor` pytree node ``(qvalue, scale)``
living *in the parameter tree* where the float kernel used to be. Consumers
dequantize with ``qt.dequantize(dtype)`` — a multiply that XLA fuses into the
consuming matmul, so the HBM working set is the int8 bytes (the entire point
on a bandwidth-bound chip) while the MXU still sees bf16.

Scale semantics match the reference:
- per_tensor_symmetric: one scale, ``absmax / qmax`` (observer.py MinMax).
- per_channel_symmetric: scale per output channel, broadcast-shaped
  (quantization_utils.py:24-44 keeps scales viewed broadcastable; we do the
  same so ``dequantize`` is a plain ``qvalue * scale``).

Sharding: the scale spec is the kernel spec restricted to the channel axis,
so a tp-sharded (None, 'tp') kernel gets a (1, 'tp')-sharded scale and
dequant needs no collective (the reference shards scales the same way,
quantization_layers.py:165-211).
"""

from __future__ import annotations

import dataclasses
import enum
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


class QuantizationType(str, enum.Enum):
    """reference quantization_config.py:19."""

    PER_TENSOR_SYMMETRIC = "per_tensor_symmetric"
    PER_CHANNEL_SYMMETRIC = "per_channel_symmetric"


#: quantized storage dtypes (reference QuantizedDtype, quantization_config.py:24
#: — int8 there; fp8 added for TPU v5+ native fp8 support).
QUANTIZED_DTYPES = {
    "int8": jnp.int8,
    "fp8_e4m3": jnp.float8_e4m3fn,
    "fp8_e5m2": jnp.float8_e5m2,
}


@dataclasses.dataclass(frozen=True)
class QuantizationConfig:
    """reference qconfig dict (quantization_config.py:27-46)."""

    quantization_type: QuantizationType = QuantizationType.PER_CHANNEL_SYMMETRIC
    quantized_dtype: str = "int8"
    # which axis of the kernel carries output channels. None = last axis.
    # (reference quantization_per_channel_axis; their weights are (out, in) so
    # axis 0 — ours are (in, out) so the default -1.)
    per_channel_axis: int = -1

    def __post_init__(self):
        if self.quantized_dtype not in QUANTIZED_DTYPES:
            raise ValueError(
                f"quantized_dtype must be one of {sorted(QUANTIZED_DTYPES)}, "
                f"got {self.quantized_dtype!r}"
            )

    @property
    def jax_dtype(self):
        return QUANTIZED_DTYPES[self.quantized_dtype]


def _qmax(dtype) -> float:
    if dtype == jnp.int8:
        return 127.0
    return float(jnp.finfo(dtype).max)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedTensor:
    """A quantized weight living in a param tree: int8/fp8 payload + scale.

    The analogue of the reference's (int8 ``weight``, ``scale`` buffer) pair
    (quantization_layers.py:116-211), packaged as one pytree node so existing
    tree-walking code (optimizer specs, checkpoints) sees a single leaf-pair.
    ``scale`` is stored broadcast-shaped against ``qvalue``
    (quantization_utils.py:24-44).
    """

    qvalue: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.qvalue.shape

    @property
    def dtype(self):
        return self.qvalue.dtype

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        """reference dequantize.direct_cast_dequantize: q * scale."""
        return (self.qvalue.astype(jnp.float32) * self.scale).astype(dtype)


def _default_reduce_axes(ndim: int, config: QuantizationConfig) -> Tuple[int, ...]:
    """Per-channel reduction = the contraction (input) axis only.

    Every kernel in this codebase is laid out (...stack dims..., in, out)
    with the contraction second-to-last; scales then vary over output
    channels AND all stack dims — per-layer for (L, in, out) stacks, and
    per-(layer, expert) for MoE (L, E, in, out) fused expert weights (the
    reference's QuantizedExpertFusedColumn/RowParallel keep per-expert
    scales the same way, quantization_layers.py:668,777). A non-default
    ``per_channel_axis`` keeps that axis plus the layer-stack axis (the
    pre-reduce-axes semantics, so axis=2 and axis=-1 agree on (L, in, out)
    stacks instead of silently dropping the per-layer scales)."""
    if config.per_channel_axis != -1:
        axis = config.per_channel_axis % ndim
        keep = {axis} | ({0} if ndim >= 3 else set())
        return tuple(i for i in range(ndim) if i not in keep)
    return (max(ndim - 2, 0),)


def quantize_array(
    w: jax.Array,
    config: QuantizationConfig = QuantizationConfig(),
    reduce_axes: Optional[Tuple[int, ...]] = None,
) -> QuantizedTensor:
    """Symmetric absmax quantization (reference observer.py MinMaxObserver /
    PerChannelAbsMaxObserver → scale = absmax/qmax; quantize = round(w/scale)).
    ``reduce_axes`` overrides which axes share a scale (per-channel mode);
    fused gate_up tensors pass their off-position contraction axis."""
    wf = w.astype(jnp.float32)
    qdt = config.jax_dtype
    qmax = _qmax(qdt)
    if config.quantization_type is QuantizationType.PER_TENSOR_SYMMETRIC:
        absmax = jnp.max(jnp.abs(wf))
        scale = jnp.maximum(absmax / qmax, 1e-12)
        scale = scale.reshape((1,) * wf.ndim)
    else:
        if reduce_axes is None:
            reduce_axes = _default_reduce_axes(wf.ndim, config)
        absmax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
        scale = jnp.maximum(absmax / qmax, 1e-12)
    q = wf / scale
    if qdt == jnp.int8:
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    else:
        q = jnp.clip(q, -qmax, qmax)
    return QuantizedTensor(q.astype(qdt), scale)


def scale_spec(
    kernel_spec: P,
    config: QuantizationConfig,
    ndim: int,
    reduce_axes: Optional[Tuple[int, ...]] = None,
) -> P:
    """PartitionSpec for a scale given its kernel's spec: keep each
    non-reduced axis's sharding, collapse reduced axes to None (scales are
    size-1 there). Per-tensor scales are replicated."""
    if config.quantization_type is QuantizationType.PER_TENSOR_SYMMETRIC:
        return P(*((None,) * ndim))
    if reduce_axes is None:
        reduce_axes = _default_reduce_axes(ndim, config)
    entries = list(kernel_spec) + [None] * (ndim - len(list(kernel_spec)))
    return P(*[None if i in reduce_axes else entries[i] for i in range(ndim)])


# ---------------------------------------------------------------------------
# pytree-level convert (reference quantize.convert, quantize.py:13)
# ---------------------------------------------------------------------------

#: kernels quantized by default: attention + MLP projection matrices,
#: including the 3D/4D fused MoE expert weights (reference
#: QuantizedExpertFusedColumnParallel/RowParallel, quantization_layers.py:
#: 668,777). Embedding/norm/bias stay float (reference default mapping
#: quantizes only the parallel linear layers, quantization_mappings.py).
DEFAULT_TARGETS = (
    r"attn/qkv/(q|k|v)_kernel$",
    r"attn/o/kernel$",
    r"mlp/gate_up$",
    r"mlp/(up|down)/kernel$",
    r"experts/gate_up$",
    r"experts/down$",
    # Mllama naming: text cross-attention and ViT attention keep separate
    # q/k/v/o linears, vision MLP is fc1/fc2 (models/mllama.py) — without
    # these the vision family silently escaped weight-only quantization
    r"(self_attn|cross_attn)/(q|k|v|o)/kernel$",
    r"mlp/fc(1|2)/kernel$",
    r"multi_modal_projector/kernel$",
)


def _match(path_key: str, patterns) -> bool:
    return any(re.search(p, path_key) for p in patterns)


def fused_reduce_axes(path: str, ndim: int) -> Optional[Tuple[int, ...]]:
    """Fused gate_up tensors (..., in, 2, out) carry their contraction axis
    third-from-last; everything else uses the (..., in, out) default."""
    if path.endswith("gate_up") and ndim >= 3:
        return (ndim - 3,)
    return None


def walk_tree(tree: Any, fn, path: str = "") -> Any:
    """Recurse dict/list/tuple pytrees applying fn(path, leaf) at leaves.
    List indices become path segments (Mllama keeps its text layers as a
    per-layer list, not a stacked array — without list recursion the whole
    family silently escaped quantization)."""
    if isinstance(tree, dict):
        return {k: walk_tree(v, fn, f"{path}/{k}" if path else k) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        out = [
            walk_tree(v, fn, f"{path}/{i}" if path else str(i))
            for i, v in enumerate(tree)
        ]
        return type(tree)(out)
    return fn(path, tree)


def quantize_params(
    params: Params,
    config: QuantizationConfig = QuantizationConfig(),
    targets: Tuple[str, ...] = DEFAULT_TARGETS,
) -> Params:
    """Quantize every kernel whose '/'-joined path matches a target regex,
    replacing the float leaf with a :class:`QuantizedTensor`. The pytree
    analogue of the reference's recursive module swap
    (quantize._convert_initialized_float_to_initialized_quantized)."""

    def visit(path, leaf):
        if isinstance(leaf, jax.Array) and leaf.ndim >= 2 and _match(path, targets):
            return quantize_array(
                leaf, config, reduce_axes=fused_reduce_axes(path, leaf.ndim)
            )
        return leaf

    return walk_tree(params, visit)


def quantize_specs(
    params: Params,
    specs: Params,
    config: QuantizationConfig = QuantizationConfig(),
    targets: Tuple[str, ...] = DEFAULT_TARGETS,
) -> Params:
    """Spec tree matching :func:`quantize_params` output: quantized leaves
    become QuantizedTensor(kernel_spec, scale_spec)."""

    flat_p: Dict[str, Any] = {}
    walk_tree(params, lambda p, l: flat_p.setdefault(p, l))

    def visit(path, spec):
        leaf = flat_p.get(path)
        if leaf is not None and getattr(leaf, "ndim", 0) >= 2 and _match(path, targets):
            return QuantizedTensor(
                spec,
                scale_spec(
                    spec, config, leaf.ndim,
                    reduce_axes=fused_reduce_axes(path, leaf.ndim),
                ),
            )
        return spec

    return walk_tree(specs, visit)


def dequantize_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """Restore a float tree: QuantizedTensor leaves → dequantized arrays.
    Under jit the dequant multiplies fuse into the consuming matmuls, so
    calling a model as ``model(dequantize_params(qparams), x)`` IS the
    quantized forward — int8 in HBM, bf16 on the MXU."""
    return jax.tree.map(
        lambda l: l.dequantize(dtype) if isinstance(l, QuantizedTensor) else l,
        params,
        is_leaf=lambda l: isinstance(l, QuantizedTensor),
    )


def quantization_error(w: jax.Array, config=QuantizationConfig()) -> jax.Array:
    """Max abs reconstruction error — used by tests and calibration reports."""
    return jnp.max(jnp.abs(quantize_array(w, config).dequantize(jnp.float32) - w))


def live_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """Per-call quantization-transparent view: dequantize QuantizedTensor
    leaves (to ``dtype``) when any are present, identity otherwise. The
    shared serving discipline — check the tree PASSED, not one captured at
    construction, so a float-constructed server handed a quantized tree
    later still dequantizes (and vice versa). Used by the text engine and
    the Mllama decoder."""
    has_q = any(
        isinstance(l, QuantizedTensor)
        for l in jax.tree.leaves(
            params, is_leaf=lambda l: isinstance(l, QuantizedTensor)
        )
    )
    return dequantize_params(params, dtype) if has_q else params
