"""Byte counts of a Mamba-1 state-space layer and of a whole decode step of a
stack that mixes such layers with attention, from shapes, kept with the
benchmark (``arith.py``'s conventions). The program's code is
``models/jamba.py``: what a sequence leaves behind a layer is ``h`` (d_state x
d_inner, float32) and the convolution's tail (d_conv - 1 rows of d_inner,
bfloat16); ``pdecode`` reads and rewrites both once a live lane a layer
(``attn/ssm/step``), a prefill chunk runs its rows one after another from the
carried ``h`` (``attn/ssm/scan``) after the convolution (``attn/ssm/conv``).

One rule keeps a roofline share built from these under 100 %: every count is
of the bytes the work *needs* — a live lane's state once in and once out, a
real row's operands once — over a device time in which the program moved at
least as many (the pass also moves idle lanes' null slots and a bucket's
padding rows, which are not counted)."""

from __future__ import annotations

STATE_ITEMSIZE = 4      # float32 h, as the configuration states
TAIL_ITEMSIZE = 2       # the convolution's tail in bfloat16
ACT_ITEMSIZE = 2        # u, c as the program holds them
F32 = 4                 # delta, B, C and y as the program holds them


def state_bytes(d_inner: int, d_state: int, d_conv: int) -> int:
    """Bytes one sequence leaves behind one Mamba layer: h and the tail."""
    return d_inner * (d_state * STATE_ITEMSIZE + (d_conv - 1) * TAIL_ITEMSIZE)


def decode_needed_state_bytes(lanes: float, layers: int, d_inner: int, d_state: int,
                              d_conv: int) -> float:
    """State bytes a decode step over ``lanes`` live lanes has to move: each
    lane's h and tail read once and written once in every Mamba layer."""
    return float(lanes) * layers * 2 * state_bytes(d_inner, d_state, d_conv)


def mamba_layer_params(hidden: int, d_inner: int, d_state: int, d_conv: int, dt_rank: int) -> int:
    """Parameters of one Mamba mixer: in_proj, the convolution and its bias,
    x_proj, dt_proj and its bias, A_log, D, the three inner norms, out_proj."""
    return (hidden * 2 * d_inner + d_inner * d_conv + d_inner
            + d_inner * (dt_rank + 2 * d_state) + dt_rank * d_inner + d_inner
            + d_inner * d_state + d_inner + dt_rank + 2 * d_state + d_inner * hidden)


def decode_weight_bytes(hidden: int, heads: int, kv_heads: int, head_dim: int, intermediate: int,
                        vocab: int, mamba_layers: int, attention_layers: int, d_inner: int,
                        d_state: int, d_conv: int, dt_rank: int, itemsize: int = 2) -> float:
    """Weight bytes a decode step reads once for all lanes: every mixer, every
    SwiGLU, the two norms a layer, and the tied embedding as the head (as an
    embedding it is a gather of one row a lane)."""
    attention = hidden * heads * head_dim * 2 + hidden * kv_heads * head_dim * 2
    swiglu = 3 * hidden * intermediate + 2 * hidden
    mamba = mamba_layer_params(hidden, d_inner, d_state, d_conv, dt_rank)
    layers = mamba_layers * (mamba + swiglu) + attention_layers * (attention + swiglu)
    return float(itemsize) * (layers + hidden * vocab)


def prefill_row_bytes(d_inner: int, d_state: int) -> int:
    """Bytes one real row needs under ``conv`` + ``scan`` in one Mamba layer,
    each operand in the dtype the program holds it: u in and c out of the
    convolution; c, delta, B and C in and y out of the scan."""
    conv = d_inner * 2 * ACT_ITEMSIZE
    scan = d_inner * (ACT_ITEMSIZE + F32 + F32) + 2 * d_state * F32
    return conv + scan


def prefill_needed_bytes(real_rows: float, calls: int, layers: int, d_inner: int, d_state: int,
                         d_conv: int) -> float:
    """Bytes the traced prefill calls need under ``conv`` + ``scan``:
    ``real_rows`` rows in all (a bucket's padding left out) a layer, and a
    lane's h and tail once in and once out a call a layer."""
    return layers * (real_rows * prefill_row_bytes(d_inner, d_state)
                     + calls * 2 * state_bytes(d_inner, d_state, d_conv))
