"""Quantization policies feeding the DPA datapath.

The hardware multiplies raw low-precision operands; software decides how
tensors are scaled into those formats.  We implement the standard
deployment recipe: absmax scaling at per-tensor / per-channel / per-block
granularity, saturating RNE cast into the target format (native XLA
convert for fp16/bf16/fp8/fp4 via ml_dtypes), and straight-through
estimation for training.

All casts preserve the DPA contract: the *product/accumulate* dtype is
always the policy's accumulate format (fp32 by default) — low precision
only ever touches the multiplier inputs, exactly as in the paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .formats import get_format


# FloatFormat -> native jnp storage dtype
_JNP_DTYPE = {
    "fp32": jnp.float32,
    "fp16": jnp.float16,
    "bf16": jnp.bfloat16,
    "fp8_e4m3": jnp.float8_e4m3fn,
    "fp8_e5m2": jnp.float8_e5m2,
    "fp4_e2m1": jnp.float4_e2m1fn,
}


def jnp_dtype(fmt) -> jnp.dtype:
    """Storage dtype for fmt."""
    return jnp.dtype(_JNP_DTYPE[get_format(fmt).name])


# -----------------------------------------------------------------------------
# FP4-E2M1 arithmetic encode/decode (TPU-friendly: no gathers, pure jnp,
# usable inside Pallas kernels).  Shared by the quantizers, the matmul
# kernels, and the emulated cast path below.
# -----------------------------------------------------------------------------

def encode_fp4(x):
    """f32 values (pre-clipped to [-6, 6]) -> uint8 E2M1 codes, RNE.

    The representable magnitudes are 0, .5, 1, 1.5, 2, 3, 4, 6; rounding is
    via midpoint thresholds with ties-to-even baked into the <=/< choices."""
    s = (x < 0).astype(jnp.uint8)
    a = jnp.abs(x)
    code = jnp.zeros(x.shape, jnp.uint8)
    mags = [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0]
    for i in range(1, 8):
        mid = 0.5 * (mags[i - 1] + mags[i])
        even_low = (i - 1) % 2 == 0
        take = (a > mid) if even_low else (a >= mid)
        code = jnp.where(take, jnp.uint8(i), code)
    return code | (s << 3)


def decode_fp4(codes):
    """uint8 E2M1 codes -> exact f32 values.

    value = (-1)^s * (e==0 ? m/2 : (1+m/2) * 2^(e-1)) — arithmetic decode,
    no lookup table."""
    c = codes.astype(jnp.int32)
    s = (c >> 3) & 1
    e = (c >> 1) & 3
    m = (c & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m,
                    (1.0 + 0.5 * m) * jnp.exp2((e - 1).astype(jnp.float32)))
    return jnp.where(s == 1, -mag, mag)


def absmax_block_scale(xb, target: float, *, axis=1):
    """The kernels' VMEM scale recipe: absmax/target with the eps and
    f32-normal floors — `compute_scale` restated for a resident block with
    a static Python-float target (Pallas-safe, shared by the quantize and
    fused-matmul kernels and their references so their bit contract cannot
    drift).  The division is written as a multiply by the reciprocal:
    XLA rewrites a division by a constant that way under jit, so the
    explicit form gives the same bits jitted, eager, or in a kernel."""
    amax = jnp.max(jnp.abs(xb), axis=axis, keepdims=True)
    return jnp.maximum(jnp.maximum(amax, 1e-30) * (1.0 / target),
                       2.0 ** -126)


def round_to_format(x, fmt):
    """Round f32 `x` (already inside fmt's finite range) to the nearest
    value of fmt's grid, ties to even, in f32 arithmetic; -> f32.

    The value an ``x.astype(fmt).astype(f32)`` round trip gives, bit for
    bit — but XLA on a TPU may drop such a convert pair as excess
    precision, and then nothing is rounded at all.  Scaling by a power
    of two and rounding to an integer cannot be dropped, and lowers the
    same in XLA and in Mosaic.  For formats whose grid spacing stays in
    the f32 normal range (fp8 e4m3/e5m2, fp16); others cast."""
    fmt = get_format(fmt)
    if fmt.emin - fmt.man_bits < -126:          # bf16, fp32
        return x.astype(jnp_dtype(fmt)).astype(jnp.float32)
    exp = (jax.lax.bitcast_convert_type(x, jnp.int32) >> 23) & 0xFF
    # spacing of fmt's grid around x (its subnormals share emin's) and
    # its inverse, built as exact powers of two from exponent fields
    e = jnp.maximum(exp - 127, fmt.emin) - fmt.man_bits
    step = jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)
    inv = jax.lax.bitcast_convert_type((127 - e) << 23, jnp.float32)
    return jnp.round(x * inv) * step


def quant_rows_grid(x, fmt, *, axis=-1):
    """Absmax-quantize along `axis` onto fmt's value grid.

    -> (values-on-the-grid f32, f32 scale with `axis` kept) such that
    grid * scale is the dequantized tensor.  This is the operand recipe the
    DPA attention path shares between the Pallas kernels, the jnp fallback,
    the quantized KV cache, and the `kernels.ref` oracles — one definition
    so their bit contract cannot drift.  fmt "fp32" is the identity
    (grid = x, scale = 1): the disabled-path contract of the attention ops.
    """
    fmt = get_format(fmt)
    xf = x.astype(jnp.float32)
    if fmt.name == "fp32":
        return xf, jnp.ones(jnp.max(xf, axis=axis, keepdims=True).shape,
                            jnp.float32)
    target = fmt.quant_target
    scale = absmax_block_scale(xf, target, axis=axis)
    y = jnp.clip(xf / scale, -target, target)
    if fmt.name == "fp4_e2m1":
        grid = decode_fp4(encode_fp4(y))
    else:
        grid = round_to_format(y, fmt)
    return grid, scale


def compute_scale(x, fmt, *, axis=None, keepdims=True, eps=1e-30):
    """absmax / max_finite scale so that x/scale fits fmt's range.

    Clamped to the fp32 normal range so wide-range target formats (bf16,
    whose max_finite ~ 3.4e38) cannot underflow the scale to zero."""
    fmt = get_format(fmt)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims)
    scale = jnp.maximum(amax, eps).astype(jnp.float32) / fmt.quant_target
    return jnp.maximum(scale, jnp.float32(2.0) ** -126)


def cast_to(x, fmt):
    """Saturating RNE cast into fmt's native dtype (no scaling).  Use
    `encode_fp4` directly when the uint8 E2M1 code representation is
    wanted."""
    fmt = get_format(fmt)
    xf = x.astype(jnp.float32)
    xf = jnp.clip(xf, -fmt.max_finite, fmt.max_finite)
    if fmt.name == "fp4_e2m1":
        return xf.astype(_JNP_DTYPE[fmt.name])
    return round_to_format(xf, fmt).astype(_JNP_DTYPE[fmt.name])


def quantize(x, fmt, *, axis=None):
    """-> (q: fmt dtype, scale: f32 broadcastable). axis=None: per-tensor;
    int/tuple: reduce over that axis (per-channel over the others)."""
    fmt = get_format(fmt)
    scale = compute_scale(x, fmt, axis=axis)
    q = cast_to(x.astype(jnp.float32) / scale, fmt)
    return q, scale


def quantize_blockwise(x, fmt, *, axis, block):
    """Per-block scales along `axis` (block must divide the dim).  Returns
    (q, scale) with scale shaped like x but with `axis` reduced per block
    and kept broadcastable after `dequantize_blockwise`."""
    fmt = get_format(fmt)
    axis = axis % x.ndim
    d = x.shape[axis]
    if d % block:
        raise ValueError(f"block {block} does not divide dim {d}")
    shp = x.shape[:axis] + (d // block, block) + x.shape[axis + 1:]
    xb = x.reshape(shp)
    scale = compute_scale(xb, fmt, axis=axis + 1)
    q = cast_to(xb.astype(jnp.float32) / scale, fmt)
    return q.reshape(x.shape), scale  # scale: (..., d//block, 1, ...)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def dequantize_blockwise(q, scale, *, axis, block):
    axis = axis % q.ndim
    d = q.shape[axis]
    shp = q.shape[:axis] + (d // block, block) + q.shape[axis + 1:]
    return (q.reshape(shp).astype(jnp.float32) * scale).reshape(q.shape)


def quant_dequant(x, fmt, *, axis=None, block=None):
    fmt = get_format(fmt)
    if fmt.name == "fp32":
        return x
    if block is not None and axis is not None:
        q, s = quantize_blockwise(x, fmt, axis=axis, block=block)
        return dequantize_blockwise(q, s, axis=axis, block=block).astype(x.dtype)
    q, s = quantize(x, fmt, axis=axis)
    return dequantize(q, s).astype(x.dtype)


def fake_quant(x, fmt, *, axis=None, block=None):
    """Straight-through-estimated quantization: forward = quant-dequant,
    backward = identity.  This is how DPA formats enter the training graph
    (weights/activations are *represented* low precision; gradients flow in
    the accumulate format — the paper's stability contract)."""
    fmt = get_format(fmt)
    if fmt.name == "fp32":
        return x
    qdq = quant_dequant(x, fmt, axis=axis, block=block)
    return x + jax.lax.stop_gradient(qdq - x)
