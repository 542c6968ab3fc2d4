"""Pallas TPU kernel: fused row-wise quantization for DPA operands.

One VMEM pass computes per-row absmax, the scale, and the saturating cast
into the DPA operand format (fp8 native dtype, or uint8 E2M1 codes for
fp4).  Fusing the three stages keeps the activation tensor's HBM traffic
at 1R + (1/4..1/8)W — the software face of the paper's "preserve the
input interface bandwidth" argument.

Rows are tiled (bm, K): K stays resident so absmax is a single reduction
(activations in the model zoo have K <= 32k f32 = 128 KiB/row, well under
VMEM at bm rows per step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import get_format
from repro.core.packing import pack_fp4
from repro.core.quantize import absmax_block_scale, jnp_dtype
from repro.core.quantize import encode_fp4 as _encode_fp4
from repro.kernels.dpa_matmul import check_packed_lowering


def _quantize_kernel(x_ref, q_ref, s_ref, *, fmt: str, target: float):
    x = x_ref[...].astype(jnp.float32)
    scale = absmax_block_scale(x, target)
    y = jnp.clip(x / scale, -target, target)
    if fmt == "fp4_e2m1":
        q_ref[...] = _encode_fp4(y)
    else:
        q_ref[...] = y.astype(jnp_dtype(fmt))
    s_ref[...] = scale


def _quantize_pack_kernel(x_ref, q_ref, s_ref, *, target: float):
    """Fused absmax -> E2M1 cast -> nibble pack: one VMEM pass, packed
    bytes out.  The write side of the paper's format-width interface: the
    quantized activation leaves VMEM at 0.5 B/code instead of 1 B."""
    x = x_ref[...].astype(jnp.float32)
    scale = absmax_block_scale(x, target)
    c = _encode_fp4(jnp.clip(x / scale, -target, target))
    q_ref[...] = pack_fp4(c)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def quantize_pack_rows(x, *, bm: int = 128, interpret: bool):
    """(M,K) f32/bf16 -> (packed fp4 codes (M, K//2) uint8, scale (M,1))."""
    check_packed_lowering("quantize_pack_rows", True, interpret)
    M, K = x.shape
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm}"
    assert K % 2 == 0, f"fp4 packing needs even K, got {K}"
    f = get_format("fp4_e2m1")
    kernel = functools.partial(_quantize_pack_kernel, target=f.quant_target)
    return pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, K // 2), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, K // 2), jnp.uint8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("fmt", "bm", "interpret"))
def quantize_rows(x, *, fmt: str, bm: int = 128, interpret: bool):
    """(M,K) f32/bf16 -> (q:(M,K) fmt dtype | uint8 codes, scale:(M,1) f32)."""
    M, K = x.shape
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm}"
    f = get_format(fmt)
    out_dtype = jnp.uint8 if fmt == "fp4_e2m1" else jnp_dtype(fmt)
    kernel = functools.partial(_quantize_kernel, fmt=fmt,
                               target=f.quant_target)
    return pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, K), out_dtype),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        interpret=interpret,
    )(x)
