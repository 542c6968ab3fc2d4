"""Pallas TPU kernels: trans-precision DPA matmul (packed + fused).

TPU adaptation of the TransDot datapath (DESIGN.md §2): the MXU is a
128x128 fp32-accumulating systolic dot-product engine — i.e. a very wide
DPA unit.  The paper's N-term DPA (narrow operands in, one wide
accumulation out) maps onto:

  HBM -> VMEM   : operands move at *format width* — fp16 two bytes, fp8
                  one byte, fp4 two E2M1 codes per byte (`pack_x`/`pack_w`
                  halve the uint8 bytes the BlockSpec moves).  The paper's
                  "fixed-width FPU interface" becomes HBM bandwidth
                  actually saved: 2x/4x/8x fewer operand bytes than f32.
  VMEM decode   : in-kernel nibble unpack + dequant-free *widening* of
                  operand codes into MXU-ingestible values (the multi-mode
                  multiplier's operand partitioning).
  MXU + scratch : fp32 accumulation across the K grid dimension (the
                  paper's wide adder + the extra DPA pipeline stage: the
                  accumulator lives across K iterations).
  epilogue      : per-channel scales applied at the final K step (the
                  exponent datapath's contribution, hoisted to software
                  scales as in all block-scaled AI formats).

Two entry points:

  dpa_matmul_prequant : both operands already quantized (and optionally
                        packed); row/column scales applied in the epilogue.
  dpa_matmul_fused    : raw f32/bf16 activations quantized *inside* the
                        kernel prologue — per-(row, K-block) absmax scales
                        folded into the accumulation, weight column scales
                        in the epilogue.  No separate XLA quantize pass, no
                        quantized-activation round-trip through HBM.

Block shapes default to MXU-aligned (128 multiples).  Validated on CPU
via interpret=True against `ref.py`; on a TPU the unpacked variants
compile through Mosaic (tests/test_tpu_compile.py), and the packed ones
refuse to (`check_packed_lowering`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_fp4_axis
from repro.core.quantize import decode_fp4, quant_rows_grid


def _mm_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def check_packed_lowering(kernel: str, packed: bool, interpret: bool):
    """Refuse a packed-fp4 operand on the compiled (Mosaic) path.

    The nibble unpack/pack interleaves two codes per byte along a minor
    dimension, a reshape Mosaic refuses ("unsupported shape cast"), so
    the packed variants run only in the interpreter.  Raising here keeps
    a TPU run from silently falling back to the interpreter or to an XLA
    route."""
    if packed and not interpret:
        raise NotImplementedError(
            f"{kernel}: packed-fp4 operands do not lower through Mosaic "
            "(the nibble unpack is an unsupported shape cast); serve an "
            "unpacked preset such as w8a8_kv8_attn8 on a TPU")


def _widen(x, fmt_name: str, *, packed: bool = False, axis: int = 0):
    """Operand codes/values -> f32 products domain (the multiplier input).

    For fp4 the input is uint8 E2M1 codes — one per byte, or two per byte
    when `packed` (unpacked along `axis`, the K dimension of the block,
    with `core.packing`'s low-nibble-even layout — the helpers are pure
    jnp so they run inside the kernel)."""
    if fmt_name == "fp4_e2m1":
        if packed:
            x = unpack_fp4_axis(x, axis)
        return decode_fp4(x)
    return x.astype(jnp.float32)


# -----------------------------------------------------------------------------
# pre-quantized operands (optionally packed)
# -----------------------------------------------------------------------------

def _dpa_matmul_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *,
                       n_k: int, fmt_x: str, fmt_w: str, pack_x: bool,
                       pack_w: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = _widen(x_ref[...], fmt_x, packed=pack_x, axis=1)
    w = _widen(w_ref[...], fmt_w, packed=pack_w, axis=0)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        # epilogue: software exponent path — row scale x column scale
        o_ref[...] = acc_ref[...] * sx_ref[...] * sw_ref[...]


@functools.partial(jax.jit, static_argnames=("fmt_x", "fmt_w", "bm", "bk",
                                             "bn", "pack_x", "pack_w",
                                             "interpret"))
def dpa_matmul_prequant(xq, wq, sx, sw, *, fmt_x: str, fmt_w: str,
                        bm: int = 128, bk: int = 128, bn: int = 128,
                        pack_x: bool = False, pack_w: bool = False,
                        interpret: bool):
    """(M,K) x (K,N) -> (M,N) f32 with fp32 accumulation.

    xq: quantized operand (native fp8/fp16/bf16 dtype, or uint8 E2M1 codes
        when fmt_x == "fp4_e2m1"; shape (M, K//2) packed bytes when
        `pack_x`);                 sx: (M,1) or (1,1) row scales.
    wq: same on the (K,N) side ((K//2, N) when `pack_w`);
                                   sw: (1,N) or (1,1) column scales.

    Packing halves the bytes the x/w BlockSpecs move HBM->VMEM; the kernel
    unpacks nibbles in VMEM before widening, so the packed path is
    bit-identical to the unpacked one.
    """
    assert not (pack_x and fmt_x != "fp4_e2m1"), "pack_x needs fp4 codes"
    assert not (pack_w and fmt_w != "fp4_e2m1"), "pack_w needs fp4 codes"
    check_packed_lowering("dpa_matmul_prequant", pack_x or pack_w, interpret)
    M = xq.shape[0]
    K = xq.shape[1] * (2 if pack_x else 1)
    K2 = wq.shape[0] * (2 if pack_w else 1)
    N = wq.shape[1]
    assert K == K2, (xq.shape, wq.shape, pack_x, pack_w)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, \
        f"shapes ({M},{K},{N}) must be multiples of blocks ({bm},{bk},{bn})"
    assert bk % 2 == 0 or not (pack_x or pack_w), "packed bk must be even"
    sx = jnp.broadcast_to(sx.astype(jnp.float32), (M, 1))
    sw = jnp.broadcast_to(sw.astype(jnp.float32), (1, N))
    n_k = K // bk
    bk_x = bk // 2 if pack_x else bk
    bk_w = bk // 2 if pack_w else bk

    kernel = functools.partial(_dpa_matmul_kernel, n_k=n_k, fmt_x=fmt_x,
                               fmt_w=fmt_w, pack_x=pack_x, pack_w=pack_w)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk_x), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_w, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_mm_params(),
        interpret=interpret,
    )(xq, wq, sx, sw)


# -----------------------------------------------------------------------------
# fused quantize -> matmul (activations quantized in the kernel prologue)
# -----------------------------------------------------------------------------

def _dpa_fused_kernel(x_ref, w_ref, sw_ref, o_ref, acc_ref, *, n_k: int,
                      fmt_x: str, fmt_w: str, pack_w: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # prologue: absmax -> scale -> saturating RNE cast, all in VMEM — the
    # `quant_rows_grid` recipe per (row, K-block), shared with the
    # reference.  The scale varies per K block, so it is folded into this
    # block's partial product here; only the K-invariant weight scales
    # wait for the epilogue.
    xq, sx = quant_rows_grid(x_ref[...], fmt_x)
    w = _widen(w_ref[...], fmt_w, packed=pack_w, axis=0)
    acc_ref[...] += jnp.dot(xq, w, preferred_element_type=jnp.float32) * sx

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...] * sw_ref[...]


@functools.partial(jax.jit, static_argnames=("fmt_x", "fmt_w", "bm", "bk",
                                             "bn", "pack_w", "interpret"))
def dpa_matmul_fused(x, wq, sw, *, fmt_x: str, fmt_w: str, bm: int = 128,
                     bk: int = 128, bn: int = 128, pack_w: bool = False,
                     interpret: bool):
    """Fused quantize->matmul: raw x (M,K) f32/bf16, pre-quantized (and
    optionally packed) weights -> (M,N) f32.

    The activation tensor never round-trips through HBM in quantized form:
    each (bm, bk) block is absmax-scaled and cast in the kernel prologue,
    its per-(row, K-block) scale folded into the partial-product
    accumulation, and the (1, bn) weight column scales applied in the
    epilogue.  Numerics follow `quantize_blockwise(x, fmt, axis=-1,
    block=bk)` — *finer*-grained than the per-row unfused path.
    """
    assert not (pack_w and fmt_w != "fp4_e2m1"), "pack_w needs fp4 codes"
    check_packed_lowering("dpa_matmul_fused", pack_w, interpret)
    M, K = x.shape
    K2 = wq.shape[0] * (2 if pack_w else 1)
    N = wq.shape[1]
    assert K == K2, (x.shape, wq.shape, pack_w)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, \
        f"shapes ({M},{K},{N}) must be multiples of blocks ({bm},{bk},{bn})"
    assert bk % 2 == 0 or not pack_w, "packed bk must be even"
    sw = jnp.broadcast_to(sw.astype(jnp.float32), (1, N))
    n_k = K // bk
    bk_w = bk // 2 if pack_w else bk

    kernel = functools.partial(
        _dpa_fused_kernel, n_k=n_k, fmt_x=fmt_x, fmt_w=fmt_w, pack_w=pack_w)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_w, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_mm_params(),
        interpret=interpret,
    )(x, wq, sw)
