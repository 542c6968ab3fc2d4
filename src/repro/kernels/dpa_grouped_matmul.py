"""Pallas TPU kernels: grouped (per-expert) trans-precision DPA matmul.

The MoE expert contraction is a stack of independent DPA matmuls — one
(M,K)x(K,N) product per expert over the same Table-I datapath as
`dpa_matmul`.  These kernels add a leading *expert* grid dimension to
the dense kernels' (M-block, N-block, K-block) grid, so every expert's
operands move HBM->VMEM at format width (fp16 two bytes, fp8 one byte,
fp4 two E2M1 codes per byte when packed) and accumulate in fp32 VMEM
scratch across the K steps.  Expert weights are the dominant resident
bytes in MoE serving (dbrx/deepseek/granite); packing them 8x smaller is
the paper's bandwidth claim applied where it pays most.

Two entry points, mirroring the dense pair:

  dpa_grouped_matmul_prequant : both operand stacks pre-quantized (and
                                optionally nibble-packed along K);
                                per-expert row/column scales in the
                                epilogue.
  dpa_grouped_matmul_fused    : raw f32/bf16 activations quantized in
                                the kernel prologue — per-(row, K-block)
                                absmax scales folded into each partial
                                product, per-expert weight column scales
                                in the epilogue.

Grid is (expert, M//bm, N//bn, K//bk) with the K step innermost
(`arbitrary`), experts and output tiles parallel.  Validated on CPU via
interpret=True against the XLA fake-quant reference; the unpacked
variants compile through Mosaic (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import quant_rows_grid
from repro.kernels.dpa_matmul import _widen, check_packed_lowering


def _gmm_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))


# -----------------------------------------------------------------------------
# pre-quantized operand stacks (optionally packed)
# -----------------------------------------------------------------------------

def _grouped_prequant_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *,
                             n_k: int, fmt_x: str, fmt_w: str, pack_x: bool,
                             pack_w: bool):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block refs carry a leading length-1 expert dim; drop it for the MXU
    x = _widen(x_ref[0], fmt_x, packed=pack_x, axis=1)
    w = _widen(w_ref[0], fmt_w, packed=pack_w, axis=0)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _store():
        # epilogue: this expert's row scales x column scales
        o_ref[0] = acc_ref[...] * sx_ref[0] * sw_ref[0]


@functools.partial(jax.jit, static_argnames=("fmt_x", "fmt_w", "bm", "bk",
                                             "bn", "pack_x", "pack_w",
                                             "interpret"))
def dpa_grouped_matmul_prequant(xq, wq, sx, sw, *, fmt_x: str, fmt_w: str,
                                bm: int = 128, bk: int = 128, bn: int = 128,
                                pack_x: bool = False, pack_w: bool = False,
                                interpret: bool):
    """(E,M,K) x (E,K,N) -> (E,M,N) f32 with per-expert fp32 accumulation.

    xq: quantized activation stack (native fp8/fp16/bf16 dtype, or uint8
        E2M1 codes when fmt_x == "fp4_e2m1"; shape (E, M, K//2) packed
        bytes when `pack_x`);          sx: (E, M, 1) row scales.
    wq: stacked expert weights ((E, K//2, N) when `pack_w`);
                                       sw: (E, 1, N) column scales.

    Packing halves the bytes the x/w BlockSpecs move HBM->VMEM per
    expert; nibbles unpack in VMEM, so the packed path is bit-identical
    to the unpacked one — the dense kernel's contract, per expert.
    """
    assert not (pack_x and fmt_x != "fp4_e2m1"), "pack_x needs fp4 codes"
    assert not (pack_w and fmt_w != "fp4_e2m1"), "pack_w needs fp4 codes"
    check_packed_lowering("dpa_grouped_matmul_prequant", pack_x or pack_w,
                          interpret)
    E, M = xq.shape[0], xq.shape[1]
    K = xq.shape[2] * (2 if pack_x else 1)
    K2 = wq.shape[1] * (2 if pack_w else 1)
    N = wq.shape[2]
    assert E == wq.shape[0], (xq.shape, wq.shape)
    assert K == K2, (xq.shape, wq.shape, pack_x, pack_w)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, \
        f"shapes ({M},{K},{N}) must be multiples of blocks ({bm},{bk},{bn})"
    assert bk % 2 == 0 or not (pack_x or pack_w), "packed bk must be even"
    sx = jnp.broadcast_to(sx.astype(jnp.float32), (E, M, 1))
    sw = jnp.broadcast_to(sw.astype(jnp.float32), (E, 1, N))
    n_k = K // bk
    bk_x = bk // 2 if pack_x else bk
    bk_w = bk // 2 if pack_w else bk

    kernel = functools.partial(_grouped_prequant_kernel, n_k=n_k,
                               fmt_x=fmt_x, fmt_w=fmt_w, pack_x=pack_x,
                               pack_w=pack_w)
    return pl.pallas_call(
        kernel,
        grid=(E, M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, bm, bk_x), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk_w, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, bm, 1), lambda e, i, j, k: (e, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda e, i, j, k: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_gmm_params(),
        interpret=interpret,
    )(xq, wq, sx, sw)


# -----------------------------------------------------------------------------
# fused quantize -> grouped matmul (activations quantized in the prologue)
# -----------------------------------------------------------------------------

def _grouped_fused_kernel(x_ref, w_ref, sw_ref, o_ref, acc_ref, *, n_k: int,
                          fmt_x: str, fmt_w: str, pack_w: bool):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # prologue: absmax -> scale -> saturating RNE cast in VMEM.  The scale
    # varies per (expert token row, K block), so it folds into this block's
    # partial product; only the K-invariant expert column scales wait for
    # the epilogue — identical numerics to the dense fused kernel.
    xq, sx = quant_rows_grid(x_ref[0], fmt_x)
    w = _widen(w_ref[0], fmt_w, packed=pack_w, axis=0)
    acc_ref[...] += jnp.dot(xq, w, preferred_element_type=jnp.float32) * sx

    @pl.when(pl.program_id(3) == n_k - 1)
    def _store():
        o_ref[0] = acc_ref[...] * sw_ref[0]


@functools.partial(jax.jit, static_argnames=("fmt_x", "fmt_w", "bm", "bk",
                                             "bn", "pack_w", "interpret"))
def dpa_grouped_matmul_fused(x, wq, sw, *, fmt_x: str, fmt_w: str,
                             bm: int = 128, bk: int = 128, bn: int = 128,
                             pack_w: bool = False, interpret: bool):
    """Fused quantize->grouped matmul: raw x (E,M,K) f32/bf16,
    pre-quantized (and optionally packed) expert weights -> (E,M,N) f32.

    Each expert's (bm, bk) activation block is absmax-scaled and cast in
    the kernel prologue — the activation stack never round-trips through
    HBM in quantized form, while the expert weights (the MoE-dominant
    resident bytes) stream at format width: 8x fewer bytes than f32 for
    packed fp4 nibbles, 4x/2x for fp8/fp16.
    """
    assert not (pack_w and fmt_w != "fp4_e2m1"), "pack_w needs fp4 codes"
    check_packed_lowering("dpa_grouped_matmul_fused", pack_w, interpret)
    E, M, K = x.shape
    K2 = wq.shape[1] * (2 if pack_w else 1)
    N = wq.shape[2]
    assert E == wq.shape[0], (x.shape, wq.shape)
    assert K == K2, (x.shape, wq.shape, pack_w)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, \
        f"shapes ({M},{K},{N}) must be multiples of blocks ({bm},{bk},{bn})"
    assert bk % 2 == 0 or not pack_w, "packed bk must be even"
    sw = jnp.broadcast_to(sw.astype(jnp.float32), (E, 1, N))
    n_k = K // bk
    bk_w = bk // 2 if pack_w else bk

    kernel = functools.partial(
        _grouped_fused_kernel, n_k=n_k, fmt_x=fmt_x, fmt_w=fmt_w,
        pack_w=pack_w)
    return pl.pallas_call(
        kernel,
        grid=(E, M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk_w, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, 1, bn), lambda e, i, j, k: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_gmm_params(),
        interpret=interpret,
    )(x, wq, sw)
