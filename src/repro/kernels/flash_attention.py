"""Pallas TPU kernels: blocked online-softmax attention (forward).

Used by the long-context configs (prefill) where materializing S x S
logits is the memory-roofline killer.  Standard FlashAttention tiling
adapted to TPU VMEM: q tiles of (bq, D) stay resident; k/v stream in
(bk, D) tiles; the running (max, denom, acc) triple lives in VMEM
scratch.  GQA is handled in the index maps (q-head block -> kv-head
block via integer division), so grouped heads never duplicate KV in HBM
— the same "narrow wires, wide accumulator" economics as the DPA GEMM.

Two entry points:

  flash_attention     : the seed f32 datapath.
  dpa_flash_attention : both attention matmuls run the DPA contract —
      QK^T and PV accumulate in f32 over operands quantized to a Table-I
      mode (fp16/bf16 2-term, fp8 4-term, fp4 8-term), while the online
      softmax (running max / denominator / alpha rescales) stays entirely
      f32.  K/V either arrive raw (quantized per-row in the prologue) or
      as quantized KV-cache rows — codes + per-row f32 scales, fp4
      optionally nibble-packed along head_dim (`core.packing` layout, so
      the BlockSpec moves half the cache bytes) — and are *dequantized in
      the prologue* (widen(codes) * scale).  Semantic spec:
      `ref.dpa_flash_attention_ref`.

Supports causal and sliding-window (RecurrentGemma local attention)
masks.  Forward only: training configs use XLA attention + remat; the
kernel serves prefill.

  paged_decode_attention : the serving engine's decode step over the
      *paged* quantized KV cache (`core.kvcache` page pool + block
      table).  The block table rides scalar prefetch and the K/V
      BlockSpec index maps read *through* it — page j of request b
      streams straight from pool page ``table[b, j]`` into VMEM with
      prologue dequant, so the contiguous view is never re-materialized
      in HBM (`gather_paged_kv` stays as the jnp reference fallback).
      Bit-identical to that fallback across all Table-I KV formats,
      packed fp4 crossing page boundaries included; selected by
      `core.exec_plan` (route ``paged_decode/pallas_block_table``) like
      every other route.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_fp4
from repro.core.quantize import decode_fp4, quant_rows_grid
from repro.kernels.dpa_matmul import check_packed_lowering

_NEG_INF = -1e30
# the dequantized K/V rows (grid x f32 scale) are not bf16-exact; the DPA
# contract multiplies them at f32, which a TPU matmul gives only at
# HIGHEST precision (the default is one bf16 pass)
_F32_DOT = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k: int, scale: float, causal: bool, window,
                  bq: int, bk: int, sq: int, sk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (sk - sq)                                   # align cache offsets
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == n_k - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    scale=None, bq: int = 128, bk: int = 128,
                    interpret: bool):
    """(B,H,Sq,D),(B,Hkv,Sk,D),(B,Hkv,Sk,D) -> (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    g = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    scale_v = float(scale if scale is not None else D ** -0.5)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * Hkv, Sk, D)
    vr = v.reshape(B * Hkv, Sk, D)
    kernel = functools.partial(
        _flash_kernel, n_k=Sk // bk, scale=scale_v, causal=causal,
        window=window, bq=bq, bk=bk, sq=Sq, sk=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, g=g: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D)


# -----------------------------------------------------------------------------
# DPA-quantized attention: QK^T and PV accumulate f32 over narrow operands
# -----------------------------------------------------------------------------

def _widen_kv(codes, fmt_kv: str, packed: bool):
    """Cache codes -> f32 grid values (the prologue widening): native
    narrow dtypes cast up; fp4 E2M1 codes decode arithmetically, after a
    nibble unpack along head_dim when `packed`."""
    if fmt_kv == "fp4_e2m1":
        if packed:
            codes = unpack_fp4(codes)
        return decode_fp4(codes)
    return codes.astype(jnp.float32)


def _dpa_flash_kernel(*refs, n_k: int, scale: float, causal: bool, window,
                      bq: int, bk: int, sq: int, sk: int, fmt: str,
                      fmt_kv: str, kv_quant: bool, kv_packed: bool):
    if kv_quant:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # prologue: quantize q onto fmt's grid (scale rides the epilogue of the
    # QK^T partial product); widen K/V to their dequantized values — from
    # cache codes * stored scales, or via in-block per-row quantization
    qg, qs = quant_rows_grid(q_ref[0], fmt)            # (bq, D), (bq, 1)
    if kv_quant:
        k_eff = _widen_kv(k_ref[0], fmt_kv, kv_packed) * ks_ref[0]
        v_eff = _widen_kv(v_ref[0], fmt_kv, kv_packed) * vs_ref[0]
    else:
        kg, ks = quant_rows_grid(k_ref[0], fmt_kv)
        vg, vs = quant_rows_grid(v_ref[0], fmt_kv)
        k_eff, v_eff = kg * ks, vg * vs

    # DPA matmul #1: narrow q x widened K, f32 accumulate, row scale after
    s = jnp.dot(qg, k_eff.T, preferred_element_type=jnp.float32) * qs * scale

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (sk - sq)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    # online softmax: running max / denominator / rescales all f32
    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    # DPA matmul #2: probabilities quantized per (row, k-block) onto fmt's
    # grid; their scale folds into BOTH the f32 PV accumulation and the
    # f32 denominator, so numerator and normalizer see the same grid
    pg, ps = quant_rows_grid(p, fmt)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pg, axis=1, keepdims=True) * ps
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pg, v_eff, preferred_element_type=jnp.float32) * ps
    m_ref[...] = m_cur

    @pl.when(j == n_k - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "fmt", "fmt_kv", "kv_quant", "kv_packed", "causal", "window", "scale",
    "bq", "bk", "interpret"))
def dpa_flash_attention(q, k, v, k_scale=None, v_scale=None, *, fmt: str,
                        fmt_kv: str | None = None, kv_quant: bool = False,
                        kv_packed: bool = False, causal: bool = True,
                        window=None, scale=None, bq: int = 128,
                        bk: int = 128, interpret: bool):
    """(B,H,Sq,D) x (B,Hkv,Sk,Dk) x (B,Hkv,Sk,Dk) -> (B,H,Sq,D).

    Raw path (kv_quant=False): k/v are float tensors, quantized per-row
    onto fmt_kv's grid in the kernel prologue.  Cache path (kv_quant=True):
    k/v are quantized KV-cache rows — native narrow dtype or uint8 E2M1
    codes (Dk = D // 2 packed bytes when kv_packed) — with per-row f32
    scales k_scale/v_scale (B,Hkv,Sk,1).  Both paths see bit-identical
    K/V values; the cache path just moves 2-8x fewer bytes HBM->VMEM.
    """
    check_packed_lowering("dpa_flash_attention", kv_quant and kv_packed,
                          interpret)
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    g = H // Hkv
    fmt_kv = fmt_kv or fmt
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    scale_v = float(scale if scale is not None else D ** -0.5)
    dk = D // 2 if (kv_quant and kv_packed) else D

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * Hkv, Sk, dk)
    vr = v.reshape(B * Hkv, Sk, dk)
    kernel = functools.partial(
        _dpa_flash_kernel, n_k=Sk // bk, scale=scale_v, causal=causal,
        window=window, bq=bq, bk=bk, sq=Sq, sk=Sk, fmt=fmt, fmt_kv=fmt_kv,
        kv_quant=kv_quant, kv_packed=kv_packed)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, dk), lambda b, i, j, g=g: (b // g, j, 0)),
        pl.BlockSpec((1, bk, dk), lambda b, i, j, g=g: (b // g, j, 0)),
    ]
    operands = [qr, kr, vr]
    if kv_quant:
        in_specs += [
            pl.BlockSpec((1, bk, 1), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, i, j, g=g: (b // g, j, 0)),
        ]
        operands += [k_scale.reshape(B * Hkv, Sk, 1),
                     v_scale.reshape(B * Hkv, Sk, 1)]
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, Sk // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, Sq, D)


# -----------------------------------------------------------------------------
# paged decode: block-table reads through scalar-prefetched index maps
# -----------------------------------------------------------------------------

def _paged_decode_kernel(tab_ref, pos_ref, q_ref, kc_ref, ks_ref, vc_ref,
                         vs_ref, o_ref, k_s, v_s, *, n_pages: int, ps: int,
                         kv_heads: int, fmt: str, fmt_kv: str,
                         kv_packed: bool, scale: float, s_view: int):
    """Grid (B, n_pages): page step j streams request b's pool page
    ``table[b, j]`` — all KV heads, the block the BlockSpec index map
    names — and widens codes * scales per head into VMEM scratch
    (KV, S_view, hd); the last step runs the DPA attention row of every
    head.

    Blocks span whole (KV, wc) trailing dims of the pool, the tiling
    Mosaic accepts; a head is picked inside the kernel.  The final
    computation deliberately mirrors `models.decode_attn.dpa_attention`'s
    einsum structure — one leading batch dim (here the head group), per-
    batch (1, hd) x (hd, S) matvecs — instead of a flat (g, hd) @ (hd, S)
    dot: XLA tiles the two shapes differently, and the batched form keeps
    the route bit-identical to the jnp gather fallback under the
    interpreter (the contract `tests/test_exec_plan.py` pins at tol 0),
    while Mosaic lowers a single leading batch dim.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
    for h in range(kv_heads):
        k_s[h, rows, :] = _widen_kv(kc_ref[0, :, h, :], fmt_kv,
                                    kv_packed) * ks_ref[0, :, h, :]
        v_s[h, rows, :] = _widen_kv(vc_ref[0, :, h, :], fmt_kv,
                                    kv_packed) * vs_ref[0, :, h, :]

    @pl.when(j == n_pages - 1)
    def _compute():
        g, hd = q_ref.shape[2], q_ref.shape[3]
        for h in range(kv_heads):
            qg, qs = quant_rows_grid(q_ref[0, h][:, None, :], fmt)
            k_all = jnp.broadcast_to(k_s[h][None], (g, s_view, hd))
            v_all = jnp.broadcast_to(v_s[h][None], (g, s_view, hd))
            logits = jnp.einsum("gsd,gtd->gst", qg, k_all,
                                precision=_F32_DOT,
                                preferred_element_type=jnp.float32)
            logits = logits * qs * scale
            kpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            logits = jnp.where(kpos <= pos_ref[b], logits, _NEG_INF)
            m = jnp.max(logits, axis=-1, keepdims=True)
            p = jnp.exp(logits - m)                   # f32 softmax core
            pg, psq = quant_rows_grid(p, fmt)
            den = jnp.sum(pg, axis=-1, keepdims=True) * psq
            num = jnp.einsum("gst,gtd->gsd", pg, v_all, precision=_F32_DOT,
                             preferred_element_type=jnp.float32) * psq
            out = num / jnp.maximum(den, 1e-30)
            o_ref[0, h] = out[:, 0, :].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "fmt_kv", "kv_packed",
                                             "scale", "interpret"))
def paged_decode_attention(q, k_codes, k_scale, v_codes, v_scale,
                           block_table, positions, *, fmt: str, fmt_kv: str,
                           kv_packed: bool = False, scale=None,
                           interpret: bool):
    """One decode step against the paged quantized KV cache.

    q: (B, 1, H, hd) (already rope'd at per-request positions);
    k/v_codes: (P, page, KV, wc) page pools (wc = hd, or hd // 2 packed
    fp4); k/v_scale: (P, page, KV, 1) f32 per-row scales; block_table:
    (B, max_pages) i32; positions: (B,) i32 current token index per
    request.  Same DPA contract as `dpa_flash_attention`'s cache mode —
    prologue dequant, f32 accumulation, f32 softmax glue — with the
    causal mask per request (row b attends key slots <= positions[b];
    scratch/stale tail pages are masked off).  VMEM holds the widened
    (KV, max_pages * page, hd) f32 K and V of one request.
    """
    check_packed_lowering("paged_decode_attention", kv_packed, interpret)
    B, Sq, H, hd = q.shape
    assert Sq == 1, "paged decode serves single-token steps"
    _, n_pages = block_table.shape
    _, ps, kv_heads, wc = k_codes.shape
    g = H // kv_heads
    s_view = n_pages * ps
    scale_v = float(scale if scale is not None else hd ** -0.5)
    qr = q[:, 0].reshape(B, kv_heads, g, hd)

    def page_idx(b, j, tab, pos):
        return (tab[b, j], 0, 0, 0)

    def req_idx(b, j, tab, pos):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, n_pages=n_pages, ps=ps, kv_heads=kv_heads,
        fmt=fmt, fmt_kv=fmt_kv, kv_packed=kv_packed, scale=scale_v,
        s_view=s_view)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, kv_heads, g, hd), req_idx),
            pl.BlockSpec((1, ps, kv_heads, wc), page_idx),
            pl.BlockSpec((1, ps, kv_heads, 1), page_idx),
            pl.BlockSpec((1, ps, kv_heads, wc), page_idx),
            pl.BlockSpec((1, ps, kv_heads, 1), page_idx),
        ],
        out_specs=pl.BlockSpec((1, kv_heads, g, hd), req_idx),
        scratch_shapes=[pltpu.VMEM((kv_heads, s_view, hd), jnp.float32),
                        pltpu.VMEM((kv_heads, s_view, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv_heads, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(positions, jnp.int32),
      qr, k_codes, k_scale, v_codes, v_scale)
    return out.reshape(B, 1, H, hd)
