"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic specification its kernel is tested against
(tests/test_kernels.py sweeps shapes x dtypes and assert_allcloses).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.formats import get_format
from repro.core.quantize import (cast_to, compute_scale, decode_fp4,
                                 quant_rows_grid)


def widen_ref(x, fmt_name: str):
    """Reference operand widening (matches dpa_matmul._widen)."""
    if fmt_name == "fp4_e2m1":
        return decode_fp4(x)
    return x.astype(jnp.float32)


def dpa_matmul_ref(xq, wq, sx, sw, *, fmt_x: str, fmt_w: str):
    """fp32-accumulated matmul over widened operands, scaled epilogue."""
    x = widen_ref(xq, fmt_x)
    w = widen_ref(wq, fmt_w)
    out = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return out * sx.astype(jnp.float32) * sw.astype(jnp.float32)


def dpa_matmul_fused_ref(x, wq, sw, *, fmt_x: str, fmt_w: str, bk: int):
    """Semantic spec of `dpa_matmul_fused`: per-(row, K-block) absmax
    quantization of raw x, blockwise scale folded into each partial
    product, weight column scales in the epilogue.  wq is *unpacked*."""
    M, K = x.shape
    xf = x.astype(jnp.float32)
    out = jnp.zeros((M, wq.shape[1]), jnp.float32)
    w = widen_ref(wq, fmt_w)
    for k0 in range(0, K, bk):
        q, scale = quant_rows_grid(xf[:, k0:k0 + bk], fmt_x)
        out = out + jnp.dot(q, w[k0:k0 + bk],
                            preferred_element_type=jnp.float32) * scale
    return out * sw.astype(jnp.float32)


def quantize_rows_ref(x, *, fmt: str):
    """Row-wise absmax quantization (matches kernels.quantize)."""
    f = get_format(fmt)
    xf = x.astype(jnp.float32)
    scale = compute_scale(xf, f, axis=1)
    y = xf / scale
    if fmt == "fp4_e2m1":
        from repro.kernels.quantize import _encode_fp4
        q = _encode_fp4(jnp.clip(y, -f.max_finite, f.max_finite))
    else:
        q = cast_to(y, f)
    return q, scale


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None,
                        window: int | None = None):
    """Reference attention: (B,H,Sq,D),(B,Hkv,Sk,D),(B,Hkv,Sk,D)->(B,H,Sq,D).

    GQA: q heads grouped over kv heads.  Optional causal mask and local
    window (RecurrentGemma-style sliding attention).
    """
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, Sq, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * scale
    Sk = kf.shape[2]
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -1e30)
    probs = _softmax(logits)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
    return out.reshape(B, H, Sq, D).astype(q.dtype)


def _softmax(x):
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def dpa_flash_attention_ref(q, k, v, *, fmt: str, fmt_kv: str | None = None,
                            causal: bool = True, window: int | None = None,
                            scale=None, bk: int = 128):
    """Semantic spec of `flash_attention.dpa_flash_attention`.

    Both attention matmuls accumulate in f32 over quantized operands
    (the Table-I DPA modes); the online-softmax running max/sum stay f32:

      q  : per-row absmax onto fmt's grid; the row scale multiplies the
           QK^T partial product (software exponent path).
      k,v: per-row absmax onto fmt_kv's grid (defaults to fmt), consumed
           *dequantized* — widen(codes) * scale — exactly the prologue of
           the quantized-KV cache path, so raw and cached K/V are
           bit-identical.
      p  : each (row, bk key-block) of exp(s - m_running) is absmax-
           quantized onto fmt's grid; its scale folds into the f32 PV
           accumulation AND the f32 denominator (probabilities and their
           normalizer see the same grid, so quantization error partially
           cancels in the ratio).

    The loop mirrors the kernel's K-grid iteration (running max, alpha
    rescale) so kernel-vs-ref parity is tight, not just statistical.
    """
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    sc = float(scale if scale is not None else D ** -0.5)
    kf = fmt_kv or fmt

    qg, qs = quant_rows_grid(q, fmt)                    # (B,H,Sq,D),(..,1)
    kg, ks = quant_rows_grid(k, kf)
    vg, vs = quant_rows_grid(v, kf)
    k_eff = jnp.repeat(kg * ks, g, axis=1)              # dequant-in-prologue
    v_eff = jnp.repeat(vg * vs, g, axis=1)

    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    m = jnp.full((B, H, Sq, 1), -1e30, jnp.float32)
    l = jnp.zeros((B, H, Sq, 1), jnp.float32)
    acc = jnp.zeros((B, H, Sq, D), jnp.float32)
    for j0 in range(0, Sk, bk):
        kb = k_eff[:, :, j0:j0 + bk]
        s = jnp.einsum("bhqd,bhkd->bhqk", qg, kb,
                       preferred_element_type=jnp.float32) * qs * sc
        kpos = j0 + jnp.arange(kb.shape[2])[None, :]
        mask = jnp.ones(qpos.shape[:1] + kpos.shape[1:], bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, -1e30)
        m_cur = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m - m_cur)
        pg, ps = quant_rows_grid(p, fmt)
        l = l * alpha + jnp.sum(pg, axis=-1, keepdims=True) * ps
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", pg, v_eff[:, :, j0:j0 + bk],
            preferred_element_type=jnp.float32) * ps
        m = m_cur
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)
