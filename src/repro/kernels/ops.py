"""Jit'd public wrappers around the Pallas kernels.

These are what the execution-plan layer dispatches to: they quantize per
the policy, pad to block multiples, dispatch the kernel, and undo
padding.  On a TPU backend the kernels compile through Mosaic; on any
other backend they run in the Pallas interpreter (`_interpret`, decided
per call from the backend, never at import).

Route selection does NOT live here: `repro.kernels.registry` registers
each pipeline below as a `core.exec_plan` route with an explicit
lowering predicate, and the policy-driven entry points (`dpa_matmul`,
`quantize_rows`) resolve through the plan so they stay semantically
identical to the call sites that use the plan directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import exec_plan
from repro.core.packing import pack_fp4_axis
from repro.core.policy import TransPrecisionPolicy, get_policy
from repro.core.quantize import compute_scale, cast_to
from repro.distributed import tp as TP
from repro.distributed.sharding import _in_manual_region
from repro.kernels import dpa_grouped_matmul as _gm
from repro.kernels import dpa_matmul as _dm
from repro.kernels import flash_attention as _fa
from repro.kernels import quantize as _q


def _interpret() -> bool:
    """Interpret mode follows the backend: Mosaic compiles the kernels
    for a TPU, and the interpreter runs them everywhere else."""
    return jax.default_backend() != "tpu"


def _run(kernel, *args, **kw):
    """Dispatch a Pallas kernel in the mode `_interpret` picks.  Under
    the engine's tensor-parallel mesh every operand these wrappers pass
    is replicated, and XLA cannot partition a Mosaic kernel, so each
    device runs its own copy inside a shard_map (inside one already —
    the sharded attention routes — the kernel is called as is)."""
    kw["interpret"] = _interpret()
    mesh = TP.active_mesh()
    if mesh is None or _in_manual_region(mesh):
        return kernel(*args, **kw)
    return TP.shard_map(lambda *a: kernel(*a, **kw), mesh,
                        (P(),) * len(args), P())(*args)


def _pad_to(x, mult, axis):
    r = x.shape[axis] % mult
    if r == 0:
        return x, 0
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - r)
    return jnp.pad(x, pad), mult - r


def _quant_operand(x, fmt: str, axis_scale):
    """-> (codes/native, scale) with scale reduced over `axis_scale`."""
    if fmt == "fp4_e2m1":
        from repro.core.formats import get_format
        from repro.core.quantize import encode_fp4
        f = get_format(fmt)
        scale = compute_scale(x, f, axis=axis_scale)
        q = encode_fp4(jnp.clip(x.astype(jnp.float32) / scale,
                                -f.max_finite, f.max_finite))
        return q, scale
    scale = compute_scale(x, fmt, axis=axis_scale)
    return cast_to(x.astype(jnp.float32) / scale, fmt), scale


def _prep_weights(w, policy, bk, bn):
    """Quantize + pad + (optionally) pack the weight side."""
    pack_w = policy.packed and policy.fmt_weights == "fp4_e2m1"
    wq, sw = _quant_operand(w, policy.fmt_weights, axis_scale=0)
    wq, _ = _pad_to(wq, bk, 0)
    wq, pn = _pad_to(wq, bn, 1)
    swp, _ = _pad_to(sw, bn, 1)
    if pack_w:
        wq = pack_fp4_axis(wq, 0)
    return wq, swp, pn, pack_w


def dpa_matmul_fused_pipeline(x, w, policy: TransPrecisionPolicy, *,
                              bm=128, bk=128, bn=128):
    """Fused-quant pipeline: x ships at its native width (f32/bf16) and
    quantizes in the kernel prologue with per-(row, K-block) scales;
    weights are pre-quantized (packed fp4 when the policy says)."""
    policy = get_policy(policy)
    lead, K, N = x.shape[:-1], x.shape[-1], w.shape[-1]
    x2 = x.reshape(-1, K)
    bm_ = min(bm, max(8, x2.shape[0]))
    wq, swp, pn, pack_w = _prep_weights(w, policy, bk, bn)
    x2p, pm = _pad_to(x2, bm_, 0)
    x2p, _ = _pad_to(x2p, bk, 1)
    out = _run(
        _dm.dpa_matmul_fused, x2p, wq, swp, fmt_x=policy.fmt_acts,
        fmt_w=policy.fmt_weights, bm=bm_, bk=bk, bn=bn, pack_w=pack_w)
    if pm:
        out = out[: x2.shape[0]]
    if pn:
        out = out[:, :N]
    return out.reshape(*lead, N).astype(x.dtype)


def dpa_matmul_prequant_pipeline(x, w, policy: TransPrecisionPolicy, *,
                                 bm=128, bk=128, bn=128):
    """Prequant pipeline: XLA quantize pass on both sides, prequant
    kernel; fp4 operand sides additionally packed 2 codes/byte before
    dispatch when the policy says — the BlockSpec moves half the bytes,
    bit-identical results."""
    policy = get_policy(policy)
    lead, K, N = x.shape[:-1], x.shape[-1], w.shape[-1]
    x2 = x.reshape(-1, K)
    bm_ = min(bm, max(8, x2.shape[0]))
    pack_x = policy.packed and policy.fmt_acts == "fp4_e2m1"
    wq, swp, pn, pack_w = _prep_weights(w, policy, bk, bn)
    xq, sx = _quant_operand(x2, policy.fmt_acts, axis_scale=-1)
    xq, pm = _pad_to(xq, bm_, 0)
    sxp, _ = _pad_to(sx, bm_, 0)
    xq, _ = _pad_to(xq, bk, 1)
    if pack_x:
        xq = pack_fp4_axis(xq, 1)
    out = _run(
        _dm.dpa_matmul_prequant, xq, wq, sxp, swp, fmt_x=policy.fmt_acts,
        fmt_w=policy.fmt_weights, bm=bm_, bk=bk, bn=bn,
        pack_x=pack_x, pack_w=pack_w)
    if pm:
        out = out[: x2.shape[0]]
    if pn:
        out = out[:, :N]
    return out.reshape(*lead, N).astype(x.dtype)


def _grouped_views(eq: str, x, w):
    """Normalize a known grouped einsum to stacked per-expert matmuls.

    -> (x3 (E,M,K), w3 (E,K,N), unview: (E,M,N) -> eq's output shape).
    The supported eqs are `core.linear.GROUPED_EQS`; the registry
    predicates keep the Pallas grouped routes off anything else."""
    if eq == "gti,gio->gto":
        return x, w, lambda o: o
    if eq == "becd,edf->becf":
        b, e, c, d = x.shape
        x3 = x.transpose(1, 0, 2, 3).reshape(e, b * c, d)
        return x3, w, lambda o: o.reshape(e, b, c,
                                          -1).transpose(1, 0, 2, 3)
    raise ValueError(f"unsupported grouped einsum {eq!r}")


def _prep_grouped_weights(w3, policy, bk, bn):
    """Quantize + pad + (optionally) pack the stacked expert weights."""
    pack_w = policy.packed and policy.fmt_weights == "fp4_e2m1"
    wq, sw = _quant_operand(w3, policy.fmt_weights, axis_scale=1)
    wq, _ = _pad_to(wq, bk, 1)
    wq, pn = _pad_to(wq, bn, 2)
    swp, _ = _pad_to(sw, bn, 2)
    if pack_w:
        wq = pack_fp4_axis(wq, 1)
    return wq, swp, pn, pack_w


def dpa_grouped_fused_pipeline(x, w, policy: TransPrecisionPolicy, *,
                               eq: str, bm=128, bk=128, bn=128):
    """Grouped fused-quant pipeline: per-expert activations ship at
    native width (f32/bf16) and quantize in the kernel prologue with
    per-(row, K-block) scales; expert weights are pre-quantized (packed
    fp4 nibbles when the policy says — 8x fewer resident weight bytes)."""
    policy = get_policy(policy)
    x3, w3, unview = _grouped_views(eq, x, w)
    M, N = x3.shape[1], w3.shape[-1]
    bm_ = min(bm, max(8, M))
    wq, swp, pn, pack_w = _prep_grouped_weights(w3, policy, bk, bn)
    x3p, pm = _pad_to(x3, bm_, 1)
    x3p, _ = _pad_to(x3p, bk, 2)
    out = _run(
        _gm.dpa_grouped_matmul_fused, x3p, wq, swp, fmt_x=policy.fmt_acts,
        fmt_w=policy.fmt_weights, bm=bm_, bk=bk, bn=bn, pack_w=pack_w)
    if pm:
        out = out[:, :M]
    if pn:
        out = out[:, :, :N]
    return unview(out.astype(x.dtype))


def dpa_grouped_prequant_pipeline(x, w, policy: TransPrecisionPolicy, *,
                                  eq: str, bm=128, bk=128, bn=128):
    """Grouped prequant pipeline: XLA quantize pass on both operand
    stacks, prequant grouped kernel; fp4 sides nibble-packed along K
    before dispatch when the policy says — per-expert BlockSpecs move
    half the bytes, bit-identical results."""
    policy = get_policy(policy)
    x3, w3, unview = _grouped_views(eq, x, w)
    M, N = x3.shape[1], w3.shape[-1]
    bm_ = min(bm, max(8, M))
    pack_x = policy.packed and policy.fmt_acts == "fp4_e2m1"
    wq, swp, pn, pack_w = _prep_grouped_weights(w3, policy, bk, bn)
    xq, sx = _quant_operand(x3, policy.fmt_acts, axis_scale=-1)
    xq, pm = _pad_to(xq, bm_, 1)
    sxp, _ = _pad_to(sx, bm_, 1)
    xq, _ = _pad_to(xq, bk, 2)
    if pack_x:
        xq = pack_fp4_axis(xq, 2)
    out = _run(
        _gm.dpa_grouped_matmul_prequant, xq, wq, sxp, swp,
        fmt_x=policy.fmt_acts, fmt_w=policy.fmt_weights, bm=bm_, bk=bk,
        bn=bn, pack_x=pack_x, pack_w=pack_w)
    if pm:
        out = out[:, :M]
    if pn:
        out = out[:, :, :N]
    return unview(out.astype(x.dtype))


def dpa_matmul(x, w, policy: TransPrecisionPolicy, *, bm=128, bk=128,
               bn=128):
    """Policy-driven trans-precision matmul: x (..., K) @ w (K, N).

    Resolves the kernel pipeline through `core.exec_plan` (routes
    ``matmul/pallas_fused`` and ``matmul/pallas_prequant``), so calling
    this directly is identical to routing via `core.linear.dpa_dot`."""
    policy = get_policy(policy)
    entry = exec_plan.resolve("matmul", policy, w_dtype=str(w.dtype),
                              kernel_only=True)
    return entry.run(x, w, policy, bm=bm, bk=bk, bn=bn)


def quantize_rows(x, fmt: str, *, bm=128, pack: bool = False):
    """Fused absmax+cast row quantization (2D input).  With `pack` (fp4
    only) the kernel also nibble-packs: (M, K//2) uint8 out — the
    quantize->pack half of the quantize->pack->DPA pipeline.  Resolved
    through `core.exec_plan` op ``quantize_pack``."""
    entry = exec_plan.resolve("quantize_pack", None, fmt=fmt, pack=pack)
    return entry.run(x, fmt=fmt, pack=pack, bm=bm)


def quantize_rows_pallas(x, *, fmt: str, pack: bool, bm=128):
    """The Pallas row-quantizer pipelines (`quantize_pack` routes)."""
    x2, pm = _pad_to(x, bm, 0)
    if pack:
        assert fmt == "fp4_e2m1", "pack=True is the fp4 pipeline"
        q, s = _run(_q.quantize_pack_rows, x2, bm=bm)
    else:
        q, s = _run(_q.quantize_rows, x2, fmt=fmt, bm=bm)
    if pm:
        q, s = q[: x.shape[0]], s[: x.shape[0]]
    return q, s


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    bq=128, bk=128):
    return _run(_fa.flash_attention, q, k, v, causal=causal, window=window,
                scale=scale, bq=bq, bk=bk)


def dpa_flash_attention(q, k, v, *, fmt, fmt_kv=None, causal=True,
                        window=None, scale=None, bq=128, bk=128):
    """DPA-quantized flash attention over raw (B,H,S,D) operands: q and
    the softmax probabilities quantize onto fmt's grid in the kernel,
    K/V onto fmt_kv's (default fmt); accumulation and the online softmax
    stay f32.  See `flash_attention.dpa_flash_attention` for the
    quantized-KV-cache entry point (codes + scales in, fewer bytes moved).
    """
    return _run(_fa.dpa_flash_attention, q, k, v, fmt=fmt, fmt_kv=fmt_kv,
                causal=causal, window=window, scale=scale, bq=bq, bk=bk)


def paged_decode_attention(q, cache, positions, *, fmt, fmt_kv,
                           kv_packed=False, scale=None):
    """Block-table paged decode (route ``paged_decode/pallas_block_table``):
    unpacks the paged-cache pytree and dispatches the Pallas kernel —
    pages stream HBM->VMEM through the block table, no gathered view."""
    return _run(
        _fa.paged_decode_attention, q, cache["k_codes"], cache["k_scale"],
        cache["v_codes"], cache["v_scale"], cache["block_table"], positions,
        fmt=fmt, fmt_kv=fmt_kv, kv_packed=kv_packed, scale=scale)
