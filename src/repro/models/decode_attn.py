"""Decode-side attention: DPA-quantized paths + sharded flash-decoding.

DPA attention (`dpa_attention` / `dpa_decode_attn`)
---------------------------------------------------
The jnp face of the DPA attention contract (kernel face:
`repro.kernels.flash_attention.dpa_flash_attention`; spec:
`repro.kernels.ref.dpa_flash_attention_ref`): QK^T and PV accumulate in
f32 over operands absmax-quantized onto a Table-I format grid, and the
softmax max/denominator stay f32.  These run under plain XLA, so they
serve every shape the Pallas kernel's block constraints exclude (and all
decode steps, where Sq == 1).  `dpa_paged_decode_attn` is the serving-
engine variant: same contract, but K/V codes are read through a block
table over the paged cache (`core.kvcache` paged layout) with a
per-request causal mask, so one batched step serves requests of mixed
lengths.  They define the *semantics* of the path;
the *bandwidth* claim belongs to the kernel's kv_quant mode, whose
BlockSpec moves cache codes+scales HBM->VMEM and widens in the prologue
— here the dequantized K/V is an ordinary XLA f32 intermediate (the HBM
saving on the XLA path is the cache's at-rest footprint, not the
per-step traffic).

Everything here is registered as `core.exec_plan` routes by
`repro.kernels.registry`: `dpa_attention`/`sdpa_reference` are the
masked fallbacks of the ``flash_attn`` op, `dpa_decode_attn` is the
``decode_attn`` reference, and `dpa_paged_decode_attn` is the
``paged_decode/jnp_gather`` reference the block-table Pallas kernel
(`kernels.flash_attention.paged_decode_attention`) is pinned
bit-identical against.

Sharded flash-decoding (`flash_decode`)
---------------------------------------
Shard-local KV-cache update + partial softmax.

Auto-SPMD cannot see that a decode step's cache update touches one
sequence shard, nor that attention against a sequence-sharded cache only
needs (max, denom, weighted-V) per shard — it all-gathers the cache every
layer (2 x S_shard x KV x hd per layer).  This module is the manual
version: a shard_map over the "model" axis that

  1. writes k/v into the *owning* shard only (branchless in-range mask),
  2. computes local logits + local (max, exp-sum, exp-weighted V),
  3. combines across shards with three tiny collectives
     (B*H + B*H + B*H*hd floats — ~1e4x less wire than the gather).

The "data"/"pod" axes stay automatic, so the same code serves any DP
layout.  Used by layers.apply_attention when cfg.flash_decode is set and
the ambient mesh carries a "model" axis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantize import quant_rows_grid


def build_sdpa_mask(sq: int, skv: int, offset, causal: bool, window,
                    valid=None):
    """(Sq, Skv) bool attention mask shared by the masked XLA routes.

    offset: index of q position 0 within the kv timeline; window: local
    attention width (> 0); valid: optional (Skv,) extra key-slot mask
    (sliding caches)."""
    qpos = offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None and window > 0:
        mask = mask & (kpos > qpos - window)
    if valid is not None:
        mask = mask & valid[None, :]
    return mask


def sdpa_reference(q, k, v, mask, *, scale):
    """The seed f32 attention datapath (any shape, GQA expansion).

    q: (B,Sq,H,hd); k/v: (B,Skv,KV,hd); mask broadcastable to
    (B,H,Sq,Skv).  f32 logits/softmax over compute-dtype operands — the
    `flash_attn/xla_ref_attn` route every DPA attention mode is judged
    against."""
    g = q.shape[2] // k.shape[2]
    kh = jnp.repeat(k, g, axis=2)     # (B, Skv, H, hd) — GQA expansion
    vh = jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, kh,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, vh)


def dpa_attention(q, k, v, mask, *, fmt: str, fmt_kv=None, scale,
                  kv_on_grid: bool = False):
    """DPA attention over grouped K/V (XLA path, any shape).

    q: (B,Sq,H,hd); k/v: (B,Skv,KV,hd) with H a multiple of KV; mask
    broadcastable to (B,H,Sq,Skv).  With `kv_on_grid`, k/v already carry
    dequantized KV-cache values (grid * scale) and are consumed as-is;
    otherwise they are per-row quantized onto fmt_kv's grid here
    (bit-identical to a cache round-trip, so prefill and decode agree).
    Quantization happens *before* the GQA expansion — repeated heads
    share a row's scale, so expanding first would just redo identical
    absmax/encode work g times.  Matches `ref.dpa_flash_attention_ref`
    with a single key block (global max).
    """
    B, Sq, H, hd = q.shape
    g = H // k.shape[2]
    qg, qs = quant_rows_grid(q, fmt)                   # (B,Sq,H,hd/1)
    if kv_on_grid:
        k_eff = k.astype(jnp.float32)
        v_eff = v.astype(jnp.float32)
    else:
        kf = fmt_kv or fmt
        kg, ks = quant_rows_grid(k, kf)
        vg, vs = quant_rows_grid(v, kf)
        k_eff, v_eff = kg * ks, vg * vs
    if g > 1:
        k_eff = jnp.repeat(k_eff, g, axis=2)           # (B,Skv,H,hd)
        v_eff = jnp.repeat(v_eff, g, axis=2)
    # head-major operands: both einsums batch over leading (B, H) dims,
    # the per-batch matvec form the paged Pallas kernel reproduces bit
    # for bit under the interpreter (see flash_attention._paged_decode_
    # kernel).  HIGHEST: the dequantized K/V rows are not bf16-exact, and
    # a TPU's default f32 matmul is one bf16 pass
    logits = jnp.einsum("bhsd,bhtd->bhst", qg.transpose(0, 2, 1, 3),
                        k_eff.transpose(0, 2, 1, 3),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    logits = logits * qs.transpose(0, 2, 1, 3) * scale
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)                            # f32 softmax core
    pg, ps = quant_rows_grid(p, fmt)
    den = jnp.sum(pg, axis=-1, keepdims=True) * ps     # f32 denominator
    num = jnp.einsum("bhst,bhtd->bhsd", pg, v_eff.transpose(0, 2, 1, 3),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32) * ps
    out = num / jnp.maximum(den, 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def dpa_decode_attn(q, cache, offset, *, fmt: str, fmt_kv: str,
                    kv_packed: bool, scale):
    """One decode step against a quantized KV cache.

    q: (B,1,H,hd) (already rope'd); cache: `repro.core.kvcache` pytree
    (B,S_ctx,KV,...).  The cache rows are widened in the prologue
    (codes * per-row scale) and both matmuls accumulate f32 over
    fmt-grid operands; causal masking via `offset`.
    """
    from repro.core.kvcache import dequantize_cache
    k, v = dequantize_cache(cache, fmt=fmt_kv, packed=kv_packed)
    s_ctx = k.shape[1]
    valid = jnp.arange(s_ctx) <= jnp.asarray(offset, jnp.int32)
    mask = valid[None, None, None, :]
    return dpa_attention(q, k, v, mask, fmt=fmt, scale=scale,
                         kv_on_grid=True)


def dpa_paged_decode_attn(q, cache, positions, *, fmt: str, fmt_kv: str,
                          kv_packed: bool, scale):
    """One decode step against a *paged* quantized KV cache.

    q: (B,1,H,hd) (already rope'd at per-request positions); cache: paged
    `repro.core.kvcache` pytree (page pool + (B, max_pages) block table);
    positions: (B,) i32 — request b's current token index.  The block
    table gathers each request's pages into timeline order (pure relayout,
    bit-identical codes/scales to a contiguous cache), the prologue widens
    them (codes * per-row scale), and both matmuls accumulate f32 over
    fmt-grid operands — the same contract as `dpa_decode_attn`, with the
    causal mask per request: row b attends key slots <= positions[b]
    (slots past a request's live length come from scratch/stale pages and
    are masked off here)."""
    from repro.core.kvcache import dequantize_kv, gather_paged_kv
    view = gather_paged_kv(cache)
    k = dequantize_kv(view["k_codes"], view["k_scale"], fmt=fmt_kv,
                      packed=kv_packed)
    v = dequantize_kv(view["v_codes"], view["v_scale"], fmt=fmt_kv,
                      packed=kv_packed)
    s_view = k.shape[1]
    pos = jnp.asarray(positions, jnp.int32)
    valid = jnp.arange(s_view)[None, :] <= pos[:, None]     # (B, S_view)
    mask = valid[:, None, None, :]
    return dpa_attention(q, k, v, mask, fmt=fmt, scale=scale,
                         kv_on_grid=True)


def dpa_paged_verify_attn(q, cache, positions, *, fmt: str, fmt_kv: str,
                          kv_packed: bool, scale):
    """Speculative-verify attention: S_q causal query tokens per request
    against the *paged* quantized KV cache.

    q: (B, S_q, H, hd) — a request's last accepted token followed by its
    draft tokens, already rope'd at per-request positions; cache: paged
    `repro.core.kvcache` pytree whose pools already hold the query rows
    (written by `paged_write_tokens`); positions: (B,) i32 — the absolute
    timeline index of query row 0.  Same contract as
    `dpa_paged_decode_attn`, generalized to S_q > 1 with a per-request
    *causal* mask: query row i of request b attends key slots <=
    positions[b] + i — exactly the chunked-prefill masking, applied to
    the block-table view — and row i reproduces BIT-FOR-BIT what a
    single-token decode step at position positions[b] + i would compute.
    That bit-identity is what makes greedy speculative decoding exact
    (`serving.spec_decode`): the verify pass's attention outputs ARE the
    plain decode path's.

    The exactness is engineered, not assumed: the (B, S_q) query axis
    folds into the batch axis, so every einsum in `dpa_attention` sees
    exactly the S_q == 1 decode shapes and XLA lowers the identical
    per-element reduction (an (S_q, S_kv) logits matmul would pick a
    different gemm tiling and drift by ulps — enough to flip a greedy
    argmax on near-tied logits).  The price is the gathered view
    repeated per query row, S_q x the decode step's HBM traffic — the
    verify pass amortizes it over k+1 scored tokens
    (`tests/test_spec_decode.py::test_verify_attn_matches_stepped_
    paged_decode` pins the bit-identity)."""
    from repro.core.kvcache import dequantize_kv, gather_paged_kv
    B, sq, H, hd = q.shape
    view = gather_paged_kv(cache)
    k = dequantize_kv(view["k_codes"], view["k_scale"], fmt=fmt_kv,
                      packed=kv_packed)
    v = dequantize_kv(view["v_codes"], view["v_scale"], fmt=fmt_kv,
                      packed=kv_packed)
    s_view = k.shape[1]
    pos = jnp.asarray(positions, jnp.int32)[:, None] \
        + jnp.arange(sq, dtype=jnp.int32)[None]             # (B, S_q)
    pos_r = pos.reshape(B * sq)
    valid = jnp.arange(s_view)[None, :] <= pos_r[:, None]   # (B*S_q, S_view)
    mask = valid[:, None, None, :]
    out = dpa_attention(q.reshape(B * sq, 1, H, hd),
                        jnp.repeat(k, sq, axis=0),
                        jnp.repeat(v, sq, axis=0), mask, fmt=fmt,
                        scale=scale, kv_on_grid=True)
    return out.reshape(B, sq, H, hd)


def _local_update(cache, new, offset, axis_name):
    """Write `new` (B,1,KV,hd) at global position `offset` into this
    device's sequence shard of `cache` (B, S_loc, KV, hd)."""
    idx = jax.lax.axis_index(axis_name)
    s_loc = cache.shape[1]
    local_off = offset - idx * s_loc
    in_range = (local_off >= 0) & (local_off < s_loc)
    off_c = jnp.clip(local_off, 0, s_loc - 1)
    z = jnp.zeros((), jnp.int32)
    written = jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype),
        (z, off_c.astype(jnp.int32), z, z))
    return jnp.where(in_range, written, cache)


def _flash_decode_body(q, k_new, v_new, kc, vc, offset, *, axis_name,
                       scale):
    """Per-shard body.  q: (B,1,H,hd); kc/vc: (B,S_loc,KV,hd) local shard.
    Returns (out (B,1,H,hd), kc', vc')."""
    B, _, H, hd = q.shape
    s_loc = kc.shape[1]
    KV = kc.shape[2]
    g = H // KV
    idx = jax.lax.axis_index(axis_name)

    kc = _local_update(kc, k_new, offset, axis_name)
    vc = _local_update(vc, v_new, offset, axis_name)

    kh = jnp.repeat(kc.astype(q.dtype), g, axis=2)       # (B,S_loc,H,hd)
    vh = jnp.repeat(vc.astype(q.dtype), g, axis=2)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kh,
                        preferred_element_type=jnp.float32) * scale
    pos = idx * s_loc + jnp.arange(s_loc)
    valid = pos <= offset                                 # causal
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)

    m_loc = jnp.max(logits, axis=-1)                      # (B,H,1)
    m_glob = jax.lax.pmax(m_loc, axis_name)
    p = jnp.exp(logits - m_glob[..., None])
    den = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)    # (B,H,1)
    num = jnp.einsum("bhqs,bshd->bqhd", p.astype(q.dtype), vh,
                     preferred_element_type=jnp.float32)
    num = jax.lax.psum(num, axis_name)                    # (B,1,H,hd)
    out = num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype), kc, vc


def flash_decode(q, k_new, v_new, k_cache, v_cache, offset, mesh,
                 *, scale):
    """shard_map wrapper: caches sequence-sharded on "model", everything
    else under auto SPMD."""
    axis = "model"
    body = partial(_flash_decode_body, axis_name=axis, scale=scale)
    in_specs = (P(), P(), P(), P(None, axis, None, None),
                P(None, axis, None, None), P())
    out_specs = (P(), P(None, axis, None, None),
                 P(None, axis, None, None))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, axis_names={axis},
                       check_vma=False)
    return fn(q, k_new, v_new, k_cache, v_cache,
              jnp.asarray(offset, jnp.int32))
