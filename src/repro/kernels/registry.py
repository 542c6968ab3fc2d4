"""The DPA routing table: every `core.exec_plan` route, in one place.

This module is imported lazily by `core.exec_plan` on first resolution
and registers one `PlanEntry` per (op, route): the Pallas kernel
pipelines from `kernels.ops` and the XLA/jnp reference fallbacks each
kernel is pinned against.  All policy-mode interpretation that used to
be scattered across `core.linear`, `models.layers`,
`models.decode_attn`, and `launch.engine` lives in the predicates here —
the FPnew-style operation-group hierarchy, as a table.

Route conventions:

  - predicates return *named* boolean bits (`describe()` shows them), so
    a failed resolution states exactly which gate excluded each route;
  - every op's lowest-priority route is a reference fallback whose
    predicate checks only semantic viability (it can always serve what
    the op means);
  - `reference`/`tol` pin each route against its fallback —
    `tests/test_exec_plan.py` enumerates the table and enforces the pin;
  - `tests` names the tier-1 tests exercising the route;
    `tools/plan_table.py` fails CI when a route names none.

Uniform run signatures per op:

  matmul          run(x, w, policy, **block_kw) -> (..., N)
  grouped_matmul  run(x, w, policy, *, eq) -> einsum output, x.dtype
  flash_attn      run(q, k, v, *, policy, causal, window, offset, valid,
                      scale, kv_on_grid) -> (B, Sq, H, hd)
  decode_attn     run(q, cache, offset, *, policy, scale) -> (B,1,H,hd)
  paged_decode    run(q, cache, positions, *, policy, scale) -> (B,1,H,hd)
  verify_attn     run(q, cache, positions, *, policy, scale) -> (B,Sq,H,hd)
  quantize_pack   run(x, *, fmt, pack, bm) -> (codes, scales)
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from repro.core import exec_plan
from repro.core.linear import GROUPED_EQS, NATIVE_NARROW
from repro.core.packing import operand_nbytes, pack_fp4_axis
from repro.core.quantize import cast_to, compute_scale, fake_quant
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import decode_attn as D


def _acc_t(policy):
    return jnp.float32 if policy.accum == "fp32" else jnp.float16


def _kv_fmt(policy):
    """fmt_kv the attention routes consume (None = quantize onto the
    attention grid, the raw-KV contract)."""
    return policy.fmt_kv if policy.kv_quantized else None


# -----------------------------------------------------------------------------
# matmul: x @ w under the DPA contract (core.linear.dpa_dot)
# -----------------------------------------------------------------------------

def _mm_native(x, w, policy, **_):
    # pre-quantized weights (serving): keep them NATIVE in the dot —
    # fp8 x fp8 -> fp32 is the MXU DPA path itself, and it leaves no
    # whole-stack weight convert for XLA to hoist out of the layer scan.
    sx = compute_scale(x, policy.fmt_acts, axis=-1)
    xq = cast_to(x.astype(jnp.float32) / sx, policy.fmt_acts)
    out = jnp.dot(xq, w, preferred_element_type=jnp.float32)
    return out * sx


def _mm_fake_quant(x, w, policy, **_):
    wq = fake_quant(
        w, policy.fmt_weights,
        axis=0 if policy.w_granularity == "per_channel" else None,
        block=policy.block_size if policy.w_granularity == "per_block"
        else None)
    xq = fake_quant(
        x, policy.fmt_acts,
        axis=-1 if policy.a_granularity == "per_channel" else None,
        block=policy.block_size if policy.a_granularity == "per_block"
        else None)
    return jnp.dot(xq, wq, preferred_element_type=_acc_t(policy))


def _mm_f32(x, w, policy, **_):
    return jnp.dot(x, w, preferred_element_type=_acc_t(policy))


def _mm_operand_bytes(policy, ctx):
    m, k, n = ctx.get("m"), ctx.get("k"), ctx.get("n")
    if not (m and k and n):
        return None
    return (operand_nbytes(m * k, policy.fmt_acts, packed=policy.packed)
            + operand_nbytes(k * n, policy.fmt_weights, packed=policy.packed))


exec_plan.register(
    "matmul", "xla_native_narrow", backend="xla", run=_mm_native,
    priority=40, reference="xla_fake_quant", tol=0.35,
    predicate=lambda policy, ctx: {
        "native_narrow_weights": ctx.get("w_dtype") in NATIVE_NARROW,
        "full_policy_path": not ctx.get("kernel_only", False)},
    tests=("tests/test_perf_features.py::test_native_fp8_weight_dot",),
    note="serving: weights stay in their narrow dtype end to end")

exec_plan.register(
    "matmul", "pallas_fused", backend="pallas",
    run=kops.dpa_matmul_fused_pipeline,
    priority=30, reference="xla_fake_quant", tol=0.35,
    predicate=lambda policy, ctx: {
        "kernel_path": policy.use_kernel or ctx.get("kernel_only", False),
        "fused_quant": policy.fused_quant,
        "float_weights": ctx.get("w_dtype") not in NATIVE_NARROW,
        "dpa_enabled": policy.enabled},
    bytes_moved=_mm_operand_bytes,
    tests=("tests/test_kernels.py::test_fused_quantize_matmul_vs_ref",
           "tests/test_kernels.py::test_packed_fused_policy_wrapper"),
    note="in-kernel activation quantize, per-(row, K-block) scales",
    knobs=("bm", "bk", "bn"))

exec_plan.register(
    "matmul", "pallas_prequant", backend="pallas",
    run=kops.dpa_matmul_prequant_pipeline,
    priority=25, reference="xla_fake_quant", tol=0.35,
    predicate=lambda policy, ctx: {
        "kernel_path": policy.use_kernel or ctx.get("kernel_only", False),
        "prequant": not policy.fused_quant,
        "float_weights": ctx.get("w_dtype") not in NATIVE_NARROW,
        "dpa_enabled": policy.enabled},
    bytes_moved=_mm_operand_bytes,
    tests=("tests/test_kernels.py::test_dpa_matmul_vs_ref",
           "tests/test_kernels.py::test_dpa_matmul_policy_wrapper_padding"),
    note="XLA quantize pass, packed fp4 operand bytes when policy.packed",
    knobs=("bm", "bk", "bn"))

exec_plan.register(
    "matmul", "xla_fake_quant", backend="xla", run=_mm_fake_quant,
    priority=10,
    predicate=lambda policy, ctx: {
        "dpa_enabled": policy.enabled,
        "full_policy_path": not ctx.get("kernel_only", False)},
    tests=("tests/test_dpa_property.py", "tests/test_layers.py"),
    note="training path: STE quant-dequant operands, wide accumulation")

exec_plan.register(
    "matmul", "xla_f32", backend="xla", run=_mm_f32, priority=0,
    predicate=lambda policy, ctx: {
        "full_policy_path": not ctx.get("kernel_only", False)},
    tests=("tests/test_layers.py", "tests/test_archs.py"),
    note="DPA disabled: the seed f32 datapath")


# -----------------------------------------------------------------------------
# grouped_matmul: per-expert einsums (grouped linear / MoE)
# -----------------------------------------------------------------------------

def _gmm_native(x, w, policy, *, eq):
    sx = compute_scale(x, policy.fmt_acts, axis=-1)
    xq = cast_to(x.astype(jnp.float32) / sx, policy.fmt_acts)
    y = jnp.einsum(eq, xq, w, preferred_element_type=jnp.float32) * sx
    return y.astype(x.dtype)


def _gmm_fake_quant(x, w, policy, *, eq):
    # quantize the *master* weights (no pre-cast through x.dtype — that
    # would double-round them) with the same granularity treatment as the
    # dense `_mm_fake_quant`; the stacked expert layout (E, d_in, d_out)
    # puts the contraction axis at 1 where dense has it at 0.
    wq = fake_quant(
        w, policy.fmt_weights,
        axis=1 if policy.w_granularity == "per_channel" else None,
        block=policy.block_size if policy.w_granularity == "per_block"
        else None)
    xq = fake_quant(
        x, policy.fmt_acts,
        axis=-1 if policy.a_granularity == "per_channel" else None,
        block=policy.block_size if policy.a_granularity == "per_block"
        else None)
    return jnp.einsum(eq, xq, wq,
                      preferred_element_type=_acc_t(policy)).astype(x.dtype)


def _gmm_f32(x, w, policy, *, eq):
    return jnp.einsum(eq, x, w.astype(x.dtype),
                      preferred_element_type=_acc_t(policy)).astype(x.dtype)


def _gmm_operand_bytes(policy, ctx):
    """Format-width operand bytes for the stacked per-expert matmuls —
    the dense `_mm_operand_bytes` with the expert count folded in.
    `dpa_grouped_dot` derives e/m/k/n from the einsum + shapes."""
    e, m, k, n = ctx.get("e"), ctx.get("m"), ctx.get("k"), ctx.get("n")
    if not (e and m and k and n):
        return None
    return (operand_nbytes(e * m * k, policy.fmt_acts, packed=policy.packed)
            + operand_nbytes(e * k * n, policy.fmt_weights,
                             packed=policy.packed))


def _gmm_wide_bytes(policy, ctx):
    """Both operand stacks traverse at full f32 width (fake-quant and the
    disabled path quantize — if at all — inside XLA, post-load)."""
    e, m, k, n = ctx.get("e"), ctx.get("m"), ctx.get("k"), ctx.get("n")
    if not (e and m and k and n):
        return None
    return 4 * (e * m * k + e * k * n)


def _gmm_native_bytes(policy, ctx):
    """Native-narrow expert weights move at format width (never packed:
    packing needs the kernel path's nibble decode); activations quantize
    to fmt_acts before the einsum."""
    e, m, k, n = ctx.get("e"), ctx.get("m"), ctx.get("k"), ctx.get("n")
    if not (e and m and k and n):
        return None
    return (operand_nbytes(e * m * k, policy.fmt_acts, packed=False)
            + operand_nbytes(e * k * n, policy.fmt_weights, packed=False))


exec_plan.register(
    "grouped_matmul", "xla_native_narrow", backend="xla", run=_gmm_native,
    priority=40, reference="xla_fake_quant", tol=0.35,
    predicate=lambda policy, ctx: {
        "native_narrow_weights": ctx.get("w_dtype") in NATIVE_NARROW},
    bytes_moved=_gmm_native_bytes,
    tests=("tests/test_exec_plan.py::test_route_pinned_to_reference",),
    note="pre-quantized expert weights stay native in the einsum")

exec_plan.register(
    "grouped_matmul", "pallas_grouped_fused", backend="pallas",
    run=kops.dpa_grouped_fused_pipeline,
    priority=30, reference="xla_fake_quant", tol=0.35,
    predicate=lambda policy, ctx: {
        "kernel_path": policy.use_kernel or ctx.get("kernel_only", False),
        "fused_quant": policy.fused_quant,
        "float_weights": ctx.get("w_dtype") not in NATIVE_NARROW,
        "known_grouped_eq": ctx.get("eq") in GROUPED_EQS,
        "dpa_enabled": policy.enabled},
    bytes_moved=_gmm_operand_bytes,
    tests=("tests/test_grouped_dpa.py::test_grouped_pipeline_vs_fake_quant",
           "tests/test_grouped_dpa.py::test_grouped_kernel_capacity_"
           "dropped_rows"),
    note="per-expert in-kernel activation quantize; packed fp4 expert "
         "weights move 8x fewer resident bytes",
    knobs=("bm", "bk", "bn"))

exec_plan.register(
    "grouped_matmul", "pallas_grouped_prequant", backend="pallas",
    run=kops.dpa_grouped_prequant_pipeline,
    priority=25, reference="xla_fake_quant", tol=0.35,
    predicate=lambda policy, ctx: {
        "kernel_path": policy.use_kernel or ctx.get("kernel_only", False),
        "prequant": not policy.fused_quant,
        "float_weights": ctx.get("w_dtype") not in NATIVE_NARROW,
        "known_grouped_eq": ctx.get("eq") in GROUPED_EQS,
        "dpa_enabled": policy.enabled},
    bytes_moved=_gmm_operand_bytes,
    tests=("tests/test_grouped_dpa.py::test_grouped_pipeline_vs_fake_quant",
           "tests/test_grouped_dpa.py::test_grouped_prequant_matches_"
           "dense_per_expert"),
    note="XLA quantize pass over both stacks; packed fp4 operand bytes "
         "when policy.packed",
    knobs=("bm", "bk", "bn"))

exec_plan.register(
    "grouped_matmul", "xla_fake_quant", backend="xla", run=_gmm_fake_quant,
    priority=10,
    predicate=lambda policy, ctx: {"dpa_enabled": policy.enabled},
    bytes_moved=_gmm_wide_bytes,
    tests=("tests/test_layers.py::test_moe_capacity_drop_and_combine_weights",
           "tests/test_grouped_dpa.py::test_gmm_fake_quant_matches_dense_"
           "reference"),
    note="per-expert STE quant-dequant, wide accumulation")

exec_plan.register(
    "grouped_matmul", "xla_f32", backend="xla", run=_gmm_f32, priority=0,
    bytes_moved=_gmm_wide_bytes,
    tests=("tests/test_layers.py::test_moe_uniform_router_is_lossless_at_high_capacity",),
    note="DPA disabled: plain grouped einsum")


# -----------------------------------------------------------------------------
# flash_attn: full-sequence attention (models.layers._sdpa)
# -----------------------------------------------------------------------------

def _fit_block(b, s):
    """Largest block <= b that divides the sequence length — tuned
    block shapes must never break the flash kernels' divisibility
    contract (Sq % bq == 0), whatever the sweep proposes."""
    b = max(1, min(b, s))
    while s % b:
        b -= 1
    return b


def _fa_pallas_dpa(q, k, v, *, policy, causal, window, offset, valid,
                   scale, kv_on_grid, bq=128, bk=128):
    out = kops.dpa_flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), fmt=policy.fmt_attn, fmt_kv=_kv_fmt(policy),
        causal=causal, window=window,
        bq=_fit_block(bq, q.shape[1]), bk=_fit_block(bk, k.shape[1]))
    return out.transpose(0, 2, 1, 3)


def _fa_pallas_f32(q, k, v, *, policy, causal, window, offset, valid,
                   scale, kv_on_grid, bq=128, bk=128):
    out = kops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        bq=_fit_block(bq, q.shape[1]), bk=_fit_block(bk, k.shape[1]))
    return out.transpose(0, 2, 1, 3)


def _fa_xla_dpa(q, k, v, *, policy, causal, window, offset, valid,
                scale, kv_on_grid):
    mask = D.build_sdpa_mask(q.shape[1], k.shape[1], offset, causal,
                             window, valid)
    return D.dpa_attention(q, k, v, mask[None, None], fmt=policy.fmt_attn,
                           fmt_kv=_kv_fmt(policy), scale=scale,
                           kv_on_grid=kv_on_grid)


def _fa_xla_ref(q, k, v, *, policy, causal, window, offset, valid,
                scale, kv_on_grid):
    mask = D.build_sdpa_mask(q.shape[1], k.shape[1], offset, causal,
                             window, valid)
    return D.sdpa_reference(q, k, v, mask[None, None], scale=scale)


def _fa_common_bits(policy, ctx):
    return {"flash_enabled": ctx.get("use_flash", False),
            "is_prefill": ctx.get("sq", 1) > 1,
            "no_valid_mask": not ctx.get("has_valid", False)}


exec_plan.register(
    "flash_attn", "pallas_dpa_flash", backend="pallas", run=_fa_pallas_dpa,
    priority=30, reference="xla_dpa_attn", tol=0.075,
    predicate=lambda policy, ctx: dict(
        _fa_common_bits(policy, ctx),
        dpa_attn=policy.attn_enabled,
        raw_kv=not ctx.get("kv_on_grid", False)),
    tests=("tests/test_attention_dpa.py::test_dpa_flash_attention_vs_spec",
           "tests/test_exec_plan.py::test_route_pinned_to_reference"),
    note="online-softmax tiling; tol vs the global-softmax jnp fallback "
         "is the blocked-p-quantization budget test_attention_dpa pins",
    knobs=("bq", "bk"))

exec_plan.register(
    "flash_attn", "pallas_f32_flash", backend="pallas", run=_fa_pallas_f32,
    priority=20, reference="xla_ref_attn", tol=2e-6,
    predicate=lambda policy, ctx: dict(
        _fa_common_bits(policy, ctx), f32_attn=not policy.attn_enabled),
    tests=("tests/test_kernels.py::test_flash_attention_vs_ref",),
    note="the seed f32 flash kernel", knobs=("bq", "bk"))

exec_plan.register(
    "flash_attn", "xla_dpa_attn", backend="xla", run=_fa_xla_dpa,
    priority=10,
    predicate=lambda policy, ctx: {"dpa_attn": policy.attn_enabled},
    tests=("tests/test_attention_dpa.py::test_jnp_fallback_matches_single_block_spec",),
    note="any-shape jnp DPA attention (global softmax max)")

exec_plan.register(
    "flash_attn", "xla_ref_attn", backend="xla", run=_fa_xla_ref, priority=0,
    tests=("tests/test_layers.py", "tests/test_archs.py"),
    note="reference einsum + f32 softmax (the seed datapath)")


# -----------------------------------------------------------------------------
# decode_attn: single-token decode over the contiguous quantized cache
# -----------------------------------------------------------------------------

def _da_xla(q, cache, offset, *, policy, scale):
    return D.dpa_decode_attn(q, cache, offset, fmt=policy.fmt_attn,
                             fmt_kv=policy.fmt_kv,
                             kv_packed=policy.kv_packed, scale=scale)


def _kv_rows_bytes(policy, n_rows, hd):
    """codes + f32 scales for K AND V over n_rows cache rows."""
    return 2 * (operand_nbytes(n_rows * hd, policy.fmt_kv,
                               packed=policy.kv_packed) + 4 * n_rows)


exec_plan.register(
    "decode_attn", "xla_dpa_decode", backend="xla", run=_da_xla, priority=0,
    predicate=lambda policy, ctx: {"kv_quantized": policy.kv_quantized},
    bytes_moved=lambda policy, ctx: _kv_rows_bytes(
        policy, ctx.get("batch", 1) * ctx.get("s_ctx", 0)
        * ctx.get("kv_heads", 1), ctx.get("hd", 0)),
    tests=("tests/test_attention_dpa.py::"
           "test_model_prefill_matches_stepped_decode",),
    note="prologue-dequant decode off the contiguous codes+scales cache")


# -----------------------------------------------------------------------------
# paged_decode: single-token decode over the paged cache (block table)
# -----------------------------------------------------------------------------

def _pd_pallas(q, cache, positions, *, policy, scale):
    return kops.paged_decode_attention(q, cache, positions,
                                       fmt=policy.fmt_attn,
                                       fmt_kv=policy.fmt_kv,
                                       kv_packed=policy.kv_packed,
                                       scale=scale)


def _pd_gather(q, cache, positions, *, policy, scale):
    return D.dpa_paged_decode_attn(q, cache, positions, fmt=policy.fmt_attn,
                                   fmt_kv=policy.fmt_kv,
                                   kv_packed=policy.kv_packed, scale=scale)


def _pd_view_rows(ctx):
    """Cache rows one batched decode step streams: every slot's full
    block-table window (B x max_pages x page rows, per KV head)."""
    return (ctx.get("batch", 1) * ctx.get("max_pages", 0)
            * ctx.get("page_size", 0) * ctx.get("kv_heads", 1))


exec_plan.register(
    "paged_decode", "pallas_block_table", backend="pallas", run=_pd_pallas,
    priority=10, reference="jnp_gather", tol=0.0,
    predicate=lambda policy, ctx: {
        "kv_quantized": policy.kv_quantized,
        "not_disabled": os.environ.get("REPRO_PAGED_KERNEL", "1") != "0"},
    bytes_moved=lambda policy, ctx: _kv_rows_bytes(
        policy, _pd_view_rows(ctx), ctx.get("hd", 0)),
    tests=("tests/test_exec_plan.py::test_paged_decode_kernel_bit_identical",
           "tests/test_engine.py::test_engine_matches_static_batch_"
           "per_request"),
    note="BlockSpec index maps read pages through the scalar-prefetched "
         "block table; codes+scales stream HBM->VMEM exactly once")

exec_plan.register(
    "paged_decode", "jnp_gather", backend="xla", run=_pd_gather, priority=0,
    predicate=lambda policy, ctx: {"kv_quantized": policy.kv_quantized},
    bytes_moved=lambda policy, ctx: 3 * _kv_rows_bytes(
        policy, _pd_view_rows(ctx), ctx.get("hd", 0)),
    tests=("tests/test_paged_kv.py::test_paged_decode_attn_matches_"
           "contiguous",),
    note="gather_paged_kv re-materializes the contiguous view in HBM "
         "(write + re-read: ~3x the page-pool traffic)")


# -----------------------------------------------------------------------------
# verify_attn: S_q causal query tokens scored against the paged cache
# (the speculative-decoding verify pass; see serving.spec_decode)
# -----------------------------------------------------------------------------

def _va_gather(q, cache, positions, *, policy, scale):
    return D.dpa_paged_verify_attn(q, cache, positions, fmt=policy.fmt_attn,
                                   fmt_kv=policy.fmt_kv,
                                   kv_packed=policy.kv_packed, scale=scale)


exec_plan.register(
    "verify_attn", "jnp_gather", backend="xla", run=_va_gather, priority=0,
    predicate=lambda policy, ctx: {"kv_quantized": policy.kv_quantized},
    # gather re-materializes the view (read pages + write + re-read, the
    # jnp_gather 3x), then the batch-fold repeats it per query row
    # (write + attention read: 2 more view passes per sq) — the price of
    # the bit-exact decode-shaped reductions, amortized over k+1 scored
    # tokens
    bytes_moved=lambda policy, ctx: (3 + 2 * ctx.get("sq", 1))
    * _kv_rows_bytes(policy, _pd_view_rows(ctx), ctx.get("hd", 0)),
    tests=("tests/test_spec_decode.py::test_verify_attn_matches_stepped_"
           "paged_decode",
           "tests/test_spec_decode.py::test_spec_engine_greedy_matches_"
           "plain_engine"),
    note="speculative verify: per-request causal mask over the gathered "
         "block-table view (chunked-prefill masking, paged pool); row i "
         "is bit-identical to a decode step at positions[b] + i")


# -----------------------------------------------------------------------------
# sharded paged attention: the pool lives 1/n per device ("model" axis,
# within-page rows — the cache_spec rule), the wire carries format-width
# codes + per-row scales, and the reassembled pool runs the exact
# single-device op (the block-table kernel for decode, so tp=N runs what
# tp=1 runs on every backend).  Bit-identical to single-device serving by
# construction: no cross-device float reduction touches the softmax.
# -----------------------------------------------------------------------------

def _pd_sharded(q, cache, positions, *, policy, scale):
    from functools import partial

    from repro.distributed import tp as TP
    fn = partial(_pd_pallas, policy=policy, scale=scale)
    return TP.sharded_paged_attn(fn, q, cache, positions)


def _va_sharded(q, cache, positions, *, policy, scale):
    from functools import partial

    from repro.distributed import tp as TP
    fn = partial(D.dpa_paged_verify_attn, fmt=policy.fmt_attn,
                 fmt_kv=policy.fmt_kv, kv_packed=policy.kv_packed,
                 scale=scale)
    return TP.sharded_paged_attn(fn, q, cache, positions)


def _pool_rows(ctx):
    """Rows in the whole page pool (what the all-gather moves)."""
    return (ctx.get("n_pages", 0) * ctx.get("page_size", 0)
            * ctx.get("kv_heads", 1))


def _tp_wire_bytes(policy, ctx):
    """Bytes-on-wire per device for the pool all-gather: each device
    receives the other (n-1)/n of the pool as codes + per-row scales —
    the same 2x/4x/8x under an f32 wire the cache bytes enjoy."""
    n = ctx.get("n_devices", 1)
    if n <= 1:
        return 0
    return int((n - 1) / n
               * _kv_rows_bytes(policy, _pool_rows(ctx), ctx.get("hd", 0)))


exec_plan.register(
    "paged_decode", "paged_decode_sharded", backend="pallas",
    run=_pd_sharded, priority=20, reference="jnp_gather", tol=0.0,
    predicate=lambda policy, ctx: {
        "kv_quantized": policy.kv_quantized,
        "multi_device": ctx.get("n_devices", 1) > 1},
    # block-table kernel bytes + the wire term the plan now prices
    bytes_moved=lambda policy, ctx: _kv_rows_bytes(
        policy, _pd_view_rows(ctx), ctx.get("hd", 0))
    + _tp_wire_bytes(policy, ctx),
    tests=("tests/test_tp_engine.py::test_tp_engine_bit_identical_"
           "across_formats",
           "tests/test_tp_engine.py::test_tp_prefix_and_spec_decode_"
           "bit_identical"),
    note="shard_map over the \"model\" axis: all-gather pool shards at "
         "format width (pure relayout), then the single-device block-"
         "table kernel — tp=N decodes exactly as tp=1")

exec_plan.register(
    "verify_attn", "verify_attn_sharded", backend="xla", run=_va_sharded,
    priority=10, reference="jnp_gather", tol=0.0,
    predicate=lambda policy, ctx: {
        "kv_quantized": policy.kv_quantized,
        "multi_device": ctx.get("n_devices", 1) > 1},
    bytes_moved=lambda policy, ctx: (3 + 2 * ctx.get("sq", 1))
    * _kv_rows_bytes(policy, _pd_view_rows(ctx), ctx.get("hd", 0))
    + _tp_wire_bytes(policy, ctx),
    tests=("tests/test_tp_engine.py::test_tp_prefix_and_spec_decode_"
           "bit_identical",),
    note="sharded speculative verify: same pool all-gather wire, same "
         "bit-exact batch-fold body as the jnp_gather reference")


# -----------------------------------------------------------------------------
# quantize_pack: fused row quantization (+fp4 nibble pack)
# -----------------------------------------------------------------------------

def _qp_pallas(x, *, fmt, pack, bm):
    return kops.quantize_rows_pallas(x, fmt=fmt, pack=pack, bm=bm)


def _qp_xla(x, *, fmt, pack, **_):
    # swallows bm: the reference quantizer has no tiling to tune
    q, s = kref.quantize_rows_ref(x, fmt=fmt)
    if pack:
        q = pack_fp4_axis(q, 1)
    return q, s


exec_plan.register(
    "quantize_pack", "pallas_quantize_pack", backend="pallas",
    run=_qp_pallas, priority=20, reference="xla_quantize", tol=1e-6,
    predicate=lambda policy, ctx: {"fp4": ctx.get("fmt") == "fp4_e2m1",
                                   "pack": ctx.get("pack", False)},
    tests=("tests/test_kernels.py::test_quantize_pack_rows_matches_unpacked",),
    note="absmax -> E2M1 cast -> nibble pack, one kernel",
    knobs=("bm",))

exec_plan.register(
    "quantize_pack", "pallas_quantize_rows", backend="pallas",
    run=_qp_pallas, priority=10, reference="xla_quantize", tol=1e-6,
    predicate=lambda policy, ctx: {"unpacked": not ctx.get("pack", False)},
    tests=("tests/test_kernels.py::test_quantize_rows_vs_ref",),
    note="fused absmax + cast row quantizer", knobs=("bm",))

exec_plan.register(
    "quantize_pack", "xla_quantize", backend="xla", run=_qp_xla, priority=0,
    predicate=lambda policy, ctx: {
        "pack_needs_fp4": (not ctx.get("pack", False))
        or ctx.get("fmt") == "fp4_e2m1"},
    tests=("tests/test_kernels.py::test_quantize_rows_vs_ref",),
    note="jnp reference quantizer (+XLA nibble pack)")


# -----------------------------------------------------------------------------
# allreduce: gradient/partial reduction across a mesh axis (shard_map
# body).  run(grad, err, *, axis_name, fmt_name) -> (mean, new_err)
# -----------------------------------------------------------------------------

def _ar_wire(grad, err, *, axis_name, fmt_name):
    from repro.distributed.collectives import ef_compress_allreduce
    return ef_compress_allreduce(grad, err, axis_name, fmt_name)


def _ar_psum(grad, err, *, axis_name, fmt_name):
    import jax
    return (jax.lax.pmean(grad.astype(jnp.float32), axis_name),
            jnp.zeros_like(err, dtype=jnp.float32))


def _wire_fmt_bytes(ctx, default_bits=32):
    from repro.core.formats import get_format
    fmt = ctx.get("wire_fmt")
    bits = get_format(fmt).bits if fmt else default_bits
    return ctx.get("size", 0) * bits // 8


exec_plan.register(
    "allreduce", "wire_compressed", backend="xla", run=_ar_wire,
    priority=10, reference="xla_psum_f32", tol=0.1,
    predicate=lambda policy, ctx: {
        "multi_device": ctx.get("n_devices", 1) > 1,
        "wire_fmt": ctx.get("wire_fmt") is not None},
    bytes_moved=lambda policy, ctx: _wire_fmt_bytes(ctx) + 4,
    tests=("tests/test_distributed.py::"
           "test_compressed_allreduce_error_feedback",
           "tests/test_tp_engine.py::test_wire_collectives_parity",),
    note="error-feedback all-gather at wire-format width, f32 "
         "accumulation (the DPA contract on the slow axis); tol is the "
         "fp8 wire's quantization noise, killed over steps by the "
         "residual feedback")

exec_plan.register(
    "allreduce", "xla_psum_f32", backend="xla", run=_ar_psum, priority=0,
    predicate=lambda policy, ctx: {},
    bytes_moved=lambda policy, ctx: 4 * ctx.get("size", 0),
    tests=("tests/test_distributed.py::"
           "test_compressed_allreduce_error_feedback",),
    note="plain f32 psum-mean (4 bytes/element on the wire); also the "
         "identity on a size-1 axis")


# -----------------------------------------------------------------------------
# unembed: logits over the (tied) vocab table.  run(x, table, policy)
# -> (B, S, V) f32-accumulated
# -----------------------------------------------------------------------------

def _ue_xla(x, table, policy):
    return jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype),
                      preferred_element_type=jnp.float32)


exec_plan.register(
    "unembed", "xla_tied_table", backend="xla", run=_ue_xla, priority=0,
    predicate=lambda policy, ctx: {},
    bytes_moved=lambda policy, ctx: 4 * ctx.get("size", 0),
    tests=("tests/test_layers.py", "tests/test_archs.py"),
    note="fp32-accumulation logits over the transposed embedding table; "
         "the narrow-format story deliberately stops before the unembed "
         "(quality), so the only route is the wide reference")
