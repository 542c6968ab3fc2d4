"""Operations and bytes of one kernel call, from shapes alone.

These are the least work of each call under the serving policy,
independent of how a kernel happens to implement it and of the model
family; how many calls a model makes is its family's census
(`bench/families/<family>.py`):

  fp8 linear   (m, k) x (k, n): 2 m k n operations; fp8 weights (1 byte)
               with an f32 scale per output column, bf16 activations in
               and bf16 outputs out.
  paged decode one layer, one decode step: for each live request of
               context L, fp8 K and V (1 byte each) with an f32 scale per
               row and head, bf16 q in and out; 4 H hd L operations.
"""
from __future__ import annotations


def fp8_linear_call(m: int, k: int, n: int) -> tuple:
    """(operations, bytes) of one fp8 linear call."""
    return 2.0 * m * k * n, k * n + 4.0 * n + 2.0 * m * k + 2.0 * m * n


def paged_decode_layer(conf: dict, ctxs) -> tuple:
    """(operations, bytes) of one paged decode call (one layer) over
    live requests with contexts `ctxs`."""
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    rows = float(sum(ctxs))
    ops = 4.0 * H * hd * rows
    nbytes = rows * KV * (2 * hd + 2 * 4) + len(ctxs) * 2 * (2 * H * hd)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """Least time of work at the chip's peaks, and which bound sets it."""
    tc = ops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
