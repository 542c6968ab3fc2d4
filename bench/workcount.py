"""Operations and bytes the served work needs, from shapes alone.

The model census follows the arithmetic of the program's
`launch/analytic.py` for a dense decoder (copied here so that no later
change to the program can move the benchmark's yardstick): per token,
the seven linears of each layer, attention over the token's context
(QK^T and PV), and one unembedding per token that yields an output.

The kernel counts are the least work of each call under the serving
policy, independent of how a kernel happens to implement it:

  fp8 linear   (m, k) x (k, n): 2 m k n operations; fp8 weights (1 byte)
               with an f32 scale per output column, bf16 activations in
               and bf16 outputs out.
  paged decode one layer, one decode step: for each live request of
               context L, fp8 K and V (1 byte each) with an f32 scale per
               row and head, bf16 q in and out; 4 H hd L operations.
"""
from __future__ import annotations


def dims(conf: dict) -> dict:
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "KV": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "ff": conf["intermediate_size"], "V": conf["vocab_size"]}


def linear_shapes(conf: dict) -> list:
    """(k, n) of each linear of one layer: q, k, v, o, gate, up, down."""
    m = dims(conf)
    d, q, kv, ff = m["d"], m["H"] * m["hd"], m["KV"] * m["hd"], m["ff"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def linear_flops_per_token(conf: dict) -> float:
    return 2.0 * dims(conf)["L"] * sum(k * n for k, n in linear_shapes(conf))


def attn_flops(conf: dict, ctx: int) -> float:
    """QK^T and PV of one token attending over `ctx` positions, all
    layers."""
    m = dims(conf)
    return 4.0 * m["L"] * m["H"] * m["hd"] * ctx


def unembed_flops(conf: dict) -> float:
    m = dims(conf)
    return 2.0 * m["d"] * m["V"]


def step_flops(conf: dict, step: dict) -> float:
    """Model operations of one scheduler step from its record: prefill
    segments (start, n) -- each token at position p attends over p + 1 --
    decode contexts, and one unembedding per output token."""
    lin = linear_flops_per_token(conf)
    total = 0.0
    for start, n in step["prefill"]:
        # sum of (p + 1) over p in [start, start + n)
        ctx_sum = n * start + n * (n + 1) / 2.0
        total += n * lin + attn_flops(conf, 1) * ctx_sum
    for ctx in step["decode_ctx"]:
        total += lin + attn_flops(conf, ctx)
    n_out = len(step["decode_ctx"]) + step["firsts"]
    return total + n_out * unembed_flops(conf)


def fp8_linear_call(m: int, k: int, n: int) -> tuple:
    """(operations, bytes) of one fp8 linear call."""
    return 2.0 * m * k * n, k * n + 4.0 * n + 2.0 * m * k + 2.0 * m * n


def paged_decode_layer(conf: dict, ctxs) -> tuple:
    """(operations, bytes) of one paged decode call (one layer) over
    live requests with contexts `ctxs`."""
    m = dims(conf)
    H, KV, hd = m["H"], m["KV"], m["hd"]
    rows = float(sum(ctxs))
    ops = 4.0 * H * hd * rows
    nbytes = rows * KV * (2 * hd + 2 * 4) + len(ctxs) * 2 * (2 * H * hd)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """Least time of work at the chip's peaks, and which bound sets it."""
    tc = ops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
