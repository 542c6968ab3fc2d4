"""The dense decoder family: Qwen3- and Mistral-like decoder-only stacks
in which every layer is alike (GQA attention, SwiGLU MLP).

A family module turns a configuration file (`bench/configs/<name>.json`,
Hugging Face keys) into what the harness needs of it:

  model_config(conf, name)  the program's `ModelConfig` to serve it with;
  step_flops(conf, step)    model operations of one scheduler step;
  fused_linears(conf)       [(k, n, layers)]: each linear that runs on the
                            fused fp8 kernel (`dpa_matmul_fused`), and how
                            many layers call it in one model call;
  paged_layers(conf)        layers that call the paged decode kernel in
                            one decode step.

The census follows the arithmetic of the program's `launch/analytic.py`
for a dense decoder (copied here so that no later change to the program
can move the benchmark's yardstick): per token, the seven linears of
each layer, attention over the token's context (QK^T and PV), and one
unembedding per token that yields an output.  Only `model_config`
imports the program.
"""
from __future__ import annotations


def model_config(conf: dict, name: str):
    """The program's `ModelConfig` for a configuration file: published
    sizes (as run) and the execution settings the repository serves
    dense decoders with."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=name, family="decoder",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=bool(conf["qk_norm"]), rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        max_seq=conf["max_position_embeddings"],
        dtype="bf16", policy=conf["policy"], remat="full", attn_chunk=512,
        logits_chunk=512)


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


def fused_linears(conf: dict) -> list:
    """Every layer's seven linears run on the fused fp8 kernel."""
    L = dims(conf)["L"]
    return [(k, n, L) for k, n in linear_shapes(conf)]


def paged_layers(conf: dict) -> int:
    """Every layer attends through the paged decode kernel."""
    return dims(conf)["L"]
