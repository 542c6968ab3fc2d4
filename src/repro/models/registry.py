"""Model registry: builds (init, train_logits, prefill, decode_step) for a
ModelConfig across all five families.

Batch conventions
-----------------
train:   {"tokens": (B,S) i32} or {"embeddings": (B,S,d)} (+ encdec:
         {"frames": (B,Se,d), "tokens": (B,S)}), plus "labels" handled by
         the loss in repro.distributed.step.
prefill: same inputs; returns (logits_last, caches).
decode:  {"tokens": (B,1), "index": scalar i32, "caches": pytree}
         (+ encdec: {"enc_out": (B,Se,d)}); returns (logits, caches).
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig
from .transformer import (apply_stack, family_pattern, init_stack,
                          _stack_caches)


def _dtype(cfg):
    return {"float32": jnp.float32, "bf16": jnp.bfloat16,
            "bfloat16": jnp.bfloat16, "fp16": jnp.float16}[cfg.dtype]


def _embed_in(params, batch, cfg):
    dt = _dtype(cfg)
    if "embeddings" in batch:
        return batch["embeddings"].astype(dt)
    return L.apply_embedding(params["embed"], batch["tokens"], dt)


def _unembed(params, x, cfg):
    table = params["embed"]["table"] if cfg.tie_embeddings \
        else params["unembed"]["table"]
    return L.apply_unembed(None, x, table=table)


def serving_init(model, sharding=None):
    """Jitted ``key -> params`` for serving: `model.init` with every
    floating leaf cast to the compute dtype inside the jit, so the f32
    init is never live whole on the device (qwen3-4b: about 16 GB in
    f32, 8 GB in bf16).  `sharding` places the result, e.g. replicated
    on a tensor-parallel mesh; `jax.eval_shape` of the returned function
    gives the serving param shapes without touching a device."""
    dt = _dtype(model.cfg)

    def init(key):
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, model.init(key))

    return jax.jit(init, out_shardings=sharding)


def build_model(cfg: ModelConfig) -> SimpleNamespace:
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    pattern = family_pattern(cfg)

    def init(key):
        ks = jax.random.split(key, 4)
        params = {"stack": init_stack(ks[0], cfg, pattern, cfg.n_layers),
                  "norm_f": L.init_norm(cfg.d_model, cfg.norm)}
        if cfg.frontend == "none" or cfg.family == "vlm":
            params["embed"] = L.init_embedding(ks[1], cfg.vocab_size,
                                               cfg.d_model)
        if not cfg.tie_embeddings:
            params["unembed"] = L.init_embedding(ks[2], cfg.vocab_size,
                                                 cfg.d_model)
        return params

    def backbone(params, x, *, offset=0, caches=None, collect=False,
                 s_ctx=None):
        x, nc, aux = apply_stack(params["stack"], x, cfg, pattern,
                                 offset=offset, caches=caches,
                                 collect_cache=collect, s_ctx=s_ctx)
        x = L.apply_norm(params["norm_f"], x, eps=cfg.norm_eps)
        return x, nc, aux

    def backbone_features(params, batch):
        """Final hidden states before unembedding (chunked-loss path)."""
        x = _embed_in(params, batch, cfg)
        x, _, aux = backbone(params, x)
        return x, aux

    def train_logits(params, batch):
        x, aux = backbone_features(params, batch)
        return _unembed(params, x, cfg), aux

    def prefill(params, batch):
        x = _embed_in(params, batch, cfg)
        x, caches, _ = backbone(params, x, collect=True, s_ctx=x.shape[1])
        return _unembed(params, x[:, -1:], cfg), caches

    def init_caches(batch_size: int, s_ctx: int):
        return _stack_caches(cfg, pattern, cfg.n_layers, batch_size, s_ctx,
                             _dtype(cfg))

    def decode_step(params, batch, caches):
        x = _embed_in(params, batch, cfg)
        x, caches, _ = backbone(params, x, offset=batch["index"],
                                caches=caches)
        return _unembed(params, x, cfg), caches

    return SimpleNamespace(cfg=cfg, init=init, train_logits=train_logits,
                           prefill=prefill, decode_step=decode_step,
                           init_caches=init_caches,
                           backbone_features=backbone_features)


# -----------------------------------------------------------------------------
# encoder-decoder (whisper-style)
# -----------------------------------------------------------------------------

def _build_encdec(cfg: ModelConfig):
    enc_pat, dec_pat = ("enc",), ("dec",)
    n_enc = cfg.n_enc_layers or cfg.n_layers

    def init(key):
        ks = jax.random.split(key, 6)
        params = {
            "enc_stack": init_stack(ks[0], cfg, enc_pat, n_enc),
            "dec_stack": init_stack(ks[1], cfg, dec_pat, cfg.n_layers),
            "enc_norm": L.init_norm(cfg.d_model, cfg.norm),
            "norm_f": L.init_norm(cfg.d_model, cfg.norm),
            "embed": L.init_embedding(ks[2], cfg.vocab_size, cfg.d_model),
            "pos_dec": jax.random.normal(ks[3], (cfg.max_seq, cfg.d_model),
                                         jnp.float32) * 0.01,
        }
        if not cfg.tie_embeddings:
            params["unembed"] = L.init_embedding(ks[4], cfg.vocab_size,
                                                 cfg.d_model)
        return params

    def encode(params, frames):
        x, _, _ = apply_stack(params["enc_stack"], frames.astype(_dtype(cfg)),
                              cfg, enc_pat)
        return L.apply_norm(params["enc_norm"], x, eps=cfg.norm_eps)

    def _dec_embed(params, tokens, index):
        dt = _dtype(cfg)
        x = L.apply_embedding(params["embed"], tokens, dt)
        pos = params["pos_dec"].astype(dt)
        S = tokens.shape[1]
        p = jax.lax.dynamic_slice_in_dim(pos, index, S, 0) if S == 1 \
            else pos[:S]
        return x + p[None]

    def backbone_features(params, batch):
        enc_out = encode(params, batch["frames"])
        x = _dec_embed(params, batch["tokens"], 0)
        x, _, aux = apply_stack(params["dec_stack"], x, cfg, dec_pat,
                                enc_out=enc_out)
        return L.apply_norm(params["norm_f"], x, eps=cfg.norm_eps), aux

    def train_logits(params, batch):
        x, aux = backbone_features(params, batch)
        return _unembed(params, x, cfg), aux

    def prefill(params, batch):
        enc_out = encode(params, batch["frames"])
        x = _dec_embed(params, batch["tokens"], 0)
        x, caches, _ = apply_stack(params["dec_stack"], x, cfg, dec_pat,
                                   enc_out=enc_out, collect_cache=True,
                                   s_ctx=x.shape[1])
        x = L.apply_norm(params["norm_f"], x, eps=cfg.norm_eps)
        return _unembed(params, x[:, -1:], cfg), caches

    def init_caches(batch_size: int, s_ctx: int):
        return _stack_caches(cfg, dec_pat, cfg.n_layers, batch_size, s_ctx,
                             _dtype(cfg))

    def decode_step(params, batch, caches):
        x = _dec_embed(params, batch["tokens"], batch["index"])
        x, caches, _ = apply_stack(params["dec_stack"], x, cfg, dec_pat,
                                   offset=batch["index"], caches=caches,
                                   enc_out=batch["enc_out"].astype(_dtype(cfg)))
        x = L.apply_norm(params["norm_f"], x, eps=cfg.norm_eps)
        return _unembed(params, x, cfg), caches

    return SimpleNamespace(cfg=cfg, init=init, train_logits=train_logits,
                           prefill=prefill, decode_step=decode_step,
                           init_caches=init_caches, encode=encode,
                           backbone_features=backbone_features)
