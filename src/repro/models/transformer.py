"""Model stacks: pattern-scanned blocks covering all five families.

A family is a repeating block *pattern* (decoder: ("attn",); RecurrentGemma:
("rg","rg","attn_local"); xLSTM: 7x"mlstm"+1x"slstm"; enc-dec: two uniform
stacks).  Layers are grouped into `n_layers // len(pattern)` scan groups with
stacked params (HLO stays O(1) in depth); remainder layers run as an
unscanned tail.  Caches/states thread through the scan for prefill/decode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import maybe_shard

from . import layers as L
from .config import ModelConfig

# -----------------------------------------------------------------------------
# blocks
# -----------------------------------------------------------------------------

ATTN_KINDS = ("attn", "attn_local", "enc", "dec")


def init_block(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {"norm1": L.init_norm(d, cfg.norm)}
    if kind in ATTN_KINDS:
        p["attn"] = L.init_attention(ks[0], cfg)
    elif kind == "rg":
        p["rg"] = L.init_rglru(ks[0], cfg)
    elif kind == "mlstm":
        p["mix"] = L.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["mix"] = L.init_slstm(ks[0], cfg)
    else:
        raise ValueError(kind)
    if kind == "dec":
        p["norm_cross"] = L.init_norm(d, cfg.norm)
        p["cross"] = L.init_attention(ks[1], cfg)
    if cfg.d_ff > 0:
        p["norm2"] = L.init_norm(d, cfg.norm)
        p["mlp"] = L.init_moe(ks[2], cfg) if cfg.is_moe else L.init_mlp(ks[2], cfg)
    return p


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, s_ctx: int,
                     dtype):
    """Structural cache for one block (decode mode).

    Full-context attention caches honor the policy's KV bits: with
    fmt_kv set they are `repro.core.kvcache` pytrees (codes + per-row
    scales at format width) instead of raw compute-dtype tensors.
    Sliding-window caches stay raw — the shift-left update would have to
    roll codes and scales in lockstep for no bandwidth story (the window
    bounds the cache at W tokens already).
    """
    hd = cfg.hd
    if kind in ("attn", "dec"):
        from repro.core.policy import get_policy
        pol = get_policy(cfg.policy)
        if pol.kv_quantized:
            from repro.core.kvcache import init_kv_cache
            return init_kv_cache(batch, s_ctx, cfg.n_kv_heads, hd,
                                 fmt=pol.fmt_kv, packed=pol.kv_packed)
        shp = (batch, s_ctx, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
    if kind == "attn_local":
        w = min(cfg.window or s_ctx, s_ctx)
        shp = (batch, w, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
    if kind == "rg":
        dr = cfg.d_rnn or cfg.d_model
        return {"h": jnp.zeros((batch, dr), jnp.float32),
                "conv": jnp.zeros((batch, cfg.conv_width - 1, dr), dtype)}
    if kind == "mlstm":
        H = cfg.n_heads
        return (jnp.zeros((batch, H, hd, hd), jnp.float32),
                jnp.zeros((batch, H, hd), jnp.float32),
                jnp.zeros((batch, H), jnp.float32))
    if kind == "slstm":
        d = cfg.d_model
        z = jnp.zeros((batch, d), jnp.float32)
        return (z, z, z, z - 10.0)
    if kind == "enc":
        return ()
    raise ValueError(kind)


def apply_block(params, x, cfg: ModelConfig, kind: str, *, offset=0,
                cache=None, enc_out=None):
    """-> (x', new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(params["norm1"], x, eps=cfg.norm_eps)
    new_cache = cache
    if kind in ATTN_KINDS:
        window = cfg.window if kind == "attn_local" else None
        y, new_cache = L.apply_attention(
            params["attn"], h, cfg, offset=offset, cache=cache,
            window=window, causal=(kind != "enc"),
            use_rope=(cfg.rope_theta > 0),
            cache_mode="window" if kind == "attn_local" else "full")
    elif kind == "rg":
        y, new_cache = L.apply_rglru(params["rg"], h, cfg, state=cache)
    elif kind == "mlstm":
        y, new_cache = L.apply_mlstm(params["mix"], h, cfg, state=cache)
    elif kind == "slstm":
        y, new_cache = L.apply_slstm(params["mix"], h, cfg, state=cache)
    x = x + y.astype(x.dtype)
    if kind == "dec":
        from repro.core.linear import apply_linear
        from repro.core.policy import get_policy
        pol = get_policy(cfg.policy)
        h = L.apply_norm(params["norm_cross"], x, eps=cfg.norm_eps)
        B, Se = enc_out.shape[0], enc_out.shape[1]
        kc = apply_linear(params["cross"]["wk"], enc_out, pol).reshape(
            B, Se, cfg.n_kv_heads, cfg.hd)
        vc = apply_linear(params["cross"]["wv"], enc_out, pol).reshape(
            B, Se, cfg.n_kv_heads, cfg.hd)
        y, _ = L.apply_attention(params["cross"], h, cfg,
                                 cross_kv={"k": kc, "v": vc},
                                 causal=False, use_rope=False)
        x = x + y.astype(x.dtype)
    if cfg.d_ff > 0:
        h = L.apply_norm(params["norm2"], x, eps=cfg.norm_eps)
        if cfg.is_moe:
            y, aux = L.apply_moe(params["mlp"], h, cfg)
        else:
            y = L.apply_mlp(params["mlp"], h, cfg)
        x = x + y.astype(x.dtype)
    return x, new_cache, aux


# -----------------------------------------------------------------------------
# pattern stack (scan over groups)
# -----------------------------------------------------------------------------

def family_pattern(cfg: ModelConfig):
    if cfg.family in ("decoder", "vlm", "moe"):
        return ("attn",)
    if cfg.family == "rglru":
        return tuple(cfg.pattern) or ("rg", "rg", "attn_local")
    if cfg.family == "xlstm":
        n = cfg.slstm_every or 8
        return ("mlstm",) * (n - 1) + ("slstm",)
    raise ValueError(cfg.family)


def init_stack(key, cfg: ModelConfig, pattern, n_layers: int):
    """Group-stacked block params: pattern slot i of group g draws from
    layer key g * len(pattern) + i.  One vmapped init per slot builds the
    stack directly (no per-layer copies beside it)."""
    P = len(pattern)
    n_groups, tail = divmod(n_layers, P)
    keys = jax.random.split(key, n_layers + 1)
    groups = {
        f"p{i}": jax.vmap(lambda k, kind=kind: init_block(k, cfg, kind))(
            keys[i:n_groups * P:P])
        for i, kind in enumerate(pattern)}
    tail_params = [init_block(keys[n_groups * P + j], cfg, pattern[j])
                   for j in range(tail)]
    return {"groups": groups, "tail": tail_params}


def _stack_caches(cfg, pattern, n_layers, batch, s_ctx, dtype):
    P = len(pattern)
    n_groups, tail = divmod(n_layers, P)
    groups = {}
    for i, kind in enumerate(pattern):
        one = init_block_cache(cfg, kind, batch, s_ctx, dtype)
        groups[f"p{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one)
    tail_caches = [init_block_cache(cfg, pattern[j], batch, s_ctx, dtype)
                   for j in range(tail)]
    return {"groups": groups, "tail": tail_caches}


def apply_stack(params, x, cfg: ModelConfig, pattern, *, offset=0,
                caches=None, enc_out=None, collect_cache=False,
                s_ctx: Optional[int] = None):
    """-> (x, new_caches, aux_total).

    caches=None & collect_cache=False : train (no state kept)
    caches=None & collect_cache=True  : prefill (states created, returned)
    caches given                      : decode (states updated)
    """
    P = len(pattern)

    def group_body(x, group_params, group_caches):
        # sequence-parallel residual stream: saved scan carries shard S on
        # "model", so remat-saved activations cost 1/TP per device
        x = maybe_shard(x, "data", "model", None)
        aux_t = jnp.zeros((), jnp.float32)
        new_caches = {}
        for i, kind in enumerate(pattern):
            c = None if group_caches is None else group_caches[f"p{i}"]
            if c is None and collect_cache:
                c = init_block_cache(cfg, kind, x.shape[0],
                                     s_ctx or x.shape[1], x.dtype)
            x, nc, aux = apply_block(group_params[f"p{i}"], x, cfg, kind,
                                     offset=offset, cache=c, enc_out=enc_out)
            new_caches[f"p{i}"] = nc
            aux_t = aux_t + aux
        return x, new_caches, aux_t

    if cfg.remat == "full":
        group_body = jax.checkpoint(group_body)
    elif cfg.remat == "dots":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.checkpoint_dots)

    keep_caches = collect_cache or caches is not None

    def scan_fn(carry, xs):
        x, aux_acc = carry
        gp = xs[0]
        gc = xs[1] if caches is not None else None
        x, nc, aux = group_body(x, gp, gc)
        return (x, aux_acc + aux), (nc if keep_caches else 0)

    k = cfg.remat_block
    n_groups = jax.tree.leaves(params["groups"])[0].shape[0] \
        if params["groups"] else 0
    if (k > 1 and not keep_caches and cfg.remat != "none"
            and n_groups % k == 0 and n_groups > k):
        # two-level remat: outer scan over super-groups saves x every k
        # groups; the inner scan re-runs under its own checkpoint —
        # sqrt-L activation memory at ~1/k extra recompute
        sup = jax.tree.map(
            lambda p: p.reshape((n_groups // k, k) + p.shape[1:]),
            params["groups"])

        def super_body(x, sp):
            (x, aux), _ = jax.lax.scan(
                scan_fn, (x, jnp.zeros((), jnp.float32)), (sp,))
            return x, aux

        super_body = jax.checkpoint(super_body)

        def outer_fn(carry, sp):
            x, aux_acc = carry
            x, aux = super_body(x, sp)
            return (x, aux_acc + aux), 0

        (x, aux_total), ys = jax.lax.scan(
            outer_fn, (x, jnp.zeros((), jnp.float32)), sup)
    else:
        xs = (params["groups"],) if caches is None \
            else (params["groups"], caches["groups"])
        (x, aux_total), ys = jax.lax.scan(
            scan_fn, (x, jnp.zeros((), jnp.float32)), xs)
    new_caches = {"groups": ys, "tail": []} if keep_caches else None

    for j, tp in enumerate(params["tail"]):
        kind = pattern[j]
        c = None if caches is None else caches["tail"][j]
        if c is None and collect_cache:
            c = init_block_cache(cfg, kind, x.shape[0], s_ctx or x.shape[1],
                                 x.dtype)
        x, nc, aux = apply_block(tp, x, cfg, kind, offset=offset, cache=c,
                                 enc_out=enc_out)
        aux_total = aux_total + aux
        if keep_caches:
            new_caches["tail"].append(nc)
    return x, new_caches, aux_total
