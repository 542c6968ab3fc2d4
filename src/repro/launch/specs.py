"""ShapeDtypeStruct input specs for every (arch x shape) dry-run cell.

No device memory is ever allocated here: model/optimizer state shapes
come from jax.eval_shape over the real init functions, batches are
constructed ShapeDtypeStructs.  This is the weak-type-correct, shardable
stand-in pattern the dry-run lowers against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config
from repro.models import build_model, serving_init
from repro.models.config import ModelConfig
from repro.optim import adamw

N_AUDIO_CTX = 1500   # whisper stub frontend output length


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _cast_float(tree, dtype):
    def c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(x.shape, dtype)
        return x
    return jax.tree.map(c, tree)


def param_shapes(cfg: ModelConfig, *, serve: bool = False):
    model = build_model(cfg)
    if serve:   # serving keeps weights in the compute dtype
        shapes = jax.eval_shape(serving_init(model), jax.random.PRNGKey(0))
        if cfg.serve_quant:   # weight-only storage format (matmul weights)
            from repro.core.quantize import jnp_dtype
            qdt = jnp_dtype(cfg.serve_quant)

            def q(path, x):
                names = [str(getattr(p, "key", getattr(p, "idx", "")))
                         for p in path]
                if x.ndim >= 2 and names[-1] == "w":
                    return jax.ShapeDtypeStruct(x.shape, qdt)
                return x
            shapes = jax.tree_util.tree_map_with_path(q, shapes)
        return shapes
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if cfg.params_dtype == "bf16":
        shapes = _cast_float(shapes, jnp.bfloat16)
    return shapes


def train_state_shapes(cfg: ModelConfig):
    params = param_shapes(cfg)
    opt = jax.eval_shape(adamw.init, params)
    return {"params": params, "opt": opt}


def batch_specs(cfg: ModelConfig, shape_name: str):
    """-> (kind, batch pytree of ShapeDtypeStructs [, cache pytree])."""
    sh = SHAPES[shape_name]
    B, S = sh["batch"], sh["seq"]
    kind = sh["kind"]
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    model = build_model(cfg)

    def token_inputs():
        if cfg.family == "encdec":
            return {"frames": _sds((B, N_AUDIO_CTX, cfg.d_model), dt),
                    "tokens": _sds((B, S), jnp.int32)}
        if cfg.frontend == "stub":          # vlm: fused patch embeddings
            return {"embeddings": _sds((B, S, cfg.d_model), dt)}
        return {"tokens": _sds((B, S), jnp.int32)}

    if kind == "train":
        batch = token_inputs()
        batch["labels"] = _sds((B, S), jnp.int32)
        return kind, batch, None
    if kind == "prefill":
        return kind, token_inputs(), None
    # decode: one new token against an S-long context
    batch = {"tokens": _sds((B, 1), jnp.int32),
             "index": _sds((), jnp.int32)}
    if cfg.family == "encdec":
        batch["enc_out"] = _sds((B, N_AUDIO_CTX, cfg.d_model), dt)
    caches = jax.eval_shape(lambda: model.init_caches(B, S))
    return kind, batch, caches


def cell_specs(arch: str, shape_name: str, cfg_override=None):
    """Everything dryrun needs for one cell."""
    cfg = get_config(arch)
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    kind, batch, caches = batch_specs(cfg, shape_name)
    if kind == "train":
        state = train_state_shapes(cfg)
        return cfg, kind, dict(state=state, batch=batch)
    params = param_shapes(cfg, serve=True)
    if kind == "prefill":
        return cfg, kind, dict(params=params, batch=batch)
    return cfg, kind, dict(params=params, batch=batch, caches=caches)
