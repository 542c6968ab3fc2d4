"""The system under test, built from a configuration file and a seed.

The program's `ModelConfig` comes from the configuration's family
(`spec.load_family(conf).model_config`).  The benchmark makes the
weights itself, on the device, in one jitted call from the seed and in
the dtype they are served in; the program is asked only for the layout
of its parameter tree.  The server is then built through the program's
own entry point (`serve.make_engine` -> `Engine`) with the
configuration's engine geometry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def engine_geometry(conf: dict) -> dict:
    return {k: v["value"] for k, v in conf["engine"].items()}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (beyond 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _leaf(key, name: str, shape, dtype):
    """Random weights: norm gains near 1, embeddings N(0, 0.02^2), matrices
    N(0, 1 / fan_in), biases zero."""
    if name.endswith("scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name.endswith("table"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name.endswith("/w"):
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    elif name.endswith("/b"):
        x = jnp.zeros(shape, jnp.float32)
    else:
        raise ValueError(f"no rule for parameter {name}")
    return x.astype(dtype)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def make_weights(model, seed: int):
    """Serving weights for `model` from `seed`, built on the device in
    one jitted call, bf16 like the program serves them."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path_name(p) for p, _ in flat]

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(k, n, s.shape, jnp.bfloat16 if jnp.issubdtype(
                s.dtype, jnp.floating) else s.dtype)
            for k, n, (_, s) in zip(keys, names, flat)])

    return jax.jit(build)(seed_key(seed))


def make_engine(conf: dict, cfg, params, mix: dict, seed: int):
    """The program's server for this configuration and its `ModelConfig`
    `cfg`, through the normal entry point, with the given weights."""
    from repro.launch import serve
    from repro.models import build_model
    g = engine_geometry(conf)
    args = serve.parser().parse_args([
        "--arch", cfg.name, "--engine", "--policy", conf["policy"],
        "--page-size", str(g["page_size"]), "--pages", str(g["n_pages"]),
        "--max-batch", str(g["max_batch"]),
        "--max-pages-per-req", str(g["max_pages_per_req"]),
        "--token-budget", str(g["token_budget"]),
        "--prefill-chunk", str(g["prefill_chunk"]),
        "--prompt-len", str(mix["prompt"]["max"]),
        "--gen", str(mix["output"]["max"]),
        "--seed", str(seed & 0x7FFFFFFF)])
    return serve.make_engine(cfg, build_model(cfg), args, params=params)
