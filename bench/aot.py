"""Compile a configuration's serving steps for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/aot.py <config> [<config> ...]

No chip is needed: the TPU compiler builds the decode step and the
prefill-chunk step the engine jits, at the configuration's full width and
engine geometry, for one chip of a described v5e, and prints each
program's memory analysis and Mosaic kernel count -- or the compiler's
refusal (a kernel's VMEM, a program that does not fit).
"""
from __future__ import annotations

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_steps(conf: dict, name: str, device) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core import kvcache as KV
    from repro.core.policy import get_policy
    from repro.kernels import ops
    from repro.launch.engine import Engine
    from repro.models import build_model
    from repro.serving.sampler import SamplerConfig
    from bench import model as bm
    from bench import spec

    ops._interpret = lambda: False       # compile the kernels, not interpret
    cfg = spec.load_family(conf, ROOT).model_config(conf, name)
    mdl = build_model(cfg)
    g = bm.engine_geometry(conf)
    one = SingleDeviceSharding(device)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = place(jax.eval_shape(
        lambda k: jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                               if jnp.issubdtype(x.dtype, jnp.floating)
                               else x, mdl.init(k)),
        jax.random.PRNGKey(0)))
    pol = get_policy(cfg.policy)
    B, P = g["max_batch"], g["n_pages"]
    pool = jax.eval_shape(lambda: dict(
        KV.init_paged_kv_cache(P, g["page_size"], cfg.n_kv_heads, cfg.hd,
                               fmt=pol.fmt_kv, packed=pol.kv_packed),
        block_table=KV.make_block_table(B, g["max_pages_per_req"])))
    caches = place({"groups": {"p0": jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((cfg.n_layers,) + x.shape, x.dtype),
        pool)}, "tail": []})
    shell = types.SimpleNamespace(model=mdl, sampler=SamplerConfig())
    decode = jax.jit(Engine._make_decode_step(shell), donate_argnums=(2,))
    s_max = g["max_pages_per_req"] * g["page_size"]
    staging = place(jax.eval_shape(lambda: mdl.init_caches(1, s_max)))
    out = {}
    for label, fn, a in (
            ("decode", decode, (params, place(
                {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                 "index": jax.ShapeDtypeStruct((B,), jnp.int32)}), caches,
                place(jax.ShapeDtypeStruct((B,), jnp.int32)))),
            ("prefill_chunk", jax.jit(mdl.decode_step), (params, place(
                {"tokens": jax.ShapeDtypeStruct((1, g["prefill_chunk"]),
                                                jnp.int32),
                 "index": jax.ShapeDtypeStruct((), jnp.int32)}), staging))):
        try:
            c = fn.lower(*a).compile()
            m = c.memory_analysis()
            out[label] = {
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "mosaic_kernels": c.as_text().count("tpu_custom_call")}
        except Exception as e:           # the compiler's refusal, reported
            out[label] = {"refused": str(e)[-600:]}
    return out


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv:
        with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
            conf = json.load(f)
        print(json.dumps({name: compile_steps(conf, name, topo.devices[0])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
