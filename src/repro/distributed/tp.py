"""Tensor-parallel serving context: the mesh the engine traces under.

The continuous-batching engine shards the paged KV pool across a "model"
mesh axis (sequence-sharded pages, the same `cache_spec` rule the train
step uses) and routes its attention through the `*_sharded` exec-plan
entries.  Those routes need the mesh at *trace* time — inside a jit'd
step there is no ambient `with mesh:` — so the engine activates it here
and the registry reads it back.

Bit-identity contract: the sharded routes all-gather the local pool
shards (format-width codes + per-row scales — a pure relayout, and the
narrow wire the paper prices) and then run the exact single-device
attention on the reassembled pool.  No cross-device float reduction ever
touches the softmax, so sharded greedy outputs match single-device
serving bit for bit (tests/test_tp_engine.py pins this across Table-I
KV formats, prefix hits, and spec-decode).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

AXIS = "model"

# pool leaves that travel on the wire (codes + per-row scales; block_table
# and positions stay replicated — they are host-driven metadata)
POOL_WIRE_KEYS = ("k_codes", "k_scale", "v_codes", "v_scale")

_ACTIVE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_tp_mesh", default=None)


@contextlib.contextmanager
def activate(mesh):
    """Make `mesh` visible to exec-plan routes resolved/traced inside."""
    tok = _ACTIVE_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _ACTIVE_MESH.reset(tok)


def active_mesh():
    return _ACTIVE_MESH.get()


def axis_size(axis: str = AXIS) -> int:
    """Size of the TP axis of the active mesh (1 when no mesh is active)."""
    mesh = _ACTIVE_MESH.get()
    if mesh is None:
        return 1
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1))


def require_mesh():
    mesh = _ACTIVE_MESH.get()
    if mesh is None:
        raise RuntimeError(
            "sharded exec-plan route resolved without an active TP mesh; "
            "wrap the call in repro.distributed.tp.activate(mesh) "
            "(launch/engine.py does this around every jit'd step)")
    return mesh


def shard_map(body, mesh, in_specs, out_specs):
    """`jax.shard_map` with every mesh axis manual.  The serving mesh is
    (1, tp) ("data", "model"), so the size-1 "data" axis being manual
    changes nothing, and jax rejects replicated out_specs on a partly
    manual mesh."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _gather_pool(shards: dict, axis: str):
    """All-gather the pool shards back into the full pool.

    The wire moves format-width codes + per-row f32 scales — never
    dequantized f32 rows — and tiled all-gather along the within-page row
    axis (axis 1) is a pure relayout: the reassembled pool is bit-
    identical to the unsharded one."""
    return {key: jax.lax.all_gather(x, axis, axis=1, tiled=True)
            for key, x in shards.items()}


def sharded_paged_attn(attn_fn, q, cache, positions, *, axis: str = AXIS):
    """Run a paged-attention fn over the pool sharded on `axis`.

    `attn_fn(q, cache, positions)` is the exact single-device route body;
    the wrapper only changes *where the pool bytes live* (1/n per device)
    and *what the wire carries* (codes + scales, 2x/4x/8x under f32)."""
    mesh = require_mesh()

    def body(q, kc, ks, vc, vs, bt, pos):
        full = _gather_pool(
            dict(zip(POOL_WIRE_KEYS, (kc, ks, vc, vs))), axis)
        full["block_table"] = bt
        return attn_fn(q, full, pos)

    in_specs = (P(), P(None, axis, None, None), P(None, axis, None, None),
                P(None, axis, None, None), P(None, axis, None, None),
                P(), P())
    fn = shard_map(body, mesh, in_specs, P())
    return fn(q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
              cache["v_scale"], cache["block_table"],
              jnp.asarray(positions, jnp.int32))


def psum_wire(x, axis: str, fmt_name: str = "fp8_e4m3"):
    """All-reduce with format-width wire + f32 accumulation (inside a
    shard_map body).  The DPA contract applied to the collective: each
    device ships its partial at `fmt_name` width plus one f32 scale, and
    the sum happens after widening.  Lossy at the wire format's precision
    — serving's pure-relayout routes never use it; it exists for
    row/column-parallel projection partials and gradient reduction."""
    from repro.distributed.collectives import quantize_for_wire
    q, scale = quantize_for_wire(x, fmt_name)
    qs = jax.lax.all_gather(q, axis)
    ss = jax.lax.all_gather(scale, axis)
    n = qs.shape[0]
    widened = qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * x.ndim)
    return jnp.sum(widened, axis=0)


def all_gather_wire(x, axis: str, fmt_name: str = "fp8_e4m3",
                    *, gather_axis: int = 0):
    """Tiled all-gather with format-width wire: quantize the local shard,
    gather codes + per-shard scales, dequantize each slab after landing.
    For tensors that are already narrow codes (the KV pool) use plain
    all_gather — that wire is already at format width and stays
    bit-exact."""
    from repro.distributed.collectives import (dequantize_from_wire,
                                               quantize_for_wire)
    q, scale = quantize_for_wire(x, fmt_name)
    qs = jax.lax.all_gather(q, axis, axis=gather_axis, tiled=True)
    ss = jax.lax.all_gather(scale, axis)
    n_dev = ss.shape[0]
    parts = jnp.split(qs, n_dev, axis=gather_axis)
    return jnp.concatenate(
        [dequantize_from_wire(p, s) for p, s in zip(parts, ss)],
        axis=gather_axis)


def make_tp_mesh(tp: int):
    """(1, tp) host mesh over the first tp devices: ("data", "model")."""
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(n_data=1, n_model=tp)
