"""DPALinear — every matmul in the framework goes through here.

Forward contract (the paper's Table I):  y = sum_k q(x)_k * q(w)_k + c
with products in the operand format and accumulation in fp32 (or fp16).
Which execution route serves a given call — plain f32 dot, STE
fake-quant (training), native-narrow-weight dot (serving), or one of the
Pallas kernel pipelines (packed / fused-quant) — is decided by the
execution-plan layer: `dpa_dot` asks `core.exec_plan.resolve("matmul",
policy, ...)` and runs the winning route.  The routes themselves and
their lowering predicates live in `repro.kernels.registry`; this module
keeps only the parameter plumbing and dtype guards.

Parameters are plain pytrees ({"w": ..., "b": ...}); the module system in
`repro.models` composes these functions.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import exec_plan
from .policy import TransPrecisionPolicy, get_policy


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32, scale: Optional[float] = None):
    wkey, _ = jax.random.split(key)
    s = scale if scale is not None else d_in ** -0.5
    params = {"w": (jax.random.normal(wkey, (d_in, d_out), jnp.float32) * s
                    ).astype(dtype)}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
    return params


# jnp dtypes whose arrays are accepted *as-is* as pre-quantized weights.
NATIVE_NARROW = ("float8_e4m3fn", "float8_e5m2", "float4_e2m1fn")
_NATIVE_NARROW = NATIVE_NARROW


def dpa_dot(x, w, policy: TransPrecisionPolicy):
    """The DPA execution contract for x @ w (contraction on last/first)."""
    policy = get_policy(policy)
    entry = exec_plan.resolve("matmul", policy, w_dtype=str(w.dtype),
                              m=int(jnp.size(x) // x.shape[-1]),
                              k=x.shape[-1], n=w.shape[-1])
    return entry.run(x, w, policy)


# grouped einsums the Pallas grouped-DPA pipelines understand as a stack
# of per-expert (M,K)x(K,N) products.  Anything else falls back to the
# XLA grouped routes (the registry predicates gate on this tuple).
GROUPED_EQS = ("gti,gio->gto", "becd,edf->becf")


def grouped_dims(eq: str, x_shape, w_shape):
    """(experts, per-expert M, K, N) for a known grouped einsum, else
    None.  "becd,edf->becf" folds the batch dim into per-expert rows
    (M = B*C), matching the pipeline's normalized (E,M,K) view."""
    if eq == "gti,gio->gto":
        return x_shape[0], x_shape[1], x_shape[2], w_shape[2]
    if eq == "becd,edf->becf":
        b, e, c, d = x_shape
        return e, b * c, d, w_shape[2]
    return None


def dpa_grouped_dot(x, w, policy: TransPrecisionPolicy, *, eq: str):
    """The grouped (per-expert) DPA contract: einsum `eq` over x and the
    stacked expert weights w, routed through the plan layer."""
    policy = get_policy(policy)
    dims = grouped_dims(eq, x.shape, w.shape)
    ctx = {} if dims is None else dict(zip(("e", "m", "k", "n"), map(int,
                                                                     dims)))
    entry = exec_plan.resolve("grouped_matmul", policy,
                              w_dtype=str(w.dtype), eq=eq, **ctx)
    return entry.run(x, w, policy, eq=eq)


def apply_linear(params, x, policy: TransPrecisionPolicy = None):
    policy = get_policy(policy or "fp32")
    w = params["w"]
    if w.dtype == jnp.uint8:
        # fp4 E2M1 *code* weights (the storage dtype on JAX builds without
        # native float4).  Casting codes 0..15 to floats would silently
        # produce garbage — code-weight serving needs the kernel path with
        # explicit scales, which plain params don't carry.
        raise TypeError(
            "apply_linear got uint8 code weights; store fp4 weights as "
            "floats (fake-quant / kernel policies quantize them) or drive "
            "repro.kernels.ops.dpa_matmul with explicit scales")
    if str(w.dtype) not in _NATIVE_NARROW:
        w = w.astype(x.dtype)
    y = dpa_dot(x, w, policy)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# grouped (expert) linear for MoE: contraction per expert
# ---------------------------------------------------------------------------

def init_grouped_linear(key, n_groups: int, d_in: int, d_out: int, *,
                        dtype=jnp.float32):
    s = d_in ** -0.5
    w = jax.random.normal(key, (n_groups, d_in, d_out), jnp.float32) * s
    return {"w": w.astype(dtype)}


def apply_grouped_linear(params, x, policy: TransPrecisionPolicy = None):
    """x: (n_groups, tokens, d_in) -> (n_groups, tokens, d_out)."""
    policy = get_policy(policy or "fp32")
    return dpa_grouped_dot(x, params["w"], policy, eq="gti,gio->gto")
