"""Plain float32 reference of a dense decoder-only transformer.

Written from the published layer equations of Qwen3 and Mistral
(Hugging Face `modeling_qwen3` / `modeling_mistral`): pre-norm RMSNorm,
grouped-query attention with rotary position embedding (the half-split
form, `rotate_half`), optional per-head RMSNorm of q and k before the
rotation (Qwen3), causal softmax attention, SwiGLU MLP, final RMSNorm,
and logits through the tied embedding or an untied head.  Every matrix
product runs in float32 at `HIGHEST` precision; there are no kernels, no
cache and no batching of requests.  It imports nothing of the system
under test.

Weights come as a nested dict (the checkpoint layout the benchmark
writes; any dtype, widened to float32 here), with the layers stacked on
a leading axis so that the forward scans them one at a time:

  stack/groups/p0/norm1/scale (L, d)      stack/groups/p0/norm2/scale
  stack/groups/p0/attn/{wq,wk,wv,wo}/w    (L, in, out)
  stack/groups/p0/attn/{q,k}_norm/scale   (L, hd)      [qk_norm only]
  stack/groups/p0/mlp/{wg,wu,wd}/w        (L, in, out)
  norm_f/scale (d,)   embed/table (V, d)   unembed/table (V, d) [untied]

Departures from the published models: none in the equations.  The
weights are random from a seed, not the released checkpoint, and the
configuration's context is cut (see the configuration file).

`quant` runs the same forward with every operand the serving policy
narrows (linear inputs per row, weights per output column, q, K and V
per row and head, attention probabilities per row) rounded onto a
narrow float grid with absmax scaling: the lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# (mantissa bits, smallest normal exponent, largest finite value)
GRIDS = {"fp8_e4m3": (3, -6, 448.0), "fp4_e2m1": (1, 0, 6.0)}


def round_to_grid(y, fmt: str):
    """Round to the nearest value of a narrow float format (ties to even,
    saturating), in float32 arithmetic."""
    mbits, emin, vmax = GRIDS[fmt]
    a = jnp.abs(y)
    _, e = jnp.frexp(a)
    e = jnp.maximum(e - 1, emin)
    step = jnp.ldexp(jnp.ones_like(a), e - mbits)
    q = jnp.minimum(jnp.round(a / step) * step, vmax)
    return jnp.sign(y) * q


def fake_quant(x, fmt, axis):
    """Absmax-scaled rounding of x onto `fmt` along `axis` (identity when
    fmt is None)."""
    if fmt is None:
        return x
    vmax = GRIDS[fmt][2]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / vmax
    s = jnp.where(s > 0, s, 1.0)
    return round_to_grid(x / s, fmt) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(x, w, quant):
    w = fake_quant(w.astype(jnp.float32), quant, 0)
    return jnp.dot(fake_quant(x, quant, -1), w, precision=HIGHEST)


def _layer(x, p, pos, cfg, quant):
    S = x.shape[0]
    H, KV, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    eps = cfg["eps"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    a = p["attn"]
    h = _rms(x, f32(p["norm1"]["scale"]), eps)
    q = _linear(h, a["wq"]["w"], quant).reshape(S, H, hd)
    k = _linear(h, a["wk"]["w"], quant).reshape(S, KV, hd)
    v = _linear(h, a["wv"]["w"], quant).reshape(S, KV, hd)
    if cfg["qk_norm"]:
        q = _rms(q, f32(a["q_norm"]["scale"]), eps)
        k = _rms(k, f32(a["k_norm"]["scale"]), eps)
    q = _rope(q, pos, cfg["theta"])
    k = _rope(k, pos, cfg["theta"])
    q = fake_quant(q, quant, -1)
    k = fake_quant(k, quant, -1)
    v = fake_quant(v, quant, -1)
    g = H // KV
    qg = q.reshape(S, KV, g, hd)
    s = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    prob = fake_quant(prob, quant, -1)
    o = jnp.einsum("kgst,tkd->skgd", prob, v, precision=HIGHEST)
    x = x + _linear(o.reshape(S, H * hd), a["wo"]["w"], quant)
    m = p["mlp"]
    h = _rms(x, f32(p["norm2"]["scale"]), eps)
    gate = _linear(h, m["wg"]["w"], quant)
    up = _linear(h, m["wu"]["w"], quant)
    return x + _linear(jax.nn.silu(gate) * up, m["wd"]["w"], quant)


def model_dims(conf: dict) -> dict:
    """The reference's sizes from a configuration file (Hugging Face
    config keys)."""
    return {"heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"], "eps": float(conf["rms_norm_eps"]),
            "theta": float(conf["rope_theta"]),
            "qk_norm": bool(conf["qk_norm"]),
            "tied": bool(conf["tie_word_embeddings"])}


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def logits(weights, tokens, *, dims, quant=None):
    """(S,) token ids -> (S, V) float32 logits of the whole forward."""
    cfg = dict(dims)
    pos = jnp.arange(tokens.shape[0])
    table = weights["embed"]["table"]
    x = table[tokens].astype(jnp.float32)

    def body(x, p):
        return _layer(x, p, pos, cfg, quant), None

    x, _ = jax.lax.scan(body, x, weights["stack"]["groups"]["p0"])
    x = _rms(x, weights["norm_f"]["scale"].astype(jnp.float32), cfg["eps"])
    head = table if cfg["tied"] else weights["unembed"]["table"]
    return jnp.dot(x, head.astype(jnp.float32).T, precision=HIGHEST)


@jax.jit
def _gap(ref, picked):
    """max(ref) - ref[picked] per row."""
    return jnp.max(ref, -1) - jnp.take_along_axis(
        ref, picked[:, None], -1)[:, 0]


def served_gaps(weights, conf: dict, tokens, targets, *, length: int,
                quant=None):
    """Per-position logit gaps of served tokens.

    tokens (n,) int are the prompt and the served tokens, targets (n,)
    the token served after each position (-1 where none).  The sequence
    is padded to `length` (causal attention leaves the real positions
    untouched), so every request runs the one compiled program.
    Returns (gap, control_gap) as numpy arrays over the positions with a
    target:

      gap          max(reference logits) - reference logit of the target;
      control_gap  when `quant` names a format, the same gap of the token
                   that the `quant` forward puts first, else None.
    """
    import numpy as np
    n = len(tokens)
    tok = np.zeros(length, np.int32)
    tok[:n] = tokens
    tgt = np.zeros(length, np.int32)
    keep = np.flatnonzero(np.asarray(targets) >= 0)
    tgt[keep] = np.asarray(targets)[keep]
    dims = tuple(sorted(model_dims(conf).items()))
    ref = logits(weights, jnp.asarray(tok), dims=dims)
    gap = np.asarray(_gap(ref, jnp.asarray(tgt)))[keep]
    if quant is None:
        return gap, None
    low = logits(weights, jnp.asarray(tok), dims=dims, quant=quant)
    cgap = np.asarray(_gap(ref, jnp.argmax(low, -1)))[keep]
    return gap, cgap
