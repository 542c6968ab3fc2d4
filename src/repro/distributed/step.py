"""Train / serve step builders: loss, grads, optimizer, pjit plumbing.

`make_train_step` returns a pure (state, batch) -> (state, metrics)
function suitable for jax.jit with sharded in/out; `make_serve_step`
returns the decode step.  The cross-entropy supports chunked evaluation
over the sequence (beyond-paper memory optimization — the unembedding
logits for a 150k vocab dominate activation memory at 4k seq).

Every matmul in these steps reaches the hardware through
`core.exec_plan.resolve` — the backbone via `apply_linear`/attention
routes, the unembed via the `unembed` plan op (`layers.apply_unembed`),
and the gradient collective in `make_compressed_train_step` via the
`allreduce` op.  No pre-plan branching survives here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.optim import adamw


def softmax_xent(logits, labels, z_coef: float = 1e-4):
    """logits (B,S,V) f32, labels (B,S) i32 -> scalar mean loss (+z-loss)."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(logz - gold)
    if z_coef:
        loss = loss + z_coef * jnp.mean(jnp.square(logz))
    return loss


def chunked_xent(params, model, x, labels, chunk: int, z_coef: float = 1e-4):
    """Per-chunk unembed + xent: never materializes (B,S,V)."""
    from repro.distributed.sharding import maybe_shard
    cfg = model.cfg
    B, S, _ = x.shape
    n = S // chunk
    table = params["embed"]["table"] if cfg.tie_embeddings \
        else params["unembed"]["table"]

    @jax.checkpoint
    def body(carry, idx):
        # checkpointed: each chunk's (B, chunk, V) logits are recomputed
        # in backward — saving them re-materializes the full (B,S,V)
        # tensor the chunking exists to avoid (§Perf A6)
        xs = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, 1)
        xs = maybe_shard(xs, "data", None, None)
        ls = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, 1)
        logits = L.apply_unembed(None, xs, table=table)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        part = jnp.sum(logz - gold) + z_coef * jnp.sum(jnp.square(logz))
        return carry + part, None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(n))
    return total / (B * S)


def make_loss_fn(model):
    cfg = model.cfg

    def loss_fn(params, batch):
        seq = batch["labels"].shape[1]
        chunk = min(cfg.logits_chunk, seq) if cfg.logits_chunk else 0
        if chunk and seq % chunk == 0:
            x, aux = model.backbone_features(params, batch)
            loss = chunked_xent(params, model, x, batch["labels"], chunk)
        else:
            logits, aux = model.train_logits(params, batch)
            loss = softmax_xent(logits, batch["labels"])
        return loss + aux, {"loss": loss, "aux": aux}

    return loss_fn


def make_train_step(model, opt_cfg: adamw.AdamWConfig):
    loss_fn = make_loss_fn(model)

    def train_step(state, batch):
        params, opt_state = state["params"], state["opt"]
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state,
                                               params)
        metrics = {"loss": parts["loss"], "aux": parts["aux"],
                   "total": loss, **om}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_compressed_train_step(model, opt_cfg: adamw.AdamWConfig, mesh,
                               fmt_name: str = "fp8_e4m3",
                               axis: str = "data"):
    """Data-parallel train step with wire-compressed gradient reduction.

    shard_map over `axis`: params/opt replicated, batch sharded on its
    leading dim, per-shard grads all-reduced through the exec-plan
    ``allreduce`` op — the wire-compressed route when `fmt_name` names a
    wire format (format-width codes + f32 scales, error feedback carried
    in state["err"]), the f32 psum reference when it is None.  The error
    state has a leading device axis (one residual per device) and
    checkpoints with the rest of the state; build it with
    `init_err_state`.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import CompressedReducer
    from repro.distributed.tp import shard_map

    loss_fn = make_loss_fn(model)
    reducer = CompressedReducer(fmt_name)
    n_dev = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])

    def body(state, batch):
        params, opt_state = state["params"], state["opt"]
        err = jax.tree.map(lambda e: e[0], state["err"])
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        grads, new_err = reducer.reduce(grads, err, axis, n_devices=n_dev)
        new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state,
                                               params)
        loss = jax.lax.pmean(loss, axis)
        parts = jax.tree.map(lambda t: jax.lax.pmean(t, axis), parts)
        metrics = {"loss": parts["loss"], "aux": parts["aux"],
                   "total": loss, **om}
        new_state = {"params": new_params, "opt": new_opt,
                     "err": jax.tree.map(lambda e: e[None], new_err)}
        return new_state, metrics

    state_specs = {"params": P(), "opt": P(), "err": P(axis)}
    return shard_map(body, mesh, in_specs=(state_specs, P(axis)),
                     out_specs=(state_specs, P()))


def init_err_state(params, n_devices: int):
    """Per-device error-feedback residuals, leading axis = mesh axis."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_devices,) + p.shape, jnp.float32), params)


def make_serve_step(model):
    def serve_step(params, batch, caches):
        logits, caches = model.decode_step(params, batch, caches)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, caches
    return serve_step


def make_prefill_step(model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step
