"""Batched serving driver: prefill + greedy decode with KV caches.

Demonstrates the inference side of the DPA contract: weights quantized to
the policy format ride the narrow wires (HBM), activations quantize
per-row, accumulation stays FP32.  Attention policies (attn_fp8_dpa,
kv4_attn8_packed, ...) extend the contract to the serving hot path: both
attention matmuls accumulate f32 over narrow operands and the KV cache is
stored at format width, so every decode step streams 2-8x fewer cache
bytes.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --batch 4 --prompt-len 32 --gen 16 --policy kv4_attn8_packed

Two modes:

  static (default) : one rigid (B, S_max) batch stepped in lockstep —
      every request pays for the longest sequence.  Its report prices the
      cache at B x S_max, because that is what this mode really holds.
  --engine : the continuous-batching engine (`repro.launch.engine`) over
      the *paged* quantized KV cache — mixed-length requests under
      open-loop Poisson traffic, cache memory proportional to live
      tokens, and a report that counts KV bytes from actual per-request
      lengths plus page-allocator utilization (see `docs/serving.md`).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --engine --requests 16 --rate 50 --policy kv4_attn8_packed
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.core.policy import get_policy
from repro.distributed.step import make_serve_step
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model, serving_init


def report_plan(cfg, s_ctx: int) -> str:
    """One line naming the exec-plan routes this policy's serving step
    resolves to (the introspectable answer to "which kernel ran?").
    Static mode only runs the contiguous-cache ops — paged_decode is the
    engine's route and is deliberately left out here."""
    from repro.launch.hlo_analysis import plan_routes
    routes = plan_routes(cfg.policy, shapes={
        "flash_attn": {"sq": s_ctx, "skv": s_ctx,
                       "use_flash": cfg.use_flash},
        "decode_attn": {"s_ctx": s_ctx, "kv_heads": cfg.n_kv_heads,
                        "hd": cfg.hd}})
    static_ops = ("matmul", "flash_attn", "decode_attn")
    parts = [f"{op}->{routes[op]['route']}" for op in sorted(static_ops)
             if routes.get(op) is not None]
    return "plan: " + " ".join(parts)


def report_kv_cache(cfg, batch: int, s_ctx: int) -> str:
    """One-line KV-cache footprint for the selected policy."""
    pol = get_policy(cfg.policy)
    if not pol.kv_quantized:
        return "kv-cache: raw %s (policy %s)" % (cfg.dtype, cfg.policy)
    from repro.core.kvcache import kv_cache_nbytes
    nb = kv_cache_nbytes(batch, s_ctx, cfg.n_kv_heads, cfg.hd,
                         fmt=pol.fmt_kv, packed=pol.kv_packed)
    n_attn = sum(1 for i in range(cfg.n_layers)
                 if _pattern_kind(cfg, i) in ("attn", "dec"))
    return (f"kv-cache: {pol.fmt_kv}{' packed' if pol.kv_packed else ''} "
            f"{nb['total'] * n_attn / 1e6:.2f} MB vs f32 "
            f"{nb['f32_total'] * n_attn / 1e6:.2f} MB "
            f"({nb['reduction_vs_f32']:.2f}x fewer bytes/decode-step, "
            f"{n_attn} attn layers)")


def _pattern_kind(cfg, layer: int) -> str:
    from repro.models.transformer import family_pattern
    pat = family_pattern(cfg)
    return pat[layer % len(pat)]


def generate(model, params, prompt, n_gen: int, s_ctx: int):
    """prompt: (B, S0) -> tokens (B, S0+n_gen).  Greedy."""
    cfg = model.cfg
    B, S0 = prompt.shape
    caches = model.init_caches(B, s_ctx)
    serve_step = jax.jit(make_serve_step(model), donate_argnums=(2,))

    # prefill by stepping the decode path over the prompt (production uses
    # model.prefill; stepping exercises the exact serving cache path)
    tok = prompt[:, :1]
    toks = [tok]
    for t in range(S0 + n_gen - 1):
        nxt, caches = serve_step(
            params, {"tokens": tok, "index": jnp.int32(t)}, caches)
        tok = prompt[:, t + 1:t + 2] if t + 1 < S0 else nxt[:, None]
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)


def make_engine(cfg, model, args, params=None):
    """The --engine server: the tuned-DB knobs, the geometry and scheduler
    flags as an `EngineConfig`, self-speculative (--spec-draft) or
    adaptive (--adaptive-draft) drafting, sampling
    (--temperature/--top-k/--top-p; default greedy), and serving params
    built on the device in the compute dtype (`serving_init`) unless
    `params` are given."""
    from repro.launch.engine import (Engine, EngineConfig, SamplerConfig,
                                     SpecConfig)
    from repro.runtime.controller import ControllerConfig, default_ladder
    if args.tuned_db:
        # export first so every exec_plan.resolve() below (engine
        # construction included) consults the measured table
        os.environ["REPRO_TUNED_DB"] = args.tuned_db
        from repro.runtime import tuner
        best = tuner.best_engine_knobs(args.tuned_db)
        if best:
            ps = int(best.get("page_size", args.page_size))
            if ps != args.page_size:
                # rescale the per-request page budget so S_max (tokens a
                # request may hold) is preserved under the tuned page size
                s_max = args.page_size * args.max_pages_per_req
                args.max_pages_per_req = max(1, s_max // ps)
                args.page_size = ps
            if not args.spec_draft and int(best.get("spec_k", 0)) > 0:
                args.spec_draft = tuner.ENGINE_DRAFT_POLICY
                args.spec_k = int(best["spec_k"])
            print(f"tuned engine knobs from {args.tuned_db}: {best}")
    ecfg = EngineConfig(page_size=args.page_size, n_pages=args.pages,
                        max_batch=args.max_batch or args.batch,
                        max_pages_per_req=args.max_pages_per_req,
                        token_budget=args.token_budget,
                        prefill_chunk=args.prefill_chunk,
                        prefix_cache=args.prefix_cache,
                        tp=args.tp)
    adaptive = None
    if args.adaptive_draft:
        if args.spec_draft:
            raise SystemExit("--adaptive-draft replaces --spec-draft "
                             "(the ladder covers the static draft "
                             "policy); pass one or the other")
        adaptive = ControllerConfig(default_ladder(cfg.policy),
                                    k=args.spec_k)
    spec = SpecConfig(args.spec_draft, args.spec_k) if args.spec_draft \
        else None
    spec_k = adaptive.max_k if adaptive else (args.spec_k if spec else 0)
    # mixed traffic stretches the longest request to 4x the --gen /
    # --prompt-len ceilings (see synthetic_workload); guard for that
    p_max = 4 * args.prompt_len if args.mixed > 0 else args.prompt_len
    g_max = 4 * args.gen if args.mixed > 0 else args.gen
    if args.shared_prefix + p_max + g_max + spec_k > ecfg.s_max:
        raise SystemExit(
            f"--shared-prefix {args.shared_prefix} + prompt {p_max} + "
            f"gen {g_max}{' (4x for --mixed)' if args.mixed > 0 else ''} "
            f"(+ the {spec_k}-token draft window) exceeds the engine's "
            f"S_max = {ecfg.s_max} tokens/request; raise "
            "--max-pages-per-req or --page-size")
    sampler = SamplerConfig(temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed)
    if params is None:
        params = serving_init(model)(jax.random.PRNGKey(0))
    return Engine(model, params, ecfg, sampler=sampler, spec=spec,
                  adaptive=adaptive)


def run_engine(cfg, model, args):
    """--engine mode: the `make_engine` server driven by an open-loop
    synthetic workload (--mixed makes the traffic heterogeneous)."""
    from repro.launch.engine import format_report, synthetic_workload
    engine = make_engine(cfg, model, args)
    reqs = synthetic_workload(
        args.requests, vocab=cfg.vocab_size, seed=args.seed,
        rate=args.rate, prompt_range=(max(1, args.prompt_len // 2),
                                      args.prompt_len),
        gen_range=(max(1, args.gen // 2), args.gen),
        shared_prefix=args.shared_prefix, mixed=args.mixed)
    rep = engine.run(reqs)
    print(format_report(rep, cfg.policy))
    if engine.finished:
        sample = engine.finished[0]
        print(f"sample (req {sample.rid}): {sample.tokens()[:24].tolist()}")
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=2, allow_nan=False)
        print(f"report written to {args.json}")
    return rep


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--n-model", type=int, default=1)
    eg = ap.add_argument_group("engine", "continuous-batching mode")
    eg.add_argument("--engine", action="store_true",
                    help="serve with the paged-cache engine")
    eg.add_argument("--page-size", type=int, default=16)
    eg.add_argument("--pages", type=int, default=128,
                    help="page-pool capacity (page 0 is scratch)")
    eg.add_argument("--max-batch", type=int, default=0,
                    help="decode slots (default: --batch)")
    eg.add_argument("--max-pages-per-req", type=int, default=8)
    eg.add_argument("--token-budget", type=int, default=32,
                    help="tokens per scheduler step")
    eg.add_argument("--prefill-chunk", type=int, default=16)
    eg.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width: shard the KV page pool "
                         "across a (1, tp) \"model\" mesh and serve "
                         "through the sharded exec-plan routes (bit-"
                         "identical to --tp 1).  On CPU, expose devices "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launch")
    eg.add_argument("--requests", type=int, default=16)
    eg.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, req/s (0 = all at t=0)")
    eg.add_argument("--seed", type=int, default=0,
                    help="workload + sampler RNG seed")
    eg.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt prefixes across requests "
                         "(ref-counted pages + copy-on-write)")
    eg.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every synthetic request")
    eg.add_argument("--mixed", type=float, default=0.0,
                    help="fraction of long-prompt/long-gen requests in "
                         "the synthetic workload (0 = homogeneous; drawn "
                         "from a forked RNG stream, so 0 is byte-"
                         "identical to earlier releases)")
    eg.add_argument("--json", default="",
                    help="also dump the engine report to this JSON file")
    eg.add_argument("--tuned-db", default="",
                    help="tuned measurement DB (tools/tune.py output): "
                         "exports REPRO_TUNED_DB so exec-plan routes "
                         "resolve against measurements, and applies the "
                         "DB's best engine knobs: page size (with "
                         "--max-pages-per-req rescaled to keep S_max) "
                         "and spec-k (when --spec-draft is unset)")
    sg = ap.add_argument_group("sampling + speculation", "engine mode")
    sg.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    sg.add_argument("--top-k", type=int, default=0,
                    help="keep the k largest logits (0 = off)")
    sg.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    sg.add_argument("--spec-draft", default="",
                    help="draft policy preset for self-speculative "
                         "decoding (e.g. w4a4_kv4_attn4; empty = off)")
    sg.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative round")
    sg.add_argument("--adaptive-draft", action="store_true",
                    help="adaptive trans-precision drafting: walk the "
                         "default draft-precision ladder for --policy "
                         "with the acceptance-feedback controller "
                         "(repro.runtime.controller) instead of one "
                         "static --spec-draft policy")
    return ap


def resolve_config(args):
    """The served `ModelConfig`: --arch (cut by --reduced) under --policy.
    The engine needs a quantized-KV preset; its default is the all-fp8
    one, whose kernels compile for a TPU."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.engine and not args.policy:
        args.policy = "w8a8_kv8_attn8"
    if args.policy:
        cfg = cfg.replace(policy=args.policy)
    if cfg.family in ("encdec", "vlm") or cfg.frontend == "stub":
        raise SystemExit("serve demo targets token-in/token-out archs")
    return cfg


def main(argv=None):
    args = parser().parse_args(argv)
    enable_compile_cache()
    cfg = resolve_config(args)
    model = build_model(cfg)
    if args.engine:
        # the engine places its own state: one device, or its (1, tp)
        # mesh under --tp (an ambient host mesh would re-shard its steps)
        return run_engine(cfg, model, args)
    mesh = make_host_mesh(n_model=args.n_model)
    print(report_kv_cache(cfg, args.batch, args.prompt_len + args.gen))
    print(report_plan(cfg, args.prompt_len + args.gen))
    with mesh:
        params = serving_init(model)(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
        t0 = time.monotonic()
        out = generate(model, params, prompt, args.gen,
                       args.prompt_len + args.gen)
        out.block_until_ready()
        wall = time.monotonic() - t0
        steps = args.prompt_len + args.gen - 1
        print(f"generated {out.shape} in {wall:.2f}s "
              f"({steps * args.batch / wall:.1f} tok/s, policy={cfg.policy})")
        print("sample:", out[0, :24].tolist())
    return out


if __name__ == "__main__":
    main()
