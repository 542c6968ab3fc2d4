"""Serving-engine benchmarks: paged-cache memory + engine decode rate.

The continuous-batching face of the bandwidth story.  A static (B,
S_max) cache prices every request at the longest sequence; the paged
cache prices them at live tokens (page-granular).  Rows report

  sw/paged_kv_live_bytes     : deterministic — bytes a mixed-length
                               workload's pages hold vs the static
                               (B, S_max) cache at the same format
                               (live_vs_static) and vs the f32 seed
                               cache (vs_f32_static).  The regression
                               gate pins both (modeled bytes, any drift
                               is a contract change).
  sw/engine_decode_tokens    : wall-clock of the engine serving a small
                               mixed workload end to end (reduced
                               qwen3-4b, kv4_attn8_packed) + derived
                               decode tokens/s — a loose CPU tripwire,
                               not a TPU number.
  engine/paged_decode_kernel_vs_gather :
                               the two `paged_decode` exec-plan routes
                               head to head — block-table Pallas kernel
                               vs the jnp gather fallback — on one
                               batched decode step.  bytes_saved (the
                               gather's HBM view re-materialization the
                               kernel never pays, modeled) is pinned
                               tight; the wall-clock ratio and decode
                               tokens/s are loose CPU-interpret
                               tripwires.
  engine/spec_decode         : self-speculative decoding (greedy) on the
                               same workload.  acceptance_self (a
                               self-draft, draft == verify policy) is
                               pinned EXACTLY 1.0 — the k draft steps
                               and the batched verify are the same
                               computation, so any miss means the
                               multi-token verify path drifted from
                               stepped decode.  acceptance/eff_tokens of
                               the real all-fp4 draft and the spec-vs-
                               plain wall ratio are loose tripwires
                               (random-init weights; CPU, where drafts
                               cost the same as verifies — the
                               throughput win needs the 8x fp4 DPA
                               rate the hwmodel prices).
  engine/adaptive_spec       : the acceptance-feedback draft controller
                               (`repro.runtime.controller`) on mixed
                               traffic vs each static draft rung.
                               switches is pinned >= 1 (the ladder
                               really moves) and round_eff_vs_worst >=
                               1 (per draft+verify round, adaptive
                               emits at least as much as the worst
                               static rung — deterministic, unlike the
                               tokens/s wall tripwire).
"""
from __future__ import annotations

from repro.core import get_policy
from repro.core.kvcache import kv_cache_nbytes, paged_kv_cache_nbytes

# a serving-ish mixed-length snapshot: 8 slots, S_max = 1024, live
# lengths in whole pages so live == paged (the honest comparison)
PAGE, N_SLOTS, MAX_PAGES = 64, 8, 16
LIVE_LENS = (1024, 512, 256, 128, 896, 384, 640, 64)


def paged_cache_bytes():
    """Deterministic: paged live bytes vs the static layouts."""
    pol = get_policy("kv4_attn8_packed")
    n_kv, hd = 8, 128
    live = sum(LIVE_LENS)
    pages = sum(-(-n // PAGE) for n in LIVE_LENS)
    nb = paged_kv_cache_nbytes(live, pages, PAGE, n_kv, hd,
                               fmt=pol.fmt_kv, packed=pol.kv_packed)
    static = kv_cache_nbytes(N_SLOTS, MAX_PAGES * PAGE, n_kv, hd,
                             fmt=pol.fmt_kv, packed=pol.kv_packed)
    return [("sw/paged_kv_live_bytes", float(nb["paged"]),
             f"live_vs_static={static['total'] / nb['paged']:.2f}x "
             f"vs_f32_static={static['f32_total'] / nb['paged']:.2f}x")]


def engine_decode_rate():
    """End-to-end engine wall clock on a small mixed workload."""
    import time

    import jax

    from repro.configs import get_config, reduce_config
    from repro.launch.engine import Engine, EngineConfig, synthetic_workload
    from repro.models import build_model

    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=4,
                        max_pages_per_req=6, token_budget=16,
                        prefill_chunk=8)
    reqs = synthetic_workload(6, vocab=cfg.vocab_size, seed=0,
                              prompt_range=(8, 24), gen_range=(4, 10))
    # warm-up run compiles prefill + decode; the timed run reuses them
    engine = Engine(model, params, ecfg)
    engine.run(synthetic_workload(2, vocab=cfg.vocab_size, seed=1,
                                  prompt_range=(8, 24), gen_range=(4, 10)))
    engine.reset_stats()
    t0 = time.perf_counter()
    rep = engine.run(reqs)
    us = (time.perf_counter() - t0) * 1e6
    return [("sw/engine_decode_tokens", us,
             f"tokens_per_s={rep['tokens_per_s']:.1f} "
             f"page_util={rep['page_util']:.2f}x")]


def paged_decode_kernel_vs_gather():
    """One batched decode step through both `paged_decode` routes."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import exec_plan
    from repro.core import kvcache as KV

    pol = get_policy("kv4_attn8_packed")
    B, H, n_kv, hd, ps, max_pages = 4, 8, 4, 64, 16, 4
    S = max_pages * ps
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k = jax.random.normal(ks[0], (B, S, n_kv, hd))
    v = jax.random.normal(ks[1], (B, S, n_kv, hd))
    q = jax.random.normal(ks[2], (B, 1, H, hd))
    ref = KV.update_kv_cache(
        KV.init_kv_cache(B, S, n_kv, hd, fmt=pol.fmt_kv,
                         packed=pol.kv_packed),
        k, v, 0, fmt=pol.fmt_kv, packed=pol.kv_packed)
    cache = KV.paged_from_contiguous(ref, [S] * B, page_size=ps)
    positions = jnp.asarray([S - 1] * B, jnp.int32)

    ctx = dict(batch=B, page_size=ps, max_pages=max_pages, kv_heads=n_kv,
               hd=hd)
    kernel = exec_plan.route("paged_decode", "pallas_block_table")
    gather = exec_plan.route("paged_decode", "jnp_gather")

    def timed(entry, reps=3):
        entry.run(q, cache, positions, policy=pol,
                  scale=hd ** -0.5).block_until_ready()   # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = entry.run(q, cache, positions, policy=pol,
                            scale=hd ** -0.5)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    us_k, us_g = timed(kernel), timed(gather)
    # bytes_saved derived from *actual array sizes*, independent of the
    # registry's bytes_moved model (which the gate would otherwise just
    # re-derive): the gather route reads the view's pages, writes the
    # re-materialized view, then attention reads it back; the kernel
    # streams exactly one pass of codes+scales through the block table.
    view = KV.gather_paged_kv(cache)
    view_b = sum(np.asarray(view[key]).nbytes for key in KV.QUANT_KEYS)
    gather_bytes = 3 * view_b
    saved = gather_bytes / kernel.bytes_moved(pol, ctx)
    return [("engine/paged_decode_kernel_vs_gather", us_k,
             f"bytes_saved={saved:.2f}x "
             f"kernel_vs_gather={us_k / us_g:.2f}x "
             f"tokens_per_s={B / (us_k / 1e6):.1f}")]


def spec_decode():
    """Speculative vs plain greedy decode on one mixed workload."""
    import time

    import jax

    from repro.configs import get_config, reduce_config
    from repro.launch.engine import Engine, EngineConfig, SpecConfig, \
        synthetic_workload
    from repro.models import build_model

    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=4,
                        max_pages_per_req=6, token_budget=32,
                        prefill_chunk=8)
    k = 3

    def run(spec, seed=0):
        engine = Engine(model, params, ecfg, spec=spec)
        # warm-up compiles draft/verify/decode; the timed run reuses them
        engine.run(synthetic_workload(2, vocab=cfg.vocab_size, seed=1,
                                      prompt_range=(8, 24),
                                      gen_range=(4, 10)))
        engine.reset_stats()
        reqs = synthetic_workload(6, vocab=cfg.vocab_size, seed=seed,
                                  prompt_range=(8, 24), gen_range=(4, 10))
        t0 = time.perf_counter()
        rep = engine.run(reqs)
        return (time.perf_counter() - t0) * 1e6, rep

    us_plain, _ = run(None)
    us_spec, rep = run(SpecConfig("w4a4_kv4_attn4", k=k))
    _, rep_self = run(SpecConfig("kv4_attn8_packed", k=k))
    return [("engine/spec_decode", us_spec,
             f"acceptance_self={rep_self['acceptance_rate']:.3f}x "
             f"acceptance_fp4={rep['acceptance_rate']:.2f}x "
             f"eff_tokens_per_round={rep['eff_tokens_per_round']:.2f}x "
             f"spec_vs_plain={us_spec / us_plain:.2f}x "
             f"tokens_per_s={rep['tokens_per_s']:.1f}")]


def adaptive_spec():
    """Adaptive trans-precision drafting vs each static draft rung on
    mixed (heterogeneous) traffic.

    The controller starts on the cheapest rung (fp4) and walks the
    ladder on acceptance feedback; random-init weights keep fp4
    acceptance low, so the run provably switches (switches is pinned
    >= 1).  round_eff_vs_worst — adaptive emitted-tokens-per-round over
    the *worst* static rung's — is the headline tripwire: every rung
    runs the same draft k, so a round is a fixed unit of draft+verify
    work and the ratio is deterministic (wall clocks under Pallas
    interpret mode are far too noisy to gate on).  It is
    penalty-inclusive: rung-grouped ticks fragment the batch into one
    round per live rung, and those smaller rounds drag the adaptive
    numerator down.  Per unit of draft+verify work the controller must
    still emit at least as much as pinning the worst rung for the whole
    workload.  tokens_per_s stays a loose wall-clock CPU tripwire."""
    import time

    import jax

    from repro.configs import get_config, reduce_config
    from repro.launch.engine import Engine, EngineConfig, SpecConfig, \
        synthetic_workload
    from repro.models import build_model
    from repro.runtime.controller import ControllerConfig, default_ladder

    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # S_max = 128: mixed traffic stretches prompts to 4x16=64 and gens
    # to 4x8=32; the pool holds 4 such requests plus scratch
    ecfg = EngineConfig(page_size=8, n_pages=96, max_batch=4,
                        max_pages_per_req=16, token_budget=32,
                        prefill_chunk=8)
    k, ladder = 2, default_ladder(cfg.policy)

    def workload(seed):
        return synthetic_workload(6, vocab=cfg.vocab_size, seed=seed,
                                  prompt_range=(8, 16), gen_range=(4, 8),
                                  mixed=0.3)

    def run(**kw):
        engine = Engine(model, params, ecfg, **kw)
        engine.run(workload(seed=1))     # warm-up compiles every view
        engine.reset_stats()
        reqs = workload(seed=0)
        t0 = time.perf_counter()
        rep = engine.run(reqs)
        return (time.perf_counter() - t0) * 1e6, rep

    static_eff = {name: run(spec=SpecConfig(name, k=k))[1]
                  ["eff_tokens_per_round"] for name in ladder}
    acfg = ControllerConfig(ladder, k=k, start=0, dwell=1)
    us_adapt, rep = run(adaptive=acfg)
    worst = min(static_eff.values())
    return [("engine/adaptive_spec", us_adapt,
             f"round_eff_vs_worst={rep['eff_tokens_per_round'] / worst:.2f}x "
             f"switches={float(rep['adaptive_switches']):.0f}x "
             f"acceptance={rep['acceptance_rate']:.2f}x "
             f"eff_tokens_per_round={rep['eff_tokens_per_round']:.2f}x "
             f"tokens_per_s={rep['tokens_per_s']:.1f}")]


def prefix_cache():
    """Prefix-sharing on a shared-system-prompt workload.

    Every request carries the same 16-token preamble; served one at a
    time through a warm engine, every request after the first hits the
    radix index.  hit_rate and prefill_saved are deterministic (token
    accounting, no wall clock) and pinned by the regression gate; the
    derived tokens/s is a loose CPU tripwire."""
    import time

    import jax

    from repro.configs import get_config, reduce_config
    from repro.launch.engine import Engine, EngineConfig, synthetic_workload
    from repro.models import build_model

    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(page_size=8, n_pages=64, max_batch=4,
                        max_pages_per_req=8, token_budget=16,
                        prefill_chunk=8, prefix_cache=True)
    engine = Engine(model, params, ecfg)

    def workload(seed):
        return synthetic_workload(6, vocab=cfg.vocab_size, seed=seed,
                                  prompt_range=(4, 12), gen_range=(4, 8),
                                  shared_prefix=16)

    # warm-up compiles prefill/decode AND seeds the resident prefix,
    # then drop it: the timed run measures cold-index -> warm-index
    engine.run(workload(seed=1))
    engine.prefix.drop_all()
    engine.reset_stats()
    reqs = workload(seed=0)
    t0 = time.perf_counter()
    for r in reqs:                       # sequential: later reqs hit
        engine.run([r])
    us = (time.perf_counter() - t0) * 1e6
    rep = engine.report((time.perf_counter() - t0))
    return [("engine/prefix_cache", us,
             f"hit_rate={rep['prefix_hit_rate']:.3f}x "
             f"prefill_saved={float(rep['prefill_tokens_saved']):.1f}x "
             f"cow_copies={float(rep['prefix_cow_copies']):.1f}x "
             f"tokens_per_s={rep['tokens_per_s']:.1f}")]


def tp_collective_bytes():
    """Bytes on the tensor-parallel wire, measured from the actual
    arrays (``.nbytes``), not the bytes model.

      wire_fp16 / wire_fp8 : f32 payload bytes vs the codes + scale
          `quantize_for_wire` actually ships for a (256, 1024) f32 slab
          — the wire contract of the serving/training collectives
          (Table-I widths: ~2x / ~4x under an f32 wire).
      kv_pool_wire : f32 KV pool bytes per layer vs the packed-fp4
          codes+scales a TP shard all-gathers per decode step (reduced
          qwen3-4b, kv4_attn8_packed — the same arrays `Engine.report`
          prices as tp_wire_bytes_per_step_layer).
      tokens_per_s : the engine serving with tp = the visible device
          count (1 on the single-device bench job); a loose CPU
          tripwire.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.core.kvcache import QUANT_KEYS
    from repro.distributed.collectives import quantize_for_wire
    from repro.launch.engine import Engine, EngineConfig, synthetic_workload
    from repro.models import build_model

    x = jax.random.normal(jax.random.PRNGKey(0), (256, 1024), jnp.float32)
    wire = {}
    for fmt in ("fp16", "fp8_e4m3"):
        q, s = quantize_for_wire(x, fmt)
        wire[fmt] = x.nbytes / (q.nbytes + s.nbytes)

    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=4,
                        max_pages_per_req=6, token_budget=16,
                        prefill_chunk=8, tp=len(jax.devices()))
    engine = Engine(model, params, ecfg)
    g = engine.caches["groups"]["p0"]
    pool_layer = sum(int(g[k].nbytes)
                     for k in QUANT_KEYS) // engine._n_groups
    f32_layer = 2 * 4 * (ecfg.n_pages * ecfg.page_size
                         * cfg.n_kv_heads * cfg.hd)
    # warm-up compiles prefill + decode; the timed run reuses them
    engine.run(synthetic_workload(2, vocab=cfg.vocab_size, seed=1,
                                  prompt_range=(8, 24), gen_range=(4, 10)))
    engine.reset_stats()
    t0 = time.perf_counter()
    rep = engine.run(synthetic_workload(4, vocab=cfg.vocab_size, seed=0,
                                        prompt_range=(8, 24),
                                        gen_range=(4, 10)))
    us = (time.perf_counter() - t0) * 1e6
    return [("engine/tp_collective_bytes", us,
             f"wire_fp16={wire['fp16']:.3f}x "
             f"wire_fp8={wire['fp8_e4m3']:.3f}x "
             f"kv_pool_wire={f32_layer / pool_layer:.3f}x "
             f"tokens_per_s={rep['tokens_per_s']:.1f}")]


def moe_grouped_dpa():
    """MoE serving through the fused quantize->pack->grouped-DPA expert
    pipeline (reduced granite-moe, 8 experts top-2).

      expert_w_red_fp8 / expert_w_red_fp4 : expert-weight bytes at the
          grouped route's operand interface vs the f32 expert residency
          the seed paid — deterministic byte accounting from the engine
          report (fp8 preset exactly 4x, packed-fp4 preset exactly 8x),
          pinned tight by the regression gate.
      operand_red_fp4 : grouped-matmul operand bytes per decode step
          (packed fp4 weights + fp8 activations) vs both stacks at f32
          width — the route's bytes model, deterministic.
      tokens_per_s : the engine end to end under the packed preset — a
          loose CPU-interpret tripwire, not a TPU number.
    """
    import time

    import jax

    from repro.configs import get_config, reduce_config
    from repro.launch.engine import Engine, EngineConfig, synthetic_workload
    from repro.models import build_model

    base = reduce_config(get_config("granite-moe-1b-a400m"))
    ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=4,
                        max_pages_per_req=6, token_budget=16,
                        prefill_chunk=8)

    def serve(policy, seed=0):
        cfg = base.replace(policy=policy)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        engine = Engine(model, params, ecfg)
        # warm-up compiles prefill + decode; the timed run reuses them
        engine.run(synthetic_workload(2, vocab=cfg.vocab_size, seed=1,
                                      prompt_range=(8, 16),
                                      gen_range=(4, 8)))
        engine.reset_stats()
        reqs = synthetic_workload(4, vocab=cfg.vocab_size, seed=seed,
                                  prompt_range=(8, 16), gen_range=(4, 8))
        t0 = time.perf_counter()
        rep = engine.run(reqs)
        return (time.perf_counter() - t0) * 1e6, rep

    _, rep8 = serve("w8a8_kv8_attn8")
    us, rep4 = serve("w4a8_kv4_attn8")
    ctx = dict(rep4)
    wide = 4.0  # f32 bytes per element, both operand stacks
    mk = ecfg.max_batch * (int(base.capacity_factor * base.top_k
                               / base.n_experts) + 1)
    emk = base.n_experts * mk * base.d_model
    ekn = base.n_experts * base.d_model * base.d_ff
    operand_red = wide * (emk + ekn) / ctx["moe_grouped_bytes_per_step_layer"]
    return [("engine/moe_grouped_dpa", us,
             f"expert_w_red_fp8={rep8['expert_w_reduction_vs_f32']:.2f}x "
             f"expert_w_red_fp4={rep4['expert_w_reduction_vs_f32']:.2f}x "
             f"operand_red_fp4={operand_red:.2f}x "
             f"tokens_per_s={rep4['tokens_per_s']:.1f}")]


def tuned_vs_static():
    """Tuned resolution vs static priority, over the shipped CI DB.

      db_ratio : min over the shipped DB's (op, policy, shape-class)
          keys of us(static-priority config) / us(tuned selection).
          >= 1.0 *by construction* — the tuned selection is the argmin
          over a measured pool that always contains the static config
          (every knob grid includes the defaults) — so the gate pins
          the invariant: a tuned table never selects a measured-slower
          config on any CI shape-class.  keys counts the shape-classes
          covered (drops mean the smoke sweep lost coverage).
      tuned_vs_static : live re-measure of the shape-class where the DB
          disagrees with priority order the most, resolved tuned vs
          static — a loose CPU tripwire that the consult actually
          changes what runs.
    """
    import os
    import time

    import jax

    from repro.core import exec_plan
    from repro.runtime import tuner

    db_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tuned", "ci_default.json")
    db = tuner.load_db(db_path)
    keys = sorted({(r["op"], r["policy"], r["shape_class"])
                   for r in db["records"].values()
                   if r["op"] != tuner.ENGINE_OP})
    ratios = {}
    for op, preset, cls in keys:
        pol = get_policy(preset)
        sc = tuner.shape_class(op, cls)
        static = exec_plan.resolve(op, pol, **sc.rep)
        pool = [r for r in db["records"].values()
                if (r["op"], r["policy"], r["shape_class"])
                == (op, preset, cls)]
        static_rec = [r for r in pool if r["route"] == static.name
                      and not r.get("knobs")]
        best = tuner._best_record(db, op, tuner.policy_key(pol), cls)
        if static_rec and best:
            ratios[(op, preset, cls)] = static_rec[0]["us"] / best["us"]
    db_ratio = min(ratios.values())
    # live tripwire at the key the DB reorders hardest
    op, preset, cls = max(ratios, key=ratios.get)
    pol = get_policy(preset)
    sc = tuner.shape_class(op, cls)
    args, kwargs = tuner._cutout(op, cls, pol)

    def timed(entry, reps=3):
        jax.block_until_ready(entry.run(*args, **kwargs))   # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = entry.run(*args, **kwargs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e6

    prior = exec_plan.resolve(op, pol, **sc.rep)
    saved = os.environ.get("REPRO_TUNED_DB")
    try:
        os.environ["REPRO_TUNED_DB"] = db_path
        tuner.clear_caches()
        tuned = exec_plan.resolve(op, pol, **sc.rep)
        us_tuned, us_static = timed(tuned), timed(prior)
    finally:
        if saved is None:
            os.environ.pop("REPRO_TUNED_DB", None)
        else:
            os.environ["REPRO_TUNED_DB"] = saved
        tuner.clear_caches()
    return [("engine/tuned_vs_static", us_tuned,
             f"db_ratio={db_ratio:.3f}x keys={float(len(ratios)):.0f}x "
             f"tuned_vs_static={us_static / us_tuned:.2f}x")]


ALL = [paged_cache_bytes, engine_decode_rate, paged_decode_kernel_vs_gather,
       spec_decode, adaptive_spec, prefix_cache, tp_collective_bytes,
       moe_grouped_dpa, tuned_vs_static]
SMOKE = [paged_cache_bytes, engine_decode_rate, paged_decode_kernel_vs_gather,
         spec_decode, adaptive_spec, prefix_cache, tp_collective_bytes,
         moe_grouped_dpa, tuned_vs_static]
