"""Smoke run of the serving engine on TPU: qwen3-4b at its published width.

qwen3-4b unreduced (36 layers, d 2560, GQA 32/8, head 128, vocab 151936)
with random bf16 weights from a seed, served under `w8a8_kv8_attn8`: the
fused fp8 Pallas linears and the paged fp8 decode kernel, both compiled
by Mosaic.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips: tp=4 against tp=1

The server is built through the normal entry point
(`repro.launch.serve.make_engine` -> `repro.launch.engine.Engine`) and
serves 8 greedy synthetic requests (seed 0, prompts of 128-256 tokens,
32 new tokens each, all arriving at once).  One chip checks that

  - every request finished with its 32 tokens, each in [0, vocab);
  - the compiled decode step holds a Mosaic kernel (`tpu_custom_call`);
  - on pages the run wrote, the Pallas paged decode agrees with the
    `jnp_gather` route within PAGED_TOL.

`--tp 4` runs only the tensor-parallel path: the same requests at tp=4
(page pool sharded over a (1, 4) mesh, weights replicated) and at tp=1
in this one process; the greedy tokens must be identical, and every
weight and pool leaf of the tp=4 server must live on all four devices.

The last line of stdout is ``{"ok": true, "device": {...}}``.  A failed
check, a run where JAX finds no TPU, and a copy of this file without
`src/repro` beside it all exit non-zero without that line.  The timings
printed are those of one smoke run, compilation included: not a
benchmark.  Compiled programs go to the persistent cache
(`repro.launch.compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE_ARGV = ["--arch", "qwen3-4b", "--engine", "--policy", "w8a8_kv8_attn8",
              "--pages", "1024", "--page-size", "16", "--max-batch", "8",
              "--max-pages-per-req", "32", "--prefill-chunk", "16",
              "--prompt-len", "256", "--gen", "32", "--seed", "0"]
N_REQUESTS, PROMPTS, GEN = 8, (128, 256), 32

# Pallas paged decode vs jnp_gather as max|a - b| / max|b|, the exec-plan
# route-pin metric.  Under the interpreter the two are bit-identical
# (tests/test_exec_plan.py).  On a TPU both multiply at f32 (HIGHEST) and
# round onto the fp8 grid alike, but Mosaic and XLA order the sums
# differently and neither divides exactly, so a logit may move by a few
# ulps; a probability sitting on an fp8 rounding boundary then lands one
# grid step (at most 1/8 of itself) away.  With the softmax mass spread
# over the hundreds of keys of a 512-slot view, each such step moves an
# output by well under 1% of max|out|.  0.02 admits a few of them, while
# a wrong page, head or mask position moves outputs by order 1.
PAGED_TOL = 0.02


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching
    from the persistent cache), from its own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def _serve(tp: int, params=None):
    """Build the server the way `serve --engine` does and serve the
    smoke workload; -> (cfg, engine, report, wall seconds)."""
    from repro.launch import serve
    from repro.launch.engine import format_report, synthetic_workload
    from repro.models import build_model

    args = serve.parser().parse_args(SERVE_ARGV + ["--tp", str(tp)])
    cfg = serve.resolve_config(args)
    engine = serve.make_engine(cfg, build_model(cfg), args, params=params)
    reqs = synthetic_workload(N_REQUESTS, vocab=cfg.vocab_size, seed=0,
                              rate=0.0, prompt_range=PROMPTS,
                              gen_range=(GEN, GEN))
    t0 = time.monotonic()
    rep = engine.run(reqs)
    wall = time.monotonic() - t0
    print(f"[tp={tp}] " + format_report(rep, cfg.policy).replace(
        "\n", f"\n[tp={tp}] "))
    return cfg, engine, rep, wall


def _check_outputs(cfg, engine):
    done = sorted(engine.finished, key=lambda r: r.rid)
    if len(done) != N_REQUESTS:
        _fail(f"{len(done)} of {N_REQUESTS} requests finished")
    for r in done:
        toks = r.out_tokens
        if len(toks) != r.max_new or r.max_new != GEN:
            _fail(f"request {r.rid}: {len(toks)} tokens, wanted {GEN}")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            _fail(f"request {r.rid}: token outside [0, {cfg.vocab_size})")
    return {r.rid: list(r.out_tokens) for r in done}


def _decode_hlo(engine) -> str:
    """Text of the compiled decode step the engine served with."""
    import jax.numpy as jnp
    import numpy as np
    B = engine.ecfg.max_batch
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
             "index": jnp.zeros((B,), jnp.int32)}
    with engine._tp_scope():
        lowered = engine._decode_fn.lower(engine.params, batch, engine.caches,
                                          jnp.asarray(np.zeros(B, np.int32)))
    return lowered.compile().as_text()


def _paged_kernel_error(engine) -> float:
    """Pallas paged decode vs the jnp_gather route on layer 0 of the
    served pool: block tables over the pages the run wrote, a position
    per slot from a full 512-slot view down to one page, seeded queries."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import exec_plan
    from repro.core import kvcache as KV

    cfg, e, pol = engine.cfg, engine.ecfg, engine.pol
    pools = {k: engine.caches["groups"]["p0"][k][0] for k in KV.QUANT_KEYS}
    written = np.flatnonzero(np.asarray(
        jnp.any(pools["k_scale"] > 0, axis=(1, 2, 3))))
    if len(written) < e.max_pages_per_req:
        _fail(f"only {len(written)} pool pages were written")
    table = np.stack([np.roll(written, -7 * b)[:e.max_pages_per_req]
                      for b in range(e.max_batch)]).astype(np.int32)
    s_view = e.max_pages_per_req * e.page_size
    positions = np.linspace(s_view - 1, e.page_size - 1,
                            e.max_batch).astype(np.int32)
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (e.max_batch, 1, cfg.n_heads, cfg.hd),
                          jnp.bfloat16)
    cache = dict(pools, block_table=jnp.asarray(table))
    out = {}
    for name in ("pallas_block_table", "jnp_gather"):
        run = exec_plan.route("paged_decode", name).run
        out[name] = np.asarray(jax.jit(
            lambda q, c, p, run=run: run(q, c, p, policy=pol,
                                         scale=cfg.hd ** -0.5))(
            q, cache, jnp.asarray(positions)), np.float64)
    ref = out["jnp_gather"]
    if not np.isfinite(out["pallas_block_table"]).all():
        _fail("paged decode kernel produced non-finite values")
    return float(np.max(np.abs(out["pallas_block_table"] - ref))
                 / max(1e-6, np.max(np.abs(ref))))


def smoke_one_chip(clock):
    import jax
    from repro.core import exec_plan

    cfg, engine, rep, wall = _serve(1)
    _check_outputs(cfg, engine)
    e = engine.ecfg
    routes = {
        "paged_decode": engine.plan["route"],
        "matmul": exec_plan.describe(
            "matmul", engine.pol, w_dtype="bfloat16", m=e.max_batch,
            k=cfg.d_model, n=cfg.d_ff)["route"],
        "flash_attn (prefill chunk)": exec_plan.describe(
            "flash_attn", engine.pol, sq=e.prefill_chunk, skv=e.s_max,
            kv_on_grid=True)["route"]}
    if routes["paged_decode"] != "pallas_block_table" \
            or routes["matmul"] != "pallas_fused":
        _fail(f"hot path is not on the Pallas routes: {routes}")
    compile_s = clock.seconds
    hlo = _decode_hlo(engine)
    n_kernels = hlo.count("tpu_custom_call")
    err = _paged_kernel_error(engine)
    stats = jax.devices()[0].memory_stats() or {}
    gen = rep["gen_tokens"]
    print(f"arch: {cfg.name} ({cfg.n_layers} layers, d {cfg.d_model}, "
          f"GQA {cfg.n_heads}/{cfg.n_kv_heads}, hd {cfg.hd}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype})")
    print(f"policy: {cfg.policy}")
    for op, route in routes.items():
        print(f"route: {op} -> {route}")
    print(f"param bytes: "
          f"{sum(x.nbytes for x in jax.tree.leaves(engine.params))}")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    print(f"decode step tpu_custom_call count: {n_kernels}")
    print(f"paged decode pallas vs jnp_gather: max rel err {err!r} "
          f"(tol {PAGED_TOL})")
    print(f"smoke timings (one run, not a benchmark): compile "
          f"{compile_s!r} s in the process so far; serve wall {wall!r} s "
          f"with its step compiles, {gen} tokens, {gen / wall!r} tokens/s")
    if n_kernels == 0:
        _fail("the compiled decode step holds no tpu_custom_call")
    if not err <= PAGED_TOL:
        _fail(f"paged decode kernel differs from jnp_gather by {err!r}")


def smoke_tp(clock, tp: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model, serving_init

    args = serve.parser().parse_args(SERVE_ARGV + ["--tp", str(tp)])
    cfg = serve.resolve_config(args)
    mesh = make_host_mesh(n_data=1, n_model=tp)
    # one replicated copy serves both widths: tp=1 reads device 0's
    # replica, so the weights are built once and never doubled on it
    params = serving_init(build_model(cfg), NamedSharding(mesh, P()))(
        jax.random.PRNGKey(0))
    dev0 = jax.devices()[0]
    params_dev0 = jax.tree.map(
        lambda x: next(s.data for s in x.addressable_shards
                       if s.device == dev0), params)
    _, eng1, _, wall1 = _serve(1, params_dev0)
    toks1 = _check_outputs(cfg, eng1)
    del eng1
    _, eng, rep, wall = _serve(tp, params)
    toks = _check_outputs(cfg, eng)
    devices = set(mesh.devices.flat)
    partial = [path for path, x in jax.tree_util.tree_leaves_with_path(
        {"params": eng.params, "caches": eng.caches})
        if set(x.sharding.device_set) != devices]
    print(f"[tp={tp}] decode route {rep['decode_route']}, tp {rep['tp']}")
    print(f"tp={tp} vs tp=1 greedy tokens identical: {toks == toks1}")
    print(f"leaves not on all {tp} devices: {len(partial)}")
    print(f"smoke timings (one run each, not a benchmark): compile "
          f"{clock.seconds!r} s in all; wall tp=1 {wall1!r} s, tp={tp} "
          f"{wall!r} s, compilation included")
    if rep["tp"] != tp or rep["decode_route"] != "paged_decode_sharded":
        _fail(f"tp={tp} server did not shard: {rep['tp']}, "
              f"{rep['decode_route']}")
    if toks != toks1:
        diff = [rid for rid in toks if toks[rid] != toks1[rid]]
        _fail(f"tp={tp} tokens differ from tp=1 for requests {diff}")
    if partial:
        _fail(f"leaves held by fewer than {tp} devices: {partial[:4]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel check on this many "
                         "chips (tp=N against tp=1)")
    a = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform}")
    if len(devices) < a.tp:
        raise SystemExit(f"chip_smoke: --tp {a.tp} needs {a.tp} chips; "
                         f"JAX found {len(devices)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    if jax.config.jax_enable_x64:
        _fail("jax_enable_x64 is on in the serving process")
    clock = _CompileClock()
    if a.tp > 1:
        smoke_tp(clock, a.tp)
    else:
        smoke_one_chip(clock)
    if jax.config.jax_enable_x64:
        _fail("jax_enable_x64 was turned on while serving")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
