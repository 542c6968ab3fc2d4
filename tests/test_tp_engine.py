"""Tensor-parallel serving on 8 virtual CPU devices (subprocess: the
device count must be fixed before jax initializes, and other tests need
1 device).

The contract under test is the tentpole's bit-identity anchor: a tp=8
engine — page pool sharded on the "model" axis, serving through the
`paged_decode_sharded` / `verify_attn_sharded` exec-plan routes whose
wire carries format-width codes + per-row scales — must emit exactly the
tokens the tp=1 engine emits, across Table-I KV formats, through prefix-
cache hits and speculative decoding; it replicates when the page size
does not divide the mesh axis and refuses a tp wider than the devices.
"""
import json
import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> dict:
    """Run `body` in a subprocess with 8 host devices; it must print a
    single JSON line prefixed RESULT: (same harness as
    tests/test_distributed.py)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_REPO, "src"),
               XLA_FLAGS="")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT line in: {out.stdout[-2000:]}")


_ENGINE_PRELUDE = """
    from repro.models import ModelConfig, build_model
    from repro.launch.engine import (Engine, EngineConfig, SpecConfig,
                                     synthetic_workload)

    def build(policy):
        cfg = ModelConfig("t", "decoder", 2, 64, 4, 2, 128, 256,
                          policy=policy)
        model = build_model(cfg)
        return cfg, model, model.init(jax.random.PRNGKey(0))

    def tokens_of(engine):
        return {str(r.rid): [int(t) for t in r.out_tokens]
                for r in engine.finished}
"""


def test_tp_engine_bit_identical_across_formats():
    """Greedy tp=8 == tp=1, token for token, across three Table-I KV
    formats (fp16, fp8, packed-fp4 cache), and the tp=8 report names the
    sharded route."""
    r = _run(_ENGINE_PRELUDE + """
    out = {}
    for policy in ("attn_fp16_dpa", "kv8_attn_f32", "kv4_attn8_packed"):
        cfg, model, params = build(policy)
        per_tp = {}
        routes = {}
        for tp in (1, 8):
            ecfg = EngineConfig(page_size=8, n_pages=32, max_batch=4,
                                max_pages_per_req=4, token_budget=16,
                                prefill_chunk=8, tp=tp)
            eng = Engine(model, params, ecfg)
            rep = eng.run(synthetic_workload(
                4, vocab=cfg.vocab_size, seed=0, prompt_range=(6, 18),
                gen_range=(4, 8)))
            per_tp[tp] = tokens_of(eng)
            routes[tp] = (rep["decode_route"], rep["tp"])
        out[policy] = {"match": per_tp[1] == per_tp[8],
                       "route_1": routes[1], "route_8": routes[8],
                       "n_reqs": len(per_tp[1])}
    print("RESULT:" + json.dumps(out))
    """)
    for policy, res in r.items():
        assert res["match"], (policy, res)
        assert res["n_reqs"] == 4, (policy, res)
        assert res["route_8"] == ["paged_decode_sharded", 8], (policy, res)
        assert res["route_1"][0] != "paged_decode_sharded", (policy, res)


def test_tp_prefix_and_spec_decode_bit_identical():
    """The sharded engine composes with the other serving features
    without numeric drift: a prefix-cache workload (shared system
    prompt, sequential requests so later ones hit + CoW off shared
    pages) and a speculative run (fp4 draft, `verify_attn_sharded`
    verify) both emit tp=1's exact tokens."""
    r = _run(_ENGINE_PRELUDE + """
    cfg, model, params = build("kv4_attn8_packed")
    out = {}

    def prefix_run(tp):
        ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=4,
                            max_pages_per_req=4, token_budget=16,
                            prefill_chunk=8, prefix_cache=True, tp=tp)
        eng = Engine(model, params, ecfg)
        reqs = synthetic_workload(5, vocab=cfg.vocab_size, seed=0,
                                  prompt_range=(4, 10), gen_range=(4, 6),
                                  shared_prefix=12)
        for req in reqs:                    # sequential: later ones hit
            eng.run([req])
        rep = eng.report(1.0)
        return tokens_of(eng), rep["prefix_hits"], rep["prefix_cow_copies"]

    t1, h1, c1 = prefix_run(1)
    t8, h8, c8 = prefix_run(8)
    out["prefix"] = {"match": t1 == t8, "hits": [h1, h8],
                     "cow": [c1, c8]}

    def spec_run(tp):
        ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=4,
                            max_pages_per_req=4, token_budget=32,
                            prefill_chunk=8, tp=tp)
        eng = Engine(model, params, ecfg,
                     spec=SpecConfig("w4a4_kv4_attn4", k=2))
        rep = eng.run(synthetic_workload(4, vocab=cfg.vocab_size, seed=2,
                                         prompt_range=(6, 14),
                                         gen_range=(4, 8)))
        return tokens_of(eng), rep
    s1, _ = spec_run(1)
    s8, rep8 = spec_run(8)
    out["spec"] = {"match": s1 == s8,
                   "verify_route": rep8["verify_route"],
                   "draft_route": rep8["draft_route"],
                   "acceptance": rep8["acceptance_rate"]}
    print("RESULT:" + json.dumps(out))
    """)
    assert r["prefix"]["match"], r["prefix"]
    assert r["prefix"]["hits"][0] == r["prefix"]["hits"][1] > 0, r["prefix"]
    assert r["prefix"]["cow"][0] == r["prefix"]["cow"][1], r["prefix"]
    assert r["spec"]["match"], r["spec"]
    assert r["spec"]["verify_route"] == "verify_attn_sharded", r["spec"]
    assert r["spec"]["draft_route"] == "paged_decode_sharded", r["spec"]


def test_tp_divisibility_fallback():
    """A page size the mesh axis does not divide serves replicated with
    a stated reason and tp=1's exact outputs; a tp wider than the
    visible devices is refused, never served on fewer devices."""
    r = _run(_ENGINE_PRELUDE + """
    cfg, model, params = build("kv4_attn8_packed")
    out = {}
    base = dict(n_pages=32, max_batch=4, max_pages_per_req=4,
                token_budget=16, prefill_chunk=6)
    runs = {}
    for name, kw in (("ref", dict(page_size=12, tp=1)),
                     ("indivisible", dict(page_size=12, tp=8))):
        eng = Engine(model, params, EngineConfig(**base, **kw))
        rep = eng.run(synthetic_workload(3, vocab=cfg.vocab_size, seed=0,
                                         prompt_range=(6, 18),
                                         gen_range=(4, 8)))
        runs[name] = (tokens_of(eng), rep)
    out["indivisible"] = {
        "match": runs["ref"][0] == runs["indivisible"][0],
        "tp": runs["indivisible"][1]["tp"],
        "reason": runs["indivisible"][1].get("tp_fallback_reason", ""),
        "route": runs["indivisible"][1]["decode_route"]}
    try:
        Engine(model, params, EngineConfig(**base, page_size=12, tp=16))
        out["too_wide"] = ""
    except ValueError as e:
        out["too_wide"] = str(e)
    print("RESULT:" + json.dumps(out))
    """)
    assert r["indivisible"]["match"], r["indivisible"]
    assert r["indivisible"]["tp"] == 1, r["indivisible"]
    assert "not divisible" in r["indivisible"]["reason"], r["indivisible"]
    assert r["indivisible"]["route"] != "paged_decode_sharded"
    assert "exceeds" in r["too_wide"], r["too_wide"]


def test_wire_collectives_parity():
    """The wire primitives under shard_map on 8 devices: the pool-shard
    all-gather is a pure relayout (bit-for-bit), the lossy fp16/fp8 wire
    reductions land within pinned tolerances of the f32 collective."""
    r = _run("""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.distributed import tp as TP
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(n_data=1, n_model=8)
    out = {}

    # (a) pure relayout: uint8 codes + f32 scales sharded on the row
    # axis, all-gathered back inside shard_map == the original pool
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    codes = jax.random.randint(ks[0], (6, 16, 4, 8), 0, 256,
                               dtype=jnp.int32).astype(jnp.uint8)
    scales = jax.random.uniform(ks[1], (6, 16, 4, 1), jnp.float32)

    def gather_body(c, s):
        full = TP._gather_pool({"k_codes": c, "k_scale": s}, "model")
        return full["k_codes"], full["k_scale"]

    spec = P(None, "model", None, None)
    fn = TP.shard_map(gather_body, mesh, (spec, spec), (P(), P()))
    gc, gs = fn(codes, scales)
    out["relayout_exact"] = bool(
        np.array_equal(np.asarray(gc), np.asarray(codes))
        and np.array_equal(np.asarray(gs), np.asarray(scales)))

    # (b) lossy wire reductions: psum_wire vs the f32 psum
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 64, 32), jnp.float32)

    def red_body(fmt, xs):
        return TP.psum_wire(xs[0], "model", fmt)

    def f32_body(xs):
        return jax.lax.psum(xs[0], "model")

    want = np.asarray(TP.shard_map(f32_body, mesh, (P("model"),),
                                   P())(x))
    for fmt in ("fp16", "fp8_e4m3"):
        got = np.asarray(TP.shard_map(
            partial(red_body, fmt), mesh, (P("model"),), P())(x))
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        out["psum_" + fmt] = err

    # (c) tiled all_gather_wire vs the exact gather
    def ag_body(fmt, xs):
        return TP.all_gather_wire(xs, "model", fmt, gather_axis=0)

    for fmt in ("fp16", "fp8_e4m3"):
        got = np.asarray(TP.shard_map(
            partial(ag_body, fmt), mesh, (P("model"),), P())(x))
        err = float(np.max(np.abs(got - x)) / np.max(np.abs(np.asarray(x))))
        out["gather_" + fmt] = err
    print("RESULT:" + json.dumps(out))
    """)
    assert r["relayout_exact"] is True, r
    # pinned wire tolerances: fp16 keeps ~3 decimal digits, fp8-e4m3 ~2
    assert r["psum_fp16"] < 2e-3, r
    assert r["psum_fp8_e4m3"] < 8e-2, r
    assert r["gather_fp16"] < 2e-3, r
    assert r["gather_fp8_e4m3"] < 8e-2, r
    # and the narrow wire really is lossy-but-bounded, not exact-by-luck
    assert r["psum_fp8_e4m3"] > 1e-6, r
