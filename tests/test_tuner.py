"""Autotuner selection-invariance + measurement-DB contracts.

The tuner (`repro.runtime.tuner`) may only *reorder* the exec-plan's
resolution among routes whose reference pins already pass — a tuned DB
is a performance table, never a numerics change.  Pinned here:

  1. Selection invariance: for every config the sweep could measure,
     forcing it through a DB yields outputs within the forced route's
     pinned tolerance of the family reference; for the bit-pinned ops
     (paged_decode, verify_attn) and the greedy engine (spec + prefix
     paths), tuning on vs off is bit-identical.
  2. Every failure mode of the consult degrades to the static prior:
     unknown routes, out-of-family records, env-ineligible routes,
     corrupt DBs — warn, never crash, never change numerics.
  3. The measurement DB: content hashes are key-order/whitespace
     stable, measured configs are skipped on re-run, hash-sharding
     partitions the space exactly once, corrupt records are dropped.
  4. The bytes-moved models the tuner uses as its untuned prior match
     the actual arrays the routes move (anti-drift).
  5. `synthetic_workload` is seed-deterministic — the engine-level
     cutouts depend on it for reproducible measurements.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax

from repro.core import exec_plan
from repro.core import kvcache as KV
from repro.core.policy import get_policy
from repro.runtime import tuner


@pytest.fixture(autouse=True)
def _tuner_isolation(monkeypatch):
    """Each test starts with no tuned DB wired and cold tuner caches."""
    monkeypatch.delenv("REPRO_TUNED_DB", raising=False)
    monkeypatch.delenv("REPRO_TUNED", raising=False)
    tuner.clear_caches()
    yield
    tuner.clear_caches()


def _cfg(op, policy, cls, route, knobs=None):
    return {"op": op, "policy": policy,
            "policy_key": tuner.policy_key(get_policy(policy)),
            "shape_class": cls, "route": route, "knobs": dict(knobs or {}),
            **tuner.env_fingerprint()}


def _write_db(path, cfgs, us=1.0):
    records = {tuner.config_hash(c): {**c, "us": us, "reps": 1}
               for c in cfgs}
    tuner.save_db(str(path), {"version": 1, "meta": {},
                              "records": records})
    return str(path)


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(1e-6, np.max(np.abs(b))))


def _leaves(out):
    return [np.asarray(x, np.float64)
            for x in jax.tree_util.tree_leaves(out)]


# -----------------------------------------------------------------------------
# 1. selection invariance
# -----------------------------------------------------------------------------

def _forceable_configs():
    """One config per (op, class, route, knob-combo) of the smoke space
    under each op's first CI policy (the full sweep repeats per
    policy; one policy per op keeps the property test tractable)."""
    return [c for c in tuner.enumerate_space(smoke=True)
            if c["op"] != tuner.ENGINE_OP
            and c["policy"] == tuner.OP_POLICIES[c["op"]][0]]


def test_tuned_only_reorders_within_reference_family(tmp_path, monkeypatch):
    """Force every measurable config through a single-record DB: the
    resolution must pick exactly that route, and its output must sit
    within the route's pinned tolerance of the family reference — i.e.
    any tuned table keeps the plan's numerics contract."""
    configs = _forceable_configs()
    assert configs, "smoke space is empty?"
    for cfg in configs:
        sc = tuner.shape_class(cfg["op"], cfg["shape_class"])
        pol = get_policy(cfg["policy"])
        db = _write_db(tmp_path / "force.json", [cfg])
        monkeypatch.setenv("REPRO_TUNED_DB", db)
        tuner.clear_caches()
        entry = exec_plan.resolve(cfg["op"], pol, **sc.rep)
        assert entry.name == cfg["route"], cfg
        assert entry.tuned and entry.tuned_class == cfg["shape_class"]
        base = exec_plan.route(cfg["op"], cfg["route"])
        ref = exec_plan.reference_entry(base) or base
        args, kwargs = tuner._cutout(cfg["op"], cfg["shape_class"], pol)
        got = _leaves(entry.run(*args, **kwargs))
        want = _leaves(ref.run(*args, **kwargs))
        assert len(got) == len(want), cfg
        for g, w in zip(got, want):
            if base.tol == 0.0:
                assert np.array_equal(g, w), cfg
            else:
                assert _rel_err(g, w) <= base.tol + 5e-6, cfg


@pytest.mark.parametrize("op,cls", [("paged_decode", "paged_single"),
                                    ("verify_attn", "verify_paged")])
def test_bit_pinned_ops_identical_tuned_vs_off(op, cls, tmp_path,
                                               monkeypatch):
    """The bit-pinned ops: any in-family tuned selection is
    bit-identical to the untuned prior's output, not merely close."""
    pol = get_policy("kv4_attn8_packed")
    sc = tuner.shape_class(op, cls)
    args, kwargs = tuner._cutout(op, cls, pol)
    static = exec_plan.resolve(op, pol, **sc.rep)
    want = np.asarray(static.run(*args, **kwargs))
    db = _write_db(tmp_path / "db.json",
                   [_cfg(op, "kv4_attn8_packed", cls, "jnp_gather")])
    monkeypatch.setenv("REPRO_TUNED_DB", db)
    tuner.clear_caches()
    entry = exec_plan.resolve(op, pol, **sc.rep)
    assert entry.tuned and entry.name == "jnp_gather"
    assert np.array_equal(np.asarray(entry.run(*args, **kwargs)), want)


def test_engine_greedy_bit_identical_tuned_vs_off(tmp_path, monkeypatch):
    """Greedy engine outputs token-for-token equal with tuning on vs
    off, across the decode + speculative-verify + prefix-cache paths —
    an adversarial DB can only pick bit-pinned alternatives there."""
    from repro.configs import get_config, reduce_config
    from repro.launch.engine import (Engine, EngineConfig, SpecConfig,
                                     synthetic_workload)
    from repro.models import build_model

    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(page_size=8, n_pages=48, max_batch=3,
                        max_pages_per_req=6, token_budget=16,
                        prefill_chunk=8, prefix_cache=True)
    spec = SpecConfig("w4a4_kv4_attn4", k=2)

    def serve(db_path):
        if db_path:
            monkeypatch.setenv("REPRO_TUNED_DB", db_path)
        else:
            monkeypatch.delenv("REPRO_TUNED_DB", raising=False)
        tuner.clear_caches()
        engine = Engine(model, params, ecfg, spec=spec)
        engine.run(synthetic_workload(
            5, vocab=cfg.vocab_size, seed=0, prompt_range=(8, 20),
            gen_range=(4, 8), shared_prefix=8))
        return {r.rid: list(r.out_tokens) for r in engine.finished}

    baseline = serve(None)
    # the adversarial table: push both paged ops onto their references
    db = _write_db(tmp_path / "adv.json", [
        _cfg("paged_decode", "kv4_attn8_packed", "paged_single",
             "jnp_gather"),
        _cfg("verify_attn", "kv4_attn8_packed", "verify_paged",
             "jnp_gather")])
    tuned = serve(db)
    assert tuned == baseline
    assert baseline, "no requests finished?"


def test_tuned_resolve_deterministic_and_describe(tmp_path, monkeypatch):
    pol = get_policy("kv4_attn8_packed")
    sc = tuner.shape_class("paged_decode", "paged_single")
    prior = exec_plan.resolve("paged_decode", pol, **sc.rep)
    assert not prior.tuned
    assert prior.describe(pol, sc.rep)["selection"] == "prior"
    db = _write_db(tmp_path / "db.json",
                   [_cfg("paged_decode", "kv4_attn8_packed",
                         "paged_single", "jnp_gather")])
    monkeypatch.setenv("REPRO_TUNED_DB", db)
    tuner.clear_caches()
    first = exec_plan.resolve("paged_decode", pol, **sc.rep)
    for _ in range(3):        # identical object, not merely equal
        assert exec_plan.resolve("paged_decode", pol, **sc.rep) is first
    d = exec_plan.describe("paged_decode", pol, **sc.rep)
    assert d["selection"] == "tuned"
    assert d["shape_class"] == "paged_single"
    assert d["tuned_knobs"] == {}
    # kill switch restores the prior without touching the DB
    monkeypatch.setenv("REPRO_TUNED", "0")
    assert exec_plan.resolve("paged_decode", pol, **sc.rep) is prior


def test_tuned_ineligible_route_falls_back(tmp_path, monkeypatch):
    """A tuned route the live env disables (REPRO_PAGED_KERNEL=0) must
    fall back to the prior — and come back once re-enabled."""
    pol = get_policy("kv4_attn8_packed")
    sc = tuner.shape_class("paged_decode", "paged_single")
    db = _write_db(tmp_path / "db.json",
                   [_cfg("paged_decode", "kv4_attn8_packed",
                         "paged_single", "pallas_block_table")])
    monkeypatch.setenv("REPRO_TUNED_DB", db)
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "0")
    tuner.clear_caches()
    assert exec_plan.resolve("paged_decode", pol, **sc.rep).name \
        == "jnp_gather"
    monkeypatch.delenv("REPRO_PAGED_KERNEL")
    entry = exec_plan.resolve("paged_decode", pol, **sc.rep)
    assert entry.name == "pallas_block_table" and entry.tuned


def test_tuned_unknown_route_warns_and_falls_back(tmp_path, monkeypatch):
    pol = get_policy("kv4_attn8_packed")
    sc = tuner.shape_class("paged_decode", "paged_single")
    db = _write_db(tmp_path / "db.json",
                   [_cfg("paged_decode", "kv4_attn8_packed",
                         "paged_single", "no_such_kernel")])
    monkeypatch.setenv("REPRO_TUNED_DB", db)
    tuner.clear_caches()
    with pytest.warns(UserWarning, match="unknown route"):
        entry = exec_plan.resolve("paged_decode", pol, **sc.rep)
    assert entry.name == "pallas_block_table" and not entry.tuned


def test_out_of_family_record_never_selected(tmp_path, monkeypatch):
    """xla_f32 is eligible under any policy but shares no reference
    with the DPA family — a DB naming it must not flip an fp8 resolve
    onto the unquantized path."""
    pol = get_policy("fp8_dpa_fused")
    sc = tuner.shape_class("matmul", "gemm_decode")
    db = _write_db(tmp_path / "db.json",
                   [_cfg("matmul", "fp8_dpa_fused", "gemm_decode",
                         "xla_f32")])
    monkeypatch.setenv("REPRO_TUNED_DB", db)
    tuner.clear_caches()
    with pytest.warns(UserWarning, match="reference family"):
        entry = exec_plan.resolve("matmul", pol, **sc.rep)
    assert entry.name == "pallas_fused" and not entry.tuned


# -----------------------------------------------------------------------------
# 2. measurement DB
# -----------------------------------------------------------------------------

def test_config_hash_stable_across_key_order_and_whitespace():
    cfg = _cfg("matmul", "fp8_dpa_fused", "gemm_decode", "pallas_fused",
               {"bm": 32, "bk": 64})
    h = tuner.config_hash(cfg)
    shuffled = {k: cfg[k] for k in reversed(list(cfg))}
    assert tuner.config_hash(shuffled) == h
    assert tuner.config_hash(json.dumps(shuffled, indent=4)) == h
    assert tuner.config_hash(
        dict(cfg, knobs={"bk": 64, "bm": 32})) == h
    # ... and sensitive to what it must be sensitive to
    assert tuner.config_hash(dict(cfg, knobs={"bm": 64})) != h
    assert tuner.config_hash(dict(cfg, route="pallas_prequant")) != h
    assert tuner.config_hash(dict(cfg, jax_version="other")) != h


def test_sweep_skips_measured_and_shards_cover_space_once(tmp_path):
    """Two-shard sweep over the quantize_pack slice: the shards measure
    disjoint halves summing to the space, and a re-run measures 0."""
    db = str(tmp_path / "sweep.json")
    space = tuner.enumerate_space(smoke=True, ops=["quantize_pack"])
    hashes = [tuner.config_hash(c) for c in space]
    assert len(set(hashes)) == len(hashes)
    for n in (2, 3, 5):        # partition: every config in exactly one shard
        assert sorted(h for i in range(n) for h in hashes
                      if tuner.shard_of(h, n) == i) == sorted(hashes)
    s0 = tuner.run_sweep(db, smoke=True, shard=(0, 2), reps=1,
                         ops=["quantize_pack"])
    s1 = tuner.run_sweep(db, smoke=True, shard=(1, 2), reps=1,
                         ops=["quantize_pack"])
    assert s0["measured"] + s1["measured"] == len(space)
    assert s0["measured"] == s1["other_shard"]
    missing = tuner.missing_configs(db, smoke=True)
    assert not any(c["op"] == "quantize_pack" for c in missing)
    again = tuner.run_sweep(db, smoke=True, shard=(0, 1), reps=1,
                            ops=["quantize_pack"])
    assert again["measured"] == 0
    assert again["skipped"] == len(space)


def test_corrupt_and_partial_db_entries_ignored(tmp_path, monkeypatch):
    pol = get_policy("kv4_attn8_packed")
    sc = tuner.shape_class("paged_decode", "paged_single")
    good = _cfg("paged_decode", "kv4_attn8_packed", "paged_single",
                "jnp_gather")
    records = {
        tuner.config_hash(good): {**good, "us": 1.0, "reps": 1},
        "deadbeefdeadbeef": {"op": "paged_decode"},          # partial
        "feedfacefeedface": "not even a dict",               # corrupt
        "0123456789abcdef": {**good, "us": -3.0},            # bad value
    }
    path = tmp_path / "dirty.json"
    path.write_text(json.dumps({"version": 1, "records": records}))
    with pytest.warns(UserWarning, match="corrupt/partial"):
        db = tuner.load_db(str(path))
    assert list(db["records"]) == [tuner.config_hash(good)]
    monkeypatch.setenv("REPRO_TUNED_DB", str(path))
    tuner.clear_caches()
    entry = exec_plan.resolve("paged_decode", pol, **sc.rep)
    assert entry.tuned and entry.name == "jnp_gather"
    # a DB that is not JSON at all: warn, resolve on the prior
    path.write_text("{definitely not json")
    tuner.clear_caches()
    with pytest.warns(UserWarning, match="unreadable"):
        entry = exec_plan.resolve("paged_decode", pol, **sc.rep)
    assert not entry.tuned


def test_save_db_is_atomic_and_loadable(tmp_path):
    path = tmp_path / "nested" / "db.json"
    tuner.save_db(str(path), {"meta": {"backend": "cpu"},
                              "records": {"ab": {
                                  "op": "matmul", "policy_key": "x",
                                  "shape_class": "c", "route": "r",
                                  "us": 2.0}}})
    db = tuner.load_db(str(path))
    assert db["records"]["ab"]["us"] == 2.0
    assert not os.path.exists(str(path) + ".tmp")


# -----------------------------------------------------------------------------
# 3. plan-table contract checks (tools/plan_table.py --check)
# -----------------------------------------------------------------------------

def _plan_table():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "plan_table", os.path.join(root, "tools", "plan_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_plan_table_flags_undeclared_and_ungridded_knobs():
    pt = _plan_table()

    def run_with_knob(x, *, bm=128):
        return x

    entry = exec_plan.PlanEntry(
        op="fake", name="r", backend="xla", run=run_with_knob,
        predicate=lambda policy, ctx: {})
    errs = pt._knob_errors(entry)
    assert any("does not declare" in e for e in errs)
    declared = dataclasses.replace(entry, knobs=("bm",))
    assert pt._knob_errors(declared) == []
    ungridded = dataclasses.replace(entry, knobs=("no_such_knob",))
    assert any("no grid" in e for e in pt._knob_errors(ungridded))


def test_plan_table_flags_stale_tuned_defaults(tmp_path, monkeypatch):
    pt = _plan_table()
    d = tmp_path / "benchmarks" / "tuned"
    d.mkdir(parents=True)
    bad = _cfg("paged_decode", "kv4_attn8_packed", "paged_single",
               "route_that_got_deleted")
    (d / "stale.json").write_text(json.dumps(
        {"version": 1,
         "records": {tuner.config_hash(bad): {**bad, "us": 1.0}}}))
    monkeypatch.setattr(pt, "ROOT", str(tmp_path))
    errs = pt._tuned_defaults_errors()
    assert any("nonexistent route" in e for e in errs)
    # a hand-edited record whose key no longer matches its content
    good = _cfg("paged_decode", "kv4_attn8_packed", "paged_single",
                "jnp_gather")
    (d / "stale.json").write_text(json.dumps(
        {"version": 1, "records": {"0" * 16: {**good, "us": 1.0}}}))
    errs = pt._tuned_defaults_errors()
    assert any("content hash" in e for e in errs)


def test_shipped_tuned_defaults_are_valid():
    """The DBs under benchmarks/tuned/ pass the CI integrity check and
    cover the whole smoke space (the tune --smoke lane's contract)."""
    pt = _plan_table()
    assert pt._tuned_defaults_errors() == []
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    db = os.path.join(root, "benchmarks", "tuned", "ci_default.json")
    assert os.path.exists(db)
    assert tuner.missing_configs(db, smoke=True) == []


# -----------------------------------------------------------------------------
# 4. bytes-model anti-drift
# -----------------------------------------------------------------------------

def _view_bytes(cache):
    view = KV.gather_paged_kv(cache)
    return sum(np.asarray(view[k]).nbytes for k in KV.QUANT_KEYS)


def _pool_bytes(cache):
    return sum(np.asarray(cache[k]).nbytes for k in KV.QUANT_KEYS)


def _paged_fixture(pol, B=2, ps=8, mp=3, n_kv=2, hd=16):
    S = mp * ps
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    k = jax.random.normal(ks[0], (B, S, n_kv, hd))
    v = jax.random.normal(ks[1], (B, S, n_kv, hd))
    ref = KV.update_kv_cache(
        KV.init_kv_cache(B, S, n_kv, hd, fmt=pol.fmt_kv,
                         packed=pol.kv_packed),
        k, v, 0, fmt=pol.fmt_kv, packed=pol.kv_packed)
    cache = KV.paged_from_contiguous(ref, [S] * B, page_size=ps)
    ctx = dict(batch=B, page_size=ps, max_pages=mp, kv_heads=n_kv, hd=hd,
               n_pages=int(cache["k_codes"].shape[0]))
    return cache, ref, ctx


def _matmul_actual(pol, m, k, n):
    from repro.core.packing import pack_fp4_axis
    from repro.kernels.ops import _quant_operand
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n))
    xq, _ = _quant_operand(x, pol.fmt_acts, axis_scale=-1)
    wq, _ = _quant_operand(w, pol.fmt_weights, axis_scale=0)
    if pol.packed and pol.fmt_acts == "fp4_e2m1":
        xq = pack_fp4_axis(xq, 1)
    if pol.packed and pol.fmt_weights == "fp4_e2m1":
        wq = pack_fp4_axis(wq, 0)
    return np.asarray(xq).nbytes + np.asarray(wq).nbytes


@pytest.mark.parametrize("preset", ["fp8_dpa_fused", "fp4_dpa_packed"])
def test_bytes_model_matmul_matches_nbytes(preset):
    pol = get_policy(preset)
    m, k, n = 32, 64, 48
    ctx = dict(m=m, k=k, n=n)
    actual = _matmul_actual(pol, m, k, n)
    for route in ("pallas_fused", "pallas_prequant"):
        model = exec_plan.route("matmul", route).bytes_moved(pol, ctx)
        assert 0.5 <= model / actual <= 2.0, (route, model, actual)


def _grouped_actual(pol, e, m, k, n, *, packed):
    from repro.core.packing import pack_fp4_axis
    from repro.kernels.ops import _quant_operand
    ks = jax.random.split(jax.random.PRNGKey(12), 2)
    x = jax.random.normal(ks[0], (e, m, k))
    w = jax.random.normal(ks[1], (e, k, n))
    xq, _ = _quant_operand(x, pol.fmt_acts, axis_scale=-1)
    wq, _ = _quant_operand(w, pol.fmt_weights, axis_scale=1)
    if packed and pol.packed and pol.fmt_acts == "fp4_e2m1":
        xq = pack_fp4_axis(xq, 2)
    if packed and pol.packed and pol.fmt_weights == "fp4_e2m1":
        wq = pack_fp4_axis(wq, 1)
    return np.asarray(xq).nbytes + np.asarray(wq).nbytes


@pytest.mark.parametrize("preset", ["fp8_dpa_fused", "fp4_dpa_packed"])
def test_bytes_model_grouped_matmul_matches_nbytes(preset):
    """Declared grouped bytes vs the real quantized (and, for the kernel
    routes, packed) operand stacks' nbytes — within 2x, every grouped
    route that declares a model."""
    pol = get_policy(preset)
    e, m, k, n = 4, 16, 64, 48
    ctx = dict(e=e, m=m, k=k, n=n, eq="gti,gio->gto",
               w_dtype="float32")
    actual = _grouped_actual(pol, e, m, k, n, packed=True)
    for route in ("pallas_grouped_fused", "pallas_grouped_prequant"):
        model = exec_plan.route("grouped_matmul", route).bytes_moved(pol,
                                                                     ctx)
        assert 0.5 <= model / actual <= 2.0, (route, model, actual)
    # the wide routes traverse both stacks at f32 width
    wide = 4 * (e * m * k + e * k * n)
    for route in ("xla_fake_quant", "xla_f32"):
        model = exec_plan.route("grouped_matmul", route).bytes_moved(pol,
                                                                     ctx)
        assert 0.5 <= model / wide <= 2.0, (route, model, wide)
    # native-narrow: format width, never packed
    narrow = _grouped_actual(pol, e, m, k, n, packed=False)
    model = exec_plan.route("grouped_matmul",
                            "xla_native_narrow").bytes_moved(pol, ctx)
    assert 0.5 <= model / narrow <= 2.0, (model, narrow)


def test_bytes_model_paged_ops_match_nbytes():
    """The paged-op models = (declared pass count) x (view rows at the
    cache's format width): recompute against the real gathered-view and
    pool arrays' nbytes."""
    pol = get_policy("kv4_attn8_packed")
    cache, _, ctx = _paged_fixture(pol)
    view = _view_bytes(cache)
    pool = _pool_bytes(cache)
    cases = [
        ("paged_decode", "pallas_block_table", dict(ctx), 1 * view),
        ("paged_decode", "jnp_gather", dict(ctx), 3 * view),
        ("verify_attn", "jnp_gather", dict(ctx, sq=4), (3 + 2 * 4) * view),
        ("paged_decode", "paged_decode_sharded", dict(ctx, n_devices=2),
         1 * view + pool // 2),
        ("verify_attn", "verify_attn_sharded",
         dict(ctx, sq=4, n_devices=2), (3 + 2 * 4) * view + pool // 2),
    ]
    for op, route, c, actual in cases:
        model = exec_plan.route(op, route).bytes_moved(pol, c)
        assert 0.5 <= model / actual <= 2.0, (op, route, model, actual)


def test_bytes_model_decode_attn_matches_nbytes():
    pol = get_policy("kv4_attn8_packed")
    B, S, n_kv, hd = 2, 16, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    ref = KV.update_kv_cache(
        KV.init_kv_cache(B, S, n_kv, hd, fmt=pol.fmt_kv,
                         packed=pol.kv_packed),
        jax.random.normal(ks[0], (B, S, n_kv, hd)),
        jax.random.normal(ks[1], (B, S, n_kv, hd)),
        0, fmt=pol.fmt_kv, packed=pol.kv_packed)
    actual = sum(np.asarray(ref[k]).nbytes for k in KV.QUANT_KEYS)
    model = exec_plan.route("decode_attn", "xla_dpa_decode").bytes_moved(
        pol, dict(batch=B, s_ctx=S, kv_heads=n_kv, hd=hd))
    assert 0.5 <= model / actual <= 2.0, (model, actual)


def test_bytes_model_allreduce_and_unembed_match_nbytes():
    from repro.distributed.collectives import quantize_for_wire
    pol = get_policy("fp32")
    size = 4096
    grad = jax.random.normal(jax.random.PRNGKey(13), (size,))
    q, scale = quantize_for_wire(grad, "fp8_e4m3")
    actual_wire = np.asarray(q).nbytes + np.asarray(scale).nbytes
    model = exec_plan.route("allreduce", "wire_compressed").bytes_moved(
        pol, dict(size=size, wire_fmt="fp8_e4m3", n_devices=2))
    assert 0.5 <= model / actual_wire <= 2.0
    model = exec_plan.route("allreduce", "xla_psum_f32").bytes_moved(
        pol, dict(size=size))
    assert model == np.asarray(grad, np.float32).nbytes
    # unembed: 4 bytes per f32 logit
    B, S, D, V = 1, 4, 32, 64
    x = jax.random.normal(jax.random.PRNGKey(17), (B, S, D))
    table = jax.random.normal(jax.random.PRNGKey(19), (V, D))
    out = exec_plan.route("unembed", "xla_tied_table").run(x, table, pol)
    model = exec_plan.route("unembed", "xla_tied_table").bytes_moved(
        pol, dict(size=S * V))
    assert model == np.asarray(out).nbytes / B


def test_every_bytes_model_covered():
    """Anti-drift completeness: a new route with a bytes model must be
    added to the coverage above (this test names the current set)."""
    have = {(e.op, e.name) for op in exec_plan.ops()
            for e in exec_plan.candidates(op) if e.bytes_moved}
    covered = {("matmul", "pallas_fused"), ("matmul", "pallas_prequant"),
               ("grouped_matmul", "pallas_grouped_fused"),
               ("grouped_matmul", "pallas_grouped_prequant"),
               ("grouped_matmul", "xla_native_narrow"),
               ("grouped_matmul", "xla_fake_quant"),
               ("grouped_matmul", "xla_f32"),
               ("decode_attn", "xla_dpa_decode"),
               ("paged_decode", "pallas_block_table"),
               ("paged_decode", "jnp_gather"),
               ("paged_decode", "paged_decode_sharded"),
               ("verify_attn", "jnp_gather"),
               ("verify_attn", "verify_attn_sharded"),
               ("allreduce", "wire_compressed"),
               ("allreduce", "xla_psum_f32"),
               ("unembed", "xla_tied_table")}
    assert have == covered, have.symmetric_difference(covered)


# -----------------------------------------------------------------------------
# 5. workload seed determinism (the engine cutout's substrate)
# -----------------------------------------------------------------------------

def test_synthetic_workload_seed_determinism():
    from repro.launch.engine import synthetic_workload
    kw = dict(vocab=211, rate=2.0, prompt_range=(4, 12), gen_range=(2, 6),
              shared_prefix=4)
    a = synthetic_workload(8, seed=5, **kw)
    b = synthetic_workload(8, seed=5, **kw)
    assert len(a) == len(b) == 8
    for ra, rb in zip(a, b):
        assert ra.rid == rb.rid
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.max_new == rb.max_new
        assert ra.arrival == rb.arrival
    c = synthetic_workload(8, seed=6, **kw)
    assert any(not np.array_equal(ra.prompt, rc.prompt)
               or ra.arrival != rc.arrival for ra, rc in zip(a, c))
