"""Distributed semantics on 8 virtual CPU devices (subprocess: the device
count must be fixed before jax initializes, and other tests need 1)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> dict:
    """Run `body` in a subprocess with 8 host devices; it must print a
    single JSON line prefixed RESULT:."""
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


def test_sharded_train_step_matches_single_device():
    """pjit 4x2 mesh train step == single-device step (same seed)."""
    r = _run("""
        from repro.models import ModelConfig, build_model
        from repro.distributed.step import make_train_step
        from repro.distributed import sharding as shd
        from repro.optim import adamw
        from repro.launch.mesh import make_host_mesh

        cfg = ModelConfig("t", "decoder", 2, 64, 4, 2, 128, 256)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 256)}
        step = make_train_step(model, adamw.AdamWConfig(lr=1e-3, total_steps=10))
        state0 = {"params": params, "opt": adamw.init(params)}
        s_ref, m_ref = jax.jit(step)(state0, batch)

        mesh = make_host_mesh(n_data=4, n_model=2)
        with mesh:
            sh = {"params": shd.make_param_shardings(params, mesh),
                  "opt": {"m": shd.make_param_shardings(params, mesh),
                          "v": shd.make_param_shardings(params, mesh),
                          "count": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}}
            state = jax.device_put({"params": params, "opt": adamw.init(params)}, sh)
            bsh = shd.batch_spec(batch, mesh)
            s_d, m_d = jax.jit(step, in_shardings=(sh, bsh))(state, jax.device_put(batch, bsh))
        dl = max(float(jnp.abs(a - b).max()) for a, b in
                 zip(jax.tree.leaves(s_ref["params"]), jax.tree.leaves(s_d["params"])))
        print("RESULT:" + json.dumps({"loss_ref": float(m_ref["loss"]),
                                      "loss_d": float(m_d["loss"]),
                                      "param_diff": dl}))
    """)
    assert abs(r["loss_ref"] - r["loss_d"]) < 1e-4, r
    assert r["param_diff"] < 1e-4, r


def test_compressed_allreduce_error_feedback():
    """fp8 EF all-reduce over shard_map: (a) single-round error bounded,
    (b) error feedback makes the *average over rounds* converge to the
    true mean gradient."""
    r = _run("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import ef_compress_allreduce

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024), jnp.float32)
        true_mean = jnp.mean(g, axis=0)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")))
        def reduce_once(gs, es):
            m, e = ef_compress_allreduce(gs[0], es[0], "data")
            return m[None], e[None]

        err_state = jnp.zeros_like(g)
        acc = jnp.zeros_like(true_mean)
        rounds = 30
        for _ in range(rounds):
            mean8, err_state = reduce_once(g, err_state)
            acc = acc + mean8[0]
        single = reduce_once(g, jnp.zeros_like(g))[0][0]
        rel1 = float(jnp.abs(single - true_mean).max() / jnp.abs(true_mean).max())
        relN = float(jnp.abs(acc / rounds - true_mean).max() / jnp.abs(true_mean).max())
        print("RESULT:" + json.dumps({"rel_single": rel1, "rel_avg": relN}))
    """)
    assert r["rel_single"] < 0.08, r          # one fp8 round: ~fp8 eps
    assert r["rel_avg"] < r["rel_single"] / 2, r   # EF cancels bias over rounds


def test_sharding_specs_divisibility_guards():
    r = _run("""
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(n_data=4, n_model=2)
        # batch=1 must fall back to replication; batch=8 shards
        import jax
        specs = shd.batch_spec({"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32),
                                "big": jax.ShapeDtypeStruct((8, 16), jnp.int32)}, mesh)
        s1 = specs["tokens"].spec
        s8 = specs["big"].spec
        # odd head dim must not shard on model
        p = shd.param_spec([], jax.ShapeDtypeStruct((64, 7), jnp.float32), mesh)
        print("RESULT:" + json.dumps({"b1": str(s1), "b8": str(s8), "odd": str(p)}))
    """)
    assert "None" in r["b1"] or r["b1"] == "PartitionSpec()", r
    assert "data" in r["b8"], r
    assert "model" not in r["odd"], r


def test_elastic_checkpoint_restore_new_mesh():
    """Save under a 4x2 mesh, restore under 2x4 — elastic scaling."""
    r = _run("""
        import tempfile
        from repro.models import ModelConfig, build_model
        from repro.distributed import sharding as shd
        from repro.runtime import checkpoint as ck
        from repro.launch.mesh import make_host_mesh

        cfg = ModelConfig("t", "decoder", 2, 64, 4, 2, 128, 256)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh1 = make_host_mesh(n_data=4, n_model=2)
        with mesh1:
            p1 = jax.device_put(params, shd.make_param_shardings(params, mesh1))
        d = tempfile.mkdtemp()
        ck.save(p1, 7, d)
        mesh2 = make_host_mesh(n_data=2, n_model=4)
        with mesh2:
            p2, step = ck.restore(d, params,
                                  shardings=shd.make_param_shardings(params, mesh2))
        diff = max(float(jnp.abs(a - b).max()) for a, b in
                   zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
        nshards = len(set(str(x.sharding) for x in jax.tree.leaves(p2)))
        print("RESULT:" + json.dumps({"step": step, "diff": diff}))
    """)
    assert r["step"] == 7 and r["diff"] == 0.0, r


@pytest.mark.slow
def test_dryrun_machinery_on_8_devices():
    """The dry-run lower+compile path itself, scaled to an 8-chip mesh
    stand-in via a reduced arch (full 512-dev sweep runs via
    `python -m repro.launch.dryrun --all`, recorded in EXPERIMENTS.md)."""
    r = _run("""
        from repro.configs import get_config, reduce_config
        from repro.distributed import sharding as shd
        from repro.distributed.step import make_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.launch import hlo_analysis as H
        from repro.models import build_model
        from repro.optim import adamw

        cfg = reduce_config(get_config("llama3.2-3b")).replace(remat="full")
        model = build_model(cfg)
        mesh = make_host_mesh(n_data=4, n_model=2)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        state = {"params": params, "opt": jax.eval_shape(adamw.init, params)}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        with mesh:
            sh = {"params": shd.make_param_shardings(state["params"], mesh),
                  "opt": {"m": shd.make_param_shardings(state["opt"]["m"], mesh),
                          "v": shd.make_param_shardings(state["opt"]["v"], mesh),
                          "count": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}}
            bsh = shd.batch_spec(batch, mesh)
            step = make_train_step(model, adamw.AdamWConfig())
            compiled = jax.jit(step, in_shardings=(sh, bsh)).lower(state, batch).compile()
        coll, recs = H.collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        print("RESULT:" + json.dumps({
            "coll_total": sum(coll.values()),
            "n_coll": len(recs),
            "temp": getattr(mem, "temp_size_in_bytes", -1)}))
    """)
    assert r["coll_total"] > 0 and r["n_coll"] > 0, r
    assert r["temp"] > 0, r


def test_compressed_train_step_routes_allreduce_through_plan():
    """make_compressed_train_step on an 8-way DP mesh: the exec-plan
    ``allreduce`` op serves the gradient collective.  With fmt_name=None
    the f32 psum reference route reproduces the single-device step to
    float-reassociation tolerance; with the fp8 wire route the loss
    stays close and the error-feedback state is live (nonzero)."""
    r = _run("""
        from repro.distributed.step import (init_err_state,
                                            make_compressed_train_step,
                                            make_train_step)
        from repro.launch.mesh import make_host_mesh
        from repro.models import ModelConfig, build_model
        from repro.optim import adamw

        cfg = ModelConfig("t", "decoder", 2, 64, 4, 2, 128, 256)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
                     jax.random.PRNGKey(1), (8, 32), 0, 256),
                 "labels": jax.random.randint(
                     jax.random.PRNGKey(2), (8, 32), 0, 256)}
        ocfg = adamw.AdamWConfig(lr=1e-3, total_steps=10)
        ref_step = make_train_step(model, ocfg)
        s_ref, m_ref = jax.jit(ref_step)(
            {"params": params, "opt": adamw.init(params)}, batch)

        mesh = make_host_mesh(n_data=8, n_model=1)
        out = {}
        for fmt in (None, "fp8_e4m3"):
            step = make_compressed_train_step(model, ocfg, mesh,
                                              fmt_name=fmt)
            state = {"params": params, "opt": adamw.init(params),
                     "err": init_err_state(params, 8)}
            with mesh:
                s_d, m_d = jax.jit(step)(state, batch)
            dl = max(float(jnp.abs(a - b).max()) for a, b in
                     zip(jax.tree.leaves(s_ref["params"]),
                         jax.tree.leaves(s_d["params"])))
            err_mag = max(float(jnp.abs(e).max())
                          for e in jax.tree.leaves(s_d["err"]))
            key = fmt or "psum"
            out[key] = {"loss": float(m_d["loss"]),
                        "param_diff": dl, "err_mag": err_mag}
        out["loss_ref"] = float(m_ref["loss"])
        print("RESULT:" + json.dumps(out))
    """)
    assert abs(r["psum"]["loss"] - r["loss_ref"]) < 1e-4, r
    assert r["psum"]["param_diff"] < 1e-4, r
    assert r["psum"]["err_mag"] == 0.0, r
    assert abs(r["fp8_e4m3"]["loss"] - r["loss_ref"]) < 1e-3, r
    # fp8 wire: one update's drift is bounded by the lr (the residual
    # feeds back next step), and the residual itself is live
    assert r["fp8_e4m3"]["param_diff"] < 5e-3, r
    assert r["fp8_e4m3"]["err_mag"] > 0.0, r
