"""BENCHMARK.json's names and units, and that the harness finds a cell's
parts by name alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec, workcount

ROOT = spec.ROOT


def test_names_units_and_files():
    b = spec.load_benchmark()
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    names = []
    for c in b["configs"]:
        names.append(c["name"])
        assert all(spec.NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        for k in c["reduced"]:
            assert k in conf["published"] and conf[k] != conf["published"][k]
    for w in b["workloads"]:
        names.append(w["name"])
        assert spec.NAME.match(w["config"]) and spec.NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(ROOT, "bench", "cells",
                                           w["name"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        names.append(m["name"])
        assert spec.UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                              "higher")
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    assert all(spec.NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert 0.01 <= min(m["bound"] for m in b["end_to_end"])
    assert max(m["bound"] for m in b["end_to_end"]) <= 0.25


def test_new_cell_found_by_name(tmp_path):
    """A configuration, mix, cell and per-layer metric added as files (and
    entries in BENCHMARK.json) in a copy of bench/ are found with no edit
    to the harness."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = spec.load_benchmark()
    conf = json.loads((root / "bench" / "configs" / "qwen3-4b.json")
                      .read_text())
    conf["num_hidden_layers"] = 7
    (root / "bench" / "configs" / "new-model.json").write_text(
        json.dumps(conf))
    (root / "bench" / "traffic" / "new-mix.json").write_text(json.dumps(
        {"arrivals": "poisson",
         "prompt": {"dist": "uniform", "min": 10, "max": 20},
         "output": {"dist": "uniform", "min": 1, "max": 4}}))
    (root / "bench" / "cells" / "new-model.new-mix.json").write_text(
        json.dumps({"rate_per_s": 3.5, "why": "test"}))
    (root / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    b["configs"].append({"name": "new-model", "source": "x",
                         "file": "bench/configs/new-model.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "new-model.new-mix",
                           "config": "new-model", "traffic": "new-mix",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "new.metric", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "tokens_per_s",
                           "workloads": ["new-model.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("new-model.new-mix", root=str(root))
    assert cell.config_name == "new-model"
    assert cell.config["num_hidden_layers"] == 7
    assert cell.mix["prompt"]["max"] == 20 and cell.rate == 3.5
    assert "new.metric" in [m["name"] for m in cell.per_layer]
    assert spec.load_reader("new.metric", str(root))({}) == 42.0
    old = spec.load_cell(b["workloads"][0]["name"], root=str(root))
    assert "new.metric" not in [m["name"] for m in old.per_layer]
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", root=str(root))


def test_run_refuses_a_cpu():
    """No accelerator: a non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "qwen3-4b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


TOY_FAMILY = '''"""A test family: the dense decoder's model, with a census of its own,
that logs each call beside this file."""
import os

from bench import spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def log(what):
    with open(os.path.join(ROOT, "toy.calls"), "a") as f:
        f.write(what + "\\n")


def dense(conf):
    return spec.load_family(dict(conf, family="dense_decoder"), ROOT)


def model_config(conf, name):
    log("model_config")
    return dense(conf).model_config(conf, name)


def step_flops(conf, step):
    log("step_flops")
    return 2.0 * dense(conf).step_flops(conf, step)


def fused_linears(conf):
    log("fused_linears")
    return [(k, n, 1) for k, n, _ in dense(conf).fused_linears(conf)]


def paged_layers(conf):
    log("paged_layers")
    return 1
'''

TOY_REFERENCE = '''"""A test reference: the dense decoder's, logged."""
import os

from bench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def served_gaps(weights, conf, tokens, targets, *, length, quant=None):
    with open(os.path.join(ROOT, "toy.calls"), "a") as f:
        f.write("served_gaps\\n")
    ref = spec.load_reference(dict(conf, family="dense_decoder"), ROOT)
    return ref.served_gaps(weights, conf, tokens, targets, length=length,
                           quant=quant)
'''


def _digests(root) -> dict:
    import hashlib
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_family_found_by_name(tmp_path):
    """A second family added to a copy of bench/ as new files only -- its
    module, its reference, a configuration that names it, a cell and its
    entries -- is what a run of that cell builds the program's model
    from, counts work with and compares against; no file of the copy
    changes."""
    import types

    import jax

    from bench import run
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root / "bench")
    data = os.path.join(ROOT, "bench", "tests", "data")
    conf = json.loads(open(os.path.join(data, "tiny.json")).read())
    conf["family"] = "toy"
    new = {"families/toy.py": TOY_FAMILY, "reference/toy.py": TOY_REFERENCE,
           "configs/toy.json": json.dumps(conf),
           "traffic/toy-chat.json": open(os.path.join(
               data, "tiny-chat.json")).read(),
           "cells/toy.toy-chat.json": json.dumps(
               {"rate_per_s": 4.0, "preload": 6, "why": "test"})}
    for rel, text in new.items():
        (root / "bench" / rel).write_text(text)
    b = spec.load_benchmark()
    b["configs"].append({"name": "toy", "source": "x", "reduced": [],
                         "file": "bench/configs/toy.json", "why": "test"})
    b["workloads"].append({"name": "toy.toy-chat", "config": "toy",
                           "traffic": "toy-chat", "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        m["workloads"].append("toy.toy-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("toy.toy-chat", root=str(root))
    # the CPU stands in for a chip the peak table knows, whose peaks
    # the readers then use; the peak table itself is left as it is
    cpu = jax.devices()[0]
    dev = types.SimpleNamespace(platform=cpu.platform,
                                device_kind="TPU v5 lite",
                                memory_stats=cpu.memory_stats)
    args = run.parse(["--workload", cell.name, "--seed", "5", "--seconds",
                      "2", "--trace", "1"])
    out = run.run_cell(cell, args, [dev], root=str(root))
    assert out["correct"] is True, out["checks"]
    calls = (root / "toy.calls").read_text().split()
    assert {"model_config", "served_gaps", "step_flops"} <= set(calls)
    assert "step.mfu" in out["metrics"]
    # the kernel readers, on a record with the kernels' device time
    steps = [{"t0": 0.0, "t1": 0.5, "prefill": [(0, 16)],
              "decode_ctx": [20, 30], "firsts": 1}]
    rec = {"seconds": 1.0, "steps": steps, "conf": cell.config,
           "geometry": {"max_batch": 4, "prefill_chunk": 16},
           "peak": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
           "root": str(root), "trace": {"modules": {}, "ops": {
               "jit_step/dpa_matmul_fused.1": [9, 1.0],
               "jit_step/paged_decode_attention.2": [2, 1.0]}}}
    fp8 = spec.load_reader("kernel.fp8_linear_roofline", str(root))(rec)
    paged = spec.load_reader("kernel.paged_decode_roofline", str(root))(rec)
    dense = spec.load_family(dict(cell.config, family="dense_decoder"))
    want = sum(c * workcount.roofline_seconds(*workcount.fp8_linear_call(
        m, k, n), rec["peak"])[0] for m, c in ((4, 1), (16, 1))
        for k, n, _ in dense.fused_linears(cell.config))
    assert fp8 == pytest.approx(100.0 * want)
    assert paged == pytest.approx(100.0 * workcount.roofline_seconds(
        *workcount.paged_decode_layer(cell.config, [20, 30]),
        rec["peak"])[0])
    calls = (root / "toy.calls").read_text().split()
    assert {"fused_linears", "paged_layers"} <= set(calls)
    after = _digests(root / "bench")
    assert {p: after.get(p) for p in before} == before


def test_unknown_family_fails_naming_the_file(tmp_path):
    """A configuration whose family has no module, or that names none,
    stops the run before anything is built."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = spec.load_benchmark()
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    name = b["workloads"][0]["name"]
    path = root / "bench" / "configs" / (b["workloads"][0]["config"]
                                         + ".json")
    conf = json.loads(path.read_text())
    path.write_text(json.dumps(dict(conf, family="no_such_family")))
    with pytest.raises(FileNotFoundError) as e:
        spec.load_cell(name, root=str(root))
    assert os.path.join("bench", "families", "no_such_family.py") in \
        str(e.value)
    with pytest.raises(FileNotFoundError, match="no_such_family.py"):
        spec.load_family(dict(conf, family="no_such_family"), str(root))
    del conf["family"]
    path.write_text(json.dumps(conf))
    with pytest.raises(ValueError, match="names no family"):
        spec.load_cell(name, root=str(root))


def test_every_configuration_names_its_family():
    """Each configuration of BENCHMARK.json names a family that has a
    module and a reference."""
    b = spec.load_benchmark()
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        family = spec.load_family(conf)
        assert all(callable(getattr(family, f)) for f in (
            "model_config", "step_flops", "fused_linears", "paged_layers"))
        assert callable(spec.load_reference(conf).served_gaps)
