"""BENCHMARK.json's names and units, and that the harness finds a cell's
parts by name alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

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
