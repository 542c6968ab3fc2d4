"""Finds a cell's parts by name: `BENCHMARK.json` names the cells, and
each configuration, traffic mix, offered rate and per-layer metric is a
file of its own under this directory.  Adding a cell is adding files and
entries; nothing here names a cell, configuration, mix or metric.

  bench/configs/<config>.json   sizes as run, source, cut, engine geometry
  bench/traffic/<mix>.json      the mix's lengths and arrival process
  bench/cells/<cell>.json       the rate a cell offers, the requests in
                                flight when its window opens, and why
  bench/metrics/<metric>.py     a reader: `read(record) -> float | None`
  bench/families/<family>.py    what the harness needs of a model family:
                                the program's model config and the census
                                of its work
  bench/reference/<family>.py   the family's plain float32 reference:
                                `served_gaps(weights, conf, tokens,
                                targets, length=, quant=)`

A configuration file names its family under the key `family`; there is
no default, so a new architecture enters as files too.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict            # bench/configs/<config>.json
    mix: dict               # bench/traffic/<mix>.json
    rate: float             # offered requests per second
    preload: int            # requests in flight when the window opens
    chips: int
    end_to_end: list        # metric entries of BENCHMARK.json it reports
    per_layer: list


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_file = os.path.join(root, configs[w["config"]]["file"])
    with open(conf_file) as f:
        config = json.load(f)
    for kind in ("families", "reference"):
        path = _family_file(config, kind, root, conf_file)
        if not os.path.exists(path):
            raise FileNotFoundError(f"{conf_file} names family "
                                    f"{config['family']!r}; no file {path}")
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "traffic", f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    with open(os.path.join(bdir, "cells", f"{name}.json")) as f:
        load = json.load(f)
    return Cell(name=name, config_name=w["config"], config=config, mix=mix,
                rate=float(load["rate_per_s"]),
                preload=int(load.get("preload", 0)),
                chips=int(w["chips"]), end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def _module(path: str, name: str):
    """The module of a file under bench/, loaded once per process and
    path (a reference's compiled programs then serve every run of a
    sweep)."""
    name = name.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(name)
    if mod is not None and mod.__file__ == path:
        return mod
    if not os.path.exists(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


def load_reader(metric: str, root: str = ROOT):
    """The `read` function of bench/metrics/<metric>.py."""
    return _module(os.path.join(root, "bench", "metrics", f"{metric}.py"),
                   "bench_metric_" + metric).read


def _family_file(conf: dict, kind: str, root: str,
                 conf_file: str = "the configuration") -> str:
    family = conf.get("family")
    if not isinstance(family, str) or not NAME.match(family):
        raise ValueError(f"{conf_file} names no family: give it a key "
                         f"\"family\" that names bench/families/<family>.py "
                         f"and bench/reference/<family>.py")
    return os.path.join(root, "bench", kind, f"{family}.py")


def load_family(conf: dict, root: str = ROOT):
    """The module bench/families/<family>.py of the configuration's
    family: `model_config`, `step_flops`, `fused_linears`,
    `paged_layers`."""
    path = _family_file(conf, "families", root)
    return _module(path, "bench_family_" + conf["family"])


def load_reference(conf: dict, root: str = ROOT):
    """The module bench/reference/<family>.py of the configuration's
    family: `served_gaps`."""
    path = _family_file(conf, "reference", root)
    return _module(path, "bench_reference_" + conf["family"])
