"""Finds a cell's parts by name: `BENCHMARK.json` names the cells, and
each configuration, traffic mix, offered rate and per-layer metric is a
file of its own under this directory.  Adding a cell is adding files and
entries; nothing here names a cell, configuration, mix or metric.

  bench/configs/<config>.json   sizes as run, source, cut, engine geometry
  bench/traffic/<mix>.json      the mix's lengths and arrival process
  bench/cells/<cell>.json       the rate a cell offers, the requests in
                                flight when its window opens, and why
  bench/metrics/<metric>.py     a reader: `read(record) -> float | None`
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

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
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
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


def load_reader(metric: str, root: str = ROOT):
    """The `read` function of bench/metrics/<metric>.py."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
