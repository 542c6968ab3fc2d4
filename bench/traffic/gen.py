"""The one traffic generator: open-loop requests from a mix file and a seed.

A mix file (`bench/traffic/<mix>.json`) gives the shape of the traffic:
prompt and output length distributions with their clips, and the arrival
process.  The offered rate belongs to a cell (`bench/cells/<cell>.json`),
because each configuration is offered its own fraction of its own knee.

Every seed gets the same work.  The (prompt, output) sizes are a fixed
function of the mix (stratified quantiles of each distribution), and so
are the inter-arrival gaps (stratified exponential quantiles at the
rate); the mix's `size_seed` pairs and orders them once.  The run's seed
draws the prompt tokens (and, in the harness, the weights): two seeds
offer the same schedule of the same sizes with different content, so
what a run measures does not move with the seed.  (A seed that
reordered the schedule moved the tokens emitted inside a 51 s window by
+-10% and the median TTFT of its dozen requests by +-30%.)
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Req:
    rid: int
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # (n,) int32 token ids
    max_new: int
    in_window: bool             # due before the window closes


def load_mix(name: str, traffic_dir: str = HERE) -> dict:
    path = os.path.join(traffic_dir, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, in quantile order:
    the i-th is the quantile at (i + 0.5) / n, rounded and clipped to
    [min, max].  `lognormal` takes `median` and `sigma`; `uniform` draws
    whole numbers over [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(v) for v in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def sizes(mix: dict, n: int, max_total: int) -> list:
    """The fixed multiset of (prompt, output) lengths for n requests:
    prompts and outputs each stratified and clipped by the mix, paired by
    the mix's size_seed.  The clips must fit `max_total`, the context the
    configuration serves: a request longer than it could never be
    admitted, so a mix that allows one is an error, not a silent cut."""
    if mix["prompt"]["max"] + mix["output"]["max"] > max_total:
        raise ValueError(f"mix allows {mix['prompt']['max']} + "
                         f"{mix['output']['max']} tokens; the "
                         f"configuration serves {max_total}")
    p = lengths(mix["prompt"], n)
    o = lengths(mix["output"], n)
    o = o[np.random.default_rng(mix.get("size_seed", 0)).permutation(n)]
    return [(int(a), int(b)) for a, b in zip(p, o)]


def gaps(n: int, rate: float, span: float) -> np.ndarray:
    """n stratified exponential inter-arrival gaps at `rate`, scaled to
    sum to exactly `span` (so the last of them lands at `span`)."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    return g * (span / g.sum())


def preload(mix: dict, n: int, seed: int, vocab: int, page_size: int) -> list:
    """The n requests a server under this load already holds when the
    window opens: sizes from the mix (its first `page_size` prompt
    lengths nudged so that every residue modulo the page size occurs,
    which warms every page-scatter shape), each with a remaining answer
    drawn between one token and its full length."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 5])
    order = np.random.default_rng([mix.get("size_seed", 0), 5])
    sz = sizes(mix, n, mix["prompt"]["max"] + mix["output"]["max"])
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    u = (np.arange(n) + 0.5) / n
    left = order.permutation(u)
    out = []
    for j, k in enumerate(order.permutation(n)):
        p, o = sz[k]
        if j < page_size:
            p += (j - p) % page_size
            if p > hi:
                p -= page_size
            p = max(p, lo)
        out.append(Req(rid=-1 - j, due=-1.0,
                       prompt=rng.integers(0, vocab, size=p, dtype=np.int32),
                       max_new=max(1, int(np.ceil(left[j] * o))),
                       in_window=False))
    return out


def generate(mix: dict, rate: float, seed: int, seconds: float, vocab: int,
             max_total: int, tail_seconds: float = 60.0) -> list:
    """Requests due in the window [0, seconds) -- floor(rate * seconds)
    of them -- then a tail of the same traffic for `tail_seconds` more,
    which keeps the load on while the window's stragglers finish."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    order = np.random.default_rng([mix.get("size_seed", 0), 7])
    n = max(1, int(math.floor(rate * seconds)))
    m = max(1, int(math.ceil(rate * tail_seconds)))
    reqs, t0 = [], 0.0
    for block, (count, in_window) in enumerate(((n, True), (m, False))):
        sz = sizes(mix, count, max_total)
        perm = order.permutation(count)
        due = t0 + np.cumsum(order.permutation(gaps(count, rate,
                                                    count / rate)))
        if in_window:
            # the n-th window request lands at n / rate <= seconds
            due = due * min(1.0, seconds / due[-1] * (1 - 1e-9))
        for j in range(count):
            p, o = sz[perm[j]]
            reqs.append(Req(rid=len(reqs), due=float(due[j]),
                            prompt=rng.integers(0, vocab, size=p,
                                                dtype=np.int32),
                            max_new=o, in_window=in_window))
        t0 = float(due[-1])
    return reqs
