"""The engine's profiler spans and per-step counters, read back from a
real `jax.profiler` trace of a tiny engine on the CPU: every phase span
sits inside its `engine.step`, carries the right request ids, and the
step's stats count what the tick did."""
import math

import numpy as np
import pytest

import jax

from bench import engine_trace as et
from bench import trace_reduce as tr
from repro.configs import get_config, reduce_config
from repro.launch.engine import DECODE, Engine, EngineConfig, Request
from repro.models import build_model
from repro.serving import SpecConfig

ECFG = EngineConfig(page_size=8, n_pages=32, max_batch=3,
                    max_pages_per_req=4, token_budget=8, prefill_chunk=8)
# one prompt of three chunks (8 + 8 + 4), more requests than slots, and
# requests that finish while others still prefill
LENS = [(20, 3), (5, 2), (9, 4), (6, 2)]
PLAIN = ("engine.step", "engine.admit", "engine.decode", "engine.readback",
         "engine.prefill_chunk", "engine.scatter", "engine.first_token",
         "engine.table_sync")


@pytest.fixture(scope="module")
def model_and_params():
    cfg = reduce_config(get_config("qwen3-4b")).replace(
        policy="kv4_attn8_packed")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(vocab):
    rng = np.random.default_rng(5)
    return [Request(rid=10 + i, max_new=g,
                    prompt=rng.integers(0, vocab, s0).astype(np.int32))
            for i, (s0, g) in enumerate(LENS)]


def _serve_traced(engine, reqs, tmp_path):
    """Serve `reqs` to the end under the profiler; -> (engine spans,
    decode slots live before each step, first tokens of each step)."""
    live, firsts = [], []
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for r in reqs:
            engine.submit(r)
        while engine.waiting or any(engine.slots):
            live.append(sum(r is not None and r.state == DECODE
                            for r in engine.slots))
            had = sum(r.n_generated > 0 for r in reqs)
            engine.step(0.0)
            firsts.append(sum(r.n_generated > 0 for r in reqs) - had)
    jax.profiler.stop_trace()
    ev = et.load(tr.find(str(tmp_path)))
    return ev, live, firsts


def _steps_and_children(engine_events):
    steps = sorted((s, s + d, st) for n, s, d, st in engine_events
                   if n == "engine.step")
    kids = {}
    for n, s, d, st in engine_events:
        if n == "engine.step":
            continue
        held = [i for i, (a, b, _) in enumerate(steps) if a <= s and s + d <= b]
        assert len(held) == 1, (n, st)
        kids.setdefault(held[0], []).append((n, st))
    return steps, kids


def test_plain_path_spans_and_counters(model_and_params, tmp_path):
    model, params = model_and_params
    engine = Engine(model, params, ECFG)
    reqs = _requests(model.cfg.vocab_size)
    ev, live, firsts = _serve_traced(engine, reqs, tmp_path)
    spans = ev["engine"]
    names = {n for n, *_ in spans}
    assert set(PLAIN) <= names
    assert not names & {"engine.spec_round", "engine.cow_copy",
                        "engine.prefix_load"}
    steps, kids = _steps_and_children(spans)
    assert len(steps) == len(live) == engine.n_steps
    rids = sorted(r.rid for r in reqs)
    by = {}
    for n, s, d, st in spans:
        by.setdefault(n, []).append(st)
    assert sorted(st["rid"] for st in by["engine.admit"]) == rids
    assert sorted(st["rid"] for st in by["engine.first_token"]) == rids
    assert sorted(st["rid"] for st in by["engine.scatter"]) == rids
    for r in reqs:
        chunks = [st for st in by["engine.prefill_chunk"]
                  if st["rid"] == r.rid]
        assert sum(st["tokens"] for st in chunks) == r.n_prompt
        assert [st["start"] for st in chunks] == list(
            range(0, r.n_prompt, ECFG.prefill_chunk))
        sc = [st for st in by["engine.scatter"] if st["rid"] == r.rid][0]
        assert sc["pages"] == -(-(r.n_prompt + r.max_new) // ECFG.page_size)
        assert sc["rows"] == r.n_prompt - r.prefill_skip == r.n_prompt
    assert len([st for st in by["engine.prefill_chunk"]
                if st["rid"] == 10]) == 3
    for i, (_, _, st) in enumerate(steps):
        assert st["step"] == i
        assert st["decode_live"] == live[i]
        assert st["host_reads"] == (live[i] > 0) + firsts[i]
        mine = kids.get(i, [])
        assert st["prefill_chunks"] == sum(
            n == "engine.prefill_chunk" for n, _ in mine)
        assert st["prefill_tokens"] == sum(
            s["tokens"] for n, s in mine if n == "engine.prefill_chunk")
        assert st["admitted"] == sum(n == "engine.admit" for n, _ in mine)
        assert st["table_syncs"] == sum(n == "engine.table_sync"
                                        for n, _ in mine)
        assert [s["live"] for n, s in mine if n == "engine.decode"] == \
            [live[i]]
        assert (("engine.readback", {}) in mine) == (live[i] > 0)
    stats = [st for _, _, st in steps]
    decodes = sum(n > 0 for n in live)
    assert sum(st["host_reads"] for st in stats) == decodes + len(reqs)
    assert sum(st["finished"] for st in stats) == len(reqs)
    assert sum(st["admitted"] for st in stats) == len(reqs)
    assert stats[0]["waiting"] == len(reqs) - ECFG.max_batch
    assert stats[-1]["waiting"] == 0
    # the reduction reads the three points of each request, in order
    red = et.reduce(ev)
    assert sorted(red["requests"]) == rids
    for r in red["requests"].values():
        assert r["admitted"] <= r["prefill_start"] <= r["first_token"]
    assert [s["step"] for s in red["engine_steps"]] == list(range(len(live)))
    assert red["engine_spans"]["engine.step"][0] == len(live)
    assert all(math.isfinite(v[1]) for v in red["engine_spans"].values())


def test_spec_path_records_its_rounds(model_and_params, tmp_path):
    model, params = model_and_params
    k = 2
    engine = Engine(model, params, ECFG,
                    spec=SpecConfig("w4a4_kv4_attn4", k=k))
    reqs = _requests(model.cfg.vocab_size)
    ev, live, firsts = _serve_traced(engine, reqs, tmp_path)
    steps, kids = _steps_and_children(ev["engine"])
    rounds = [st for n, _, _, st in ev["engine"] if n == "engine.spec_round"]
    assert len(rounds) == engine.spec_rounds > 0
    assert all(st["k"] == k and "rung" not in st for st in rounds)
    assert not any(n == "engine.decode" for n, *_ in ev["engine"])
    for i, (_, _, st) in enumerate(steps):
        mine = [s for n, s in kids.get(i, []) if n == "engine.spec_round"]
        assert [s["live"] for s in mine] == ([live[i]] if live[i] else [])
        assert st["decode_live"] == live[i]
        assert st["host_reads"] == 2 * len(mine) + firsts[i]
