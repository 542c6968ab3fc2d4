"""The reduction from trace events to busy time, module time and idle
gaps, on a hand-built trace and on a small window recorded on the chip."""
import json
import os

import pytest

from bench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000          # ns


def _dev(line, name, a, b, plane="/device:TPU:0"):
    return [plane, line, name, a * MS, (b - a) * MS]


HAND = {
    "host": [["bench.window", 0, 100 * MS], ["bench.submit", 5 * MS, 3 * MS],
             ["bench.step", 10 * MS, 30 * MS], ["bench.wait", 40 * MS, 10 * MS],
             ["bench.step", 50 * MS, 40 * MS]],
    "device": [
        _dev("XLA Ops", "while.4", 12, 38),
        _dev("XLA Ops", "fusion.1", 12, 20),
        _dev("XLA Ops", "dpa_matmul_fused.3", 18, 25),
        _dev("XLA Ops", "paged_decode_attention.1", 30, 38),
        _dev("XLA Ops", "copy.2", 45, 47),
        _dev("XLA Ops", "fusion.1", 60, 95),
        _dev("XLA Ops", "fusion.1", 105, 110),
        _dev("XLA Modules", "jit_step(7)", 12, 38),
        _dev("XLA Modules", "jit_decode_step(9)", 60, 95)]}


def test_hand_trace():
    r = tr.reduce(HAND)
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [12,25] [30,38] [45,47] [60,95] = 13 + 8 + 2 + 35
    assert r["busy_s"] == pytest.approx(0.058)
    # steps [10,40] and [50,90]; busy inside them 13 + 8 + 30
    assert r["step_s"] == pytest.approx(0.070)
    assert r["busy_in_step_s"] == pytest.approx(0.051)
    assert r["modules"]["jit_step(7)"] == [1, pytest.approx(0.026)]
    assert r["modules"]["jit_decode_step(9)"] == [1, pytest.approx(0.035)]
    # ops are named after the module execution that holds them; the loop
    # that holds other ops is left out
    assert r["ops"]["jit_step/fusion.1"] == [1, pytest.approx(0.008)]
    assert r["ops"]["jit_decode_step/fusion.1"] == [1, pytest.approx(0.035)]
    assert r["ops"]["copy.2"] == [1, pytest.approx(0.002)]
    assert not [n for n in r["ops"] if "while" in n] and r["chips"] == 1
    # idle, longest first (the later of two equal ones first): [47,60]
    # in a step, [0,12] in a submit, [38,45] waiting, [95,100] outside
    # every span, [25,30] in a step
    assert [g[0] for g in r["gaps"]] == ["bench.step", "bench.submit",
                                         "bench.wait", "host", "bench.step"]
    assert [round(g[1] * 1e3, 6) for g in r["gaps"]] == [13, 12, 7, 5, 5]
    assert tr.top_ops(r, 1) == [["jit_decode_step/fusion.1",
                                 pytest.approx(0.035)]]
    assert tr.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.3"


def test_two_chips_average():
    ev = {"host": HAND["host"], "device": HAND["device"] + [
        _dev("XLA Ops", "fusion.1", 0, 100, plane="/device:TPU:1")]}
    r = tr.reduce(ev)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((0.058 + 0.100) / 2)


def test_recorded_chip_window():
    """30 ms of a qwen3-4b.chat trace recorded on a v5e (the end of one
    scheduler step, a submission, the start of the next), checked against
    counts made independently on a 10 ns grid."""
    with open(os.path.join(HERE, "data", "chip_trace.json")) as f:
        rec = json.load(f)
    r = tr.reduce(rec["events"])
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, abs=2e-7), key
    mods = dict(rec["expect_modules"])
    assert len(r["modules"]) == mods.pop("count")
    for name, (n, sec) in mods.items():
        assert r["modules"][name] == [n, pytest.approx(sec, abs=1e-12)]
    assert r["gaps"][0][0] == "bench.step"


def test_load_reads_host_spans(tmp_path):
    """The loader finds the harness's spans in a profile the JAX profiler
    wrote (on the CPU there is no device plane)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.load(tr.find(str(tmp_path)))
    names = [h[0] for h in ev["host"]]
    assert names.count("bench.window") == 1 and "bench.step" in names
    r = tr.reduce(ev)
    assert r["step_s"] > 0 and r["chips"] == 0
