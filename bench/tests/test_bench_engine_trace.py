"""The reduction of the engine's spans: gap labels, idle time by span,
request points and step stats on a hand-built trace, the five numbers
read from them, and a traced run of the tiny cell on the CPU."""
from types import SimpleNamespace

import pytest

from bench import engine_trace as et
from bench import trace_reduce as tr
from bench.tests.test_bench_trace_reduce import HAND, MS


def _eng(name, a, b, **stats):
    return [name, a * MS, (b - a) * MS, stats]


# HAND's host and device events with the engine's spans inside its two
# steps, and one step after the window's close
HAND_ENGINE = dict(HAND, engine=[
    _eng("engine.step", 11, 39, step=0, decode_live=2, host_reads=1),
    _eng("engine.admit", 11, 12, rid=5),
    _eng("engine.decode", 12, 27, live=2),
    _eng("engine.readback", 24, 27),
    _eng("engine.prefill_chunk", 27, 29, rid=5, start=0, tokens=8),
    _eng("engine.table_sync", 37, 39),
    _eng("engine.step", 51, 89, step=1, decode_live=0, host_reads=1),
    _eng("engine.prefill_chunk", 51, 53, rid=5, start=8, tokens=4),
    _eng("engine.scatter", 53, 58, rid=5, pages=2),
    _eng("engine.first_token", 58, 59, rid=5),
    _eng("engine.step", 101, 104, step=2, decode_live=1, host_reads=1),
    _eng("engine.admit", 102, 103, rid=6)])


def test_no_engine_spans_reduce_as_before():
    """A trace without engine spans (a program that has none) gives every
    key of `trace_reduce.reduce` the same value and label; the new keys
    hold nothing but the steps' idle time, all under `bench.step`."""
    base = tr.reduce(HAND)
    for ev in (HAND, dict(HAND, engine=[])):
        r = et.reduce(ev)
        assert {k: r[k] for k in base} == base
        assert r["engine_spans"] == {} and r["engine_steps"] == []
        assert r["requests"] == {}
        # idle in steps: [10,12] [25,30] [38,40] [50,60]
        assert r["idle_by_span"] == {"bench.step": pytest.approx(0.019)}


def test_engine_spans_label_gaps_and_split_idle():
    r, base = et.reduce(HAND_ENGINE), tr.reduce(HAND)
    for key in ("window_s", "busy_s", "step_s", "busy_in_step_s", "chips",
                "modules", "ops"):
        assert r[key] == base[key], key
    # the same gaps, those inside engine spans named after the innermost
    assert [g[1] for g in r["gaps"]] == [g[1] for g in base["gaps"]]
    assert [g[0] for g in r["gaps"]] == [
        "engine.scatter", "bench.submit", "bench.wait", "host",
        "engine.prefill_chunk"]
    ms = {k: round(v * 1e3, 6) for k, v in r["idle_by_span"].items()}
    assert ms == {"bench.step": 3, "engine.admit": 1, "engine.readback": 2,
                  "engine.prefill_chunk": 4, "engine.step": 2,
                  "engine.table_sync": 1, "engine.scatter": 5,
                  "engine.first_token": 1}
    assert sum(ms.values()) == round((r["step_s"] - r["busy_in_step_s"])
                                     * 1e3, 6)
    # spans and steps that start in the window; request points from the
    # whole trace
    assert r["engine_spans"]["engine.step"] == [2, pytest.approx(0.066)]
    assert r["engine_spans"]["engine.prefill_chunk"] == [
        2, pytest.approx(0.004)]
    assert r["engine_spans"]["engine.admit"][0] == 1
    assert [s["step"] for s in r["engine_steps"]] == [0, 1]
    assert r["requests"][5] == {"admitted": pytest.approx(0.012),
                                "prefill_start": pytest.approx(0.027),
                                "first_token": pytest.approx(0.059)}
    assert r["requests"][6] == {"admitted": pytest.approx(0.103)}


def test_nest_pieces():
    spans = [["a", 0, 10], ["b", 2, 3], ["c", 2, 1], ["d", 6, 4]]
    assert et.nest(spans) == [[0, 2, "a"], [2, 3, "c"], [3, 5, "b"],
                              [5, 6, "a"], [6, 10, "d"]]
    assert et.subtract([[0, 10], [20, 30]], [[2, 3], [8, 22], [25, 40]]) \
        == [[0, 2], [3, 8], [22, 25]]


def _record(trace, tracks):
    return {"seconds": 0.1, "steps": [], "trace": trace,
            "geometry": {"max_batch": 4},
            "tracks": [SimpleNamespace(rid=rid, due=due, in_window=w)
                       for rid, due, w in tracks]}


def test_engine_metrics_on_a_hand_record():
    read = et.METRICS
    red = et.reduce(HAND_ENGINE)
    # request 7 was never admitted; 8 was due before the window
    rec = _record(red, [(5, 0.005, True), (6, 0.09, True), (7, 0.02, True),
                        (8, -1.0, False)])
    assert read["scheduler.admit_wait_p50_s"](rec) == pytest.approx(0.013)
    assert read["scheduler.prefill_wait_p50_s"](rec) == float("inf")
    assert read["model.prompt_prefill_p50_s"](rec) == float("inf")
    one = _record(red, [(5, 0.005, True)])
    assert read["scheduler.admit_wait_p50_s"](one) == pytest.approx(0.007)
    assert read["scheduler.prefill_wait_p50_s"](one) == pytest.approx(0.015)
    assert read["model.prompt_prefill_p50_s"](one) == pytest.approx(0.032)
    # the steps in the window: one decode of 2 live in 4 slots; 2 reads
    assert read["scheduler.decode_occupancy"](rec) == pytest.approx(50.0)
    assert read["scheduler.host_reads_per_step"](rec) == pytest.approx(1.0)
    # without engine spans there is nothing to read
    for f in read.values():
        assert f(_record(et.reduce(HAND), [(5, 0.005, True)])) is None


def test_traced_tiny_run_reads_the_engine(tmp_path, capfd):
    """A traced run of the tiny cell on the CPU: the engine's spans reach
    the reduction, each of the five numbers is finite, the stderr lines
    name the engine spans, and the kept trace is removed."""
    import jax

    from bench import run
    from bench.tests.tiny import SEED, cell, root
    args = run.parse(["--workload", "tiny.chat", "--seed", str(SEED),
                      "--seconds", "2", "--trace", "0"])
    r = root(tmp_path)
    out = et.traced(run.run_cell, cell(), args, jax.devices(), root=r)
    assert out["correct"]
    m = out["engine"]["metrics"]
    assert sorted(m) == sorted(et.METRICS)
    assert 0 < m["scheduler.decode_occupancy"] <= 100
    assert m["scheduler.host_reads_per_step"] >= 1
    assert out["engine"]["spans"]["engine.step"][0] >= 1
    err = capfd.readouterr().err
    assert "engine span: engine.step x" in err
    assert "engine span: engine.prefill_chunk x" in err
    assert not list((tmp_path / "root" / ".bench_trace").glob("*.pb"))
