"""A sound run is correct; the lower-precision control (the reference on
the fp4 grid, one step below the fp8 policy) is not, on the same tokens."""
from bench import run
from bench.tests.tiny import cell, run_tiny


def test_sound_run_is_correct_and_reports_every_metric(tmp_path):
    out = run_tiny(tmp_path)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] == 8 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"setup_s", "tokens_per_s", "ttft_p50_s", "itl_p99_ms"}
    assert all(v["value"] > 0 for v in m.values())
    assert out["device"]["platform"] == "cpu"


def test_control_is_not_correct(tmp_path, capfd):
    run_tiny(tmp_path, "--control", "fp4_e2m1")
    err = capfd.readouterr().err
    assert "control fp4_e2m1: correct False" in err
    assert "check max_logit_gap" in err.splitlines()[-4]


def test_judge_limits():
    conf = cell().config
    ok = {"max_gap": 0.01, "mean_gap": 0.001, "tokens": 40}
    assert run.judge(ok, 0, conf)[1] is True
    assert run.judge(dict(ok, mean_gap=0.05), 0, conf)[1] is False
    assert run.judge(dict(ok, max_gap=0.5), 0, conf)[1] is False
    assert run.judge(dict(ok, tokens=3), 0, conf)[1] is False
    assert run.judge(ok, 1, conf)[1] is False
    none = {"max_gap": None, "mean_gap": None, "tokens": 0}
    assert run.judge(none, 0, conf)[1] is False
    unset = dict(conf, correct={})
    assert run.judge(ok, 0, unset)[1] is False
