"""A whole run of the harness on the CPU at test size: the look for the
chip skipped, the Pallas kernels interpreted, everything else as on the
chip (weights from the seed, the engine through its entry point, warm-up,
the open-loop window, the reference check)."""
import json
import os
import shutil

import jax

from bench import run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**32 + 17


def cell():
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        conf = json.load(f)
    with open(os.path.join(HERE, "data", "tiny-chat.json")) as f:
        mix = json.load(f)
    b = spec.load_benchmark()
    return spec.Cell(name="tiny.chat", config_name="tiny", config=conf,
                     mix=mix, rate=4.0, preload=6, chips=1,
                     end_to_end=b["end_to_end"],
                     per_layer=b["per_layer"])


def root(tmp_path) -> str:
    """A checkout-like root whose peak table knows the CPU."""
    r = tmp_path / "root"
    for part in ("metrics", "families", "reference"):
        shutil.copytree(os.path.join(spec.BENCH, part), r / "bench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (r / "bench" / "peaks.json").write_text(json.dumps(
        {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}))
    return str(r)


def run_tiny(tmp_path, *extra, fault=None, seed=SEED):
    args = run.parse(["--workload", "tiny.chat", "--seed", str(seed),
                      "--seconds", "2", "--trace", "0", *extra])
    return run.run_cell(cell(), args, jax.devices(), root=root(tmp_path),
                        fault=fault)
