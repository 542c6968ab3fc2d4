"""Run one benchmark cell on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Phases, in one process: check that JAX sees a TPU with the chips the
cell asks for; turn on the persistent compilation cache inside the
checkout; take the program's model config from the configuration's
family (`bench/families/<family>.py`); build the weights on the device
from the seed and the server through the program's own entry point;
warm up the cell's shapes and load the server with the requests it
would hold under this traffic (the prefill chunk, the decode step, and
a prompt length of every residue modulo the page size, so that every
eager page-scatter shape compiles); serve the cell's open-loop traffic
for `--seconds`; read the peak device memory; free the server; and
check the served tokens against the family's float32 reference.  With
`--trace 1` the window runs under the profiler and the line carries the
per-layer metrics; with `--trace 0`, the end-to-end ones.  The last lines on stderr, and the `checks` key that closes the
result line, give each number compared with its limit.

Calibration options, never used by the benchmark's own runs: `--rate`
offers another rate (the knee sweep), `--control <fmt>` also reads the
lower-precision control's gaps, `--keep-trace <file>` keeps a copy of
the traced window, and
`--sweep seed:rate,seed:rate,...` runs the cell once per pair in this one
process (one result line each: the knee sweep and the dozen-seed
readings, with the process set up once).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", default=None)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--sweep", default=None)
    return ap.parse_args(argv)


class CompileCounter:
    """Programs JAX compiles or loads from its cache, by name, from its
    monitoring events."""

    def __init__(self):
        import jax
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))


def warm_up(engine, page_size: int, vocab: int, skip=()):
    """Serve one short prompt per residue of its length modulo the page
    size (two tokens each) not in `skip`: the prefill chunk, the
    first-token sample, the decode step and every eager page-scatter
    shape compile here."""
    import numpy as np
    from repro.launch.engine import Request
    rng = np.random.default_rng(0)
    for r in sorted(set(range(page_size)) - set(skip)):
        engine.submit(Request(rid=-100 - r, max_new=2,
                              prompt=rng.integers(0, vocab, page_size + r,
                                                  dtype=np.int32)))
    while engine.waiting or any(engine.slots):
        engine.step(0.0)
    engine.reset_stats()


def load_server(engine, held_reqs) -> list:
    """Admit the requests a server under this load already holds and run
    their prompts through, so the window opens on a server part-way
    through their answers; -> (request, engine request) pairs."""
    from repro.launch.engine import Request
    held = []
    for r in held_reqs:
        e = Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                    arrival=r.due)
        engine.submit(e)
        held.append((r, e))
    while engine.waiting or any(s is not None and s.state == "prefill"
                                for s in engine.slots):
        engine.step(0.0)
    return held


def end_to_end(rec) -> tuple:
    """Values of the cell's end-to-end metrics other than setup_s, and the
    sample counts behind them."""
    from bench.stats import beyond, percentile
    T = rec["seconds"]
    win = [t for t in rec["tracks"] if t.in_window]
    ttft = [t.first - t.due if t.first is not None else math.inf
            for t in win]
    itl = [b - a for t in rec["tracks"]
           for a, b in zip(t.stamps, t.stamps[1:]) if b < T]
    emitted = sum(1 for t in rec["tracks"] for s in t.stamps if s < T)
    vals = {"tokens_per_s": emitted / T,
            "ttft_p50_s": percentile(ttft, 50) if ttft else math.inf,
            "itl_p99_ms": 1e3 * percentile(itl, 99) if itl else math.inf}
    counts = (f"samples: {len(win)} requests due in the window, "
              f"{sum(t.first is None for t in win)} without a first token; "
              f"ttft {len(ttft)} ({beyond(ttft, 50) if ttft else 0} beyond "
              f"p50); itl {len(itl)} ({beyond(itl, 99) if itl else 0} "
              f"beyond p99); {emitted} tokens emitted in the window")
    return vals, counts


def judge(res: dict, unserved: int, conf: dict) -> tuple:
    """The numbers compared, each with its limit, and whether all hold.
    The gap limits are the configuration's, set from sound runs and the
    control; a limit not yet set, or no token to compare, fails."""
    lim = conf["correct"]
    checks = {
        "max_logit_gap": {"value": res["max_gap"], "rule": "<=",
                          "limit": lim.get("max_logit_gap")},
        "mean_logit_gap": {"value": res["mean_gap"], "rule": "<=",
                           "limit": lim.get("mean_logit_gap")},
        "tokens_compared": {"value": res["tokens"], "rule": ">=",
                            "limit": lim.get("min_tokens", 512)},
        "unserved": {"value": unserved, "limit": 0, "rule": "<="}}
    correct = all(c["limit"] is not None and c["value"] is not None and (
        c["value"] <= c["limit"] if c["rule"] == "<=" else
        c["value"] >= c["limit"]) for c in checks.values())
    return checks, correct


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU; JAX found {devices[0].platform}")
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import spec
    cell = spec.load_cell(args.workload)
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips; JAX found "
            f"{len(devices)}")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if args.sweep:
        for pair in args.sweep.split(","):
            seed, rate = pair.split(":")
            one = argparse.Namespace(**dict(vars(args), seed=int(seed),
                                            rate=float(rate)))
            out = run_cell(cell, one, devices) or {}
            print(json.dumps(dict(out, seed=one.seed, rate=one.rate)),
                  flush=True)
            gc.collect()
        return 0
    out = run_cell(cell, args, devices)
    if out is None:
        return 2
    print(json.dumps(out), flush=True)
    return 0


def run_cell(cell, args, devices, root: str = ROOT, fault=None):
    """Everything after the look for the chip: -> the result object, or
    None when the device is not in the peak table.  `fault(engine)`, when
    given, breaks the served path after warm-up (the tests' planted
    faults, which `correct` must catch)."""
    import jax
    from bench import check, model, spec, trace_reduce, window
    from bench.traffic import gen
    counter = CompileCounter()
    conf = cell.config
    cfg = spec.load_family(conf, root).model_config(conf, cell.config_name)
    rate = args.rate or cell.rate
    geometry = model.engine_geometry(conf)
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if kind not in peaks:
        log(f"bench: no peaks for device kind {kind!r} in peaks.json")
        return None
    from repro.models import build_model
    t0 = time.monotonic()
    weights = model.make_weights(build_model(cfg), args.seed)
    jax.block_until_ready(weights)
    t1 = time.monotonic()
    engine = model.make_engine(conf, cfg, weights, cell.mix, args.seed)
    t2 = time.monotonic()
    ps = geometry["page_size"]
    held_reqs = gen.preload(cell.mix, cell.preload, args.seed,
                            conf["vocab_size"], ps)
    warm_up(engine, ps, conf["vocab_size"],
            skip={len(r.prompt) % ps for r in held_reqs})
    if fault is not None:
        fault(engine)
    held = load_server(engine, held_reqs)
    t3 = time.monotonic()
    reqs = gen.generate(cell.mix, rate, args.seed, args.seconds,
                        conf["vocab_size"], conf["max_position_embeddings"])
    log(f"setup: weights {t1 - t0!r} s, engine {t2 - t1!r} s, warm-up and "
        f"{len(held)} requests in flight {t3 - t2!r} s, "
        f"{len(counter.names)} programs compiled or loaded")
    trace_dir = os.path.join(root, ".bench_trace", cell.name)
    opened = {}

    def on_open():
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        opened["setup_s"] = time.monotonic() - T_START
        opened["compiled"] = len(counter.names)

    rec = window.drive(engine, reqs, args.seconds, held=held,
                       on_open=on_open)
    in_window = counter.names[opened["compiled"]:]
    red = None
    if args.trace:
        jax.profiler.stop_trace()
        t_r = time.monotonic()
        path = trace_reduce.find(trace_dir)
        size = os.path.getsize(path)
        red = trace_reduce.reduce(trace_reduce.load(path))
        if args.keep_trace:
            shutil.copy(path, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {size} bytes, reduced in {time.monotonic() - t_r!r} s")
    stats = devices[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    log(f"memory: peak_bytes_in_use {peak_bytes}, bytes_limit "
        f"{stats.get('bytes_limit')}, pool pages {geometry['n_pages']}")
    log(f"compiles in the window: {len(in_window)} {in_window}")
    late = sorted(t.submitted - t.due for t in rec["tracks"])
    log(f"generator lateness: median {late[len(late) // 2]!r} s, max "
        f"{late[-1]!r} s over {len(late)} submissions; {len(rec['steps'])} "
        f"steps; rate {rate!r} req/s; {rec['backlog']} waiting at the "
        f"close; tail {rec['end'] - rec['seconds']!r} s")
    del engine
    gc.collect()
    t_c = time.monotonic()
    picked = check.sample(rec["tracks"], args.seed,
                          conf["correct"].get("sample_tokens", 512))
    res = check.compare(weights, conf, picked, quant=args.control, root=root)
    log(f"reference: {res} in {time.monotonic() - t_c!r} s")
    unserved = sum(1 for t in rec["tracks"] if t.in_window and t.first is None)
    checks, correct = judge(res, unserved, conf)
    if args.control and res["tokens"]:
        ctl = {"max_gap": res["control_max_gap"],
               "mean_gap": res["control_mean_gap"], "tokens": res["tokens"]}
        log(f"control {args.control}: correct "
            f"{judge(ctl, unserved, conf)[1]}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct),
           "attempted": sum(1 for t in rec["tracks"] if t.in_window),
           "failed": unserved, "metrics": {}, "device": device}
    if args.trace:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        rd = {"seconds": rec["seconds"], "steps": rec["steps"],
              "tracks": rec["tracks"], "trace": red, "conf": conf,
              "geometry": geometry, "peak": peaks[kind], "root": root}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"], root)(rd)
            if v is not None and math.isfinite(v):
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                            "idle_gaps": red["gaps"]}
        log(f"trace: window {red['window_s']!r} s, busy {red['busy_s']!r} "
            f"s, in steps {red['busy_in_step_s']!r} of {red['step_s']!r} s")
        for n, v in sorted(red["modules"].items(),
                           key=lambda kv: -kv[1][1])[:15]:
            log(f"module: {n} x{v[0]} {v[1]!r} s")
        for n, s in trace_reduce.top_ops(red, 25):
            log(f"op: {n} x{red['ops'][n][0]} {s!r} s")
    else:
        vals, counts = end_to_end(rec)
        log(counts)
        vals["setup_s"] = opened["setup_s"]
        for m in cell.end_to_end:
            v = vals[m["name"]]
            out["metrics"][m["name"]] = {
                "value": v if math.isfinite(v) else None, "unit": m["unit"]}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} {c['rule']} {c['limit']!r}")
    return out


if __name__ == "__main__":
    sys.exit(main())
