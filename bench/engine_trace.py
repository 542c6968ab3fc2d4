"""What the engine's own profiler spans say about a traced window.

The serving engine marks its phases with `engine.*` host spans
(`jax.profiler.TraceAnnotation` in `launch/engine.py`; `engine.step`
carries the tick's counters as stats).  `trace_reduce` keeps only the
harness's `bench.*` spans; this module reads the engine's on the same
clock and adds to `trace_reduce.reduce`'s keys:

  engine_spans    per engine span name: spans that start in the window
                  and their host seconds;
  engine_steps    the stats of each `engine.step` that starts in the
                  window;
  requests        per request id, from the whole trace (the harness's
                  tail runs after the window closes), in seconds from the
                  window's opening: `admitted` (end of its
                  `engine.admit`), `prefill_start` (start of its first
                  `engine.prefill_chunk`), `first_token` (end of its
                  `engine.first_token`);
  idle_by_span    device-idle seconds inside `bench.step` spans, by the
                  innermost engine span around them (`engine.step` is
                  the step's own time outside its phases, `bench.step`
                  the harness's outside `engine.step`), averaged over the
                  chips;
  gaps            `trace_reduce`'s idle gaps, each inside an engine span
                  named after the innermost such span instead.

Engine spans time the host: the device work they dispatch runs
asynchronously and is counted in `modules` and `ops`.  A trace with no
engine spans reduces to `trace_reduce.reduce`'s keys and values.

`METRICS` reads five numbers of the scheduler and the model step from a
run record whose `trace` is this reduction.  The benchmark's traced runs
do not carry them yet; to read them from one cell on the chip:

    python3 -m bench.engine_trace --workload <cell> --seed <n> --seconds <s>

runs `bench/run.py` traced, with the trace kept, and adds an `engine`
key to its result line (the five numbers, idle by span, the relabelled
gaps, the spans) and `engine span:` lines to stderr.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import heapq
import math
import os

from bench import trace_reduce as tr
from bench.stats import percentile


def load(path: str) -> dict:
    """`trace_reduce.load`'s events, and under "engine" the engine's
    spans: [[name, start_ns, dur_ns, {stat: value}], ...]."""
    from jax.profiler import ProfileData
    ev = tr.load(path)
    ev["engine"] = [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                    for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith("engine.")]
    return ev


def reduce(events: dict, n_gaps: int = 10) -> dict:
    red = tr.reduce(events, n_gaps)
    host = events["host"]
    win = [h for h in host if h[0] == "bench.window"][0]
    lo, hi = win[1], win[1] + win[2]
    spans = sorted([h for h in host if h[0] in tr.SPAN_RANK],
                   key=lambda h: h[1])
    steps = tr.union(tr.clip([[s, s + d] for n, s, d in spans
                              if n == "bench.step"], lo, hi))
    engine = events.get("engine", [])
    pieces = nest(engine)
    planes = sorted({e[0] for e in events["device"]})
    gaps, idle = [], {}
    # the device's idle stretches as `trace_reduce.reduce` finds them
    for plane in planes:
        op_iv = tr.union(tr.clip(
            [[s, s + d] for p, line, name, s, d in events["device"]
             if p == plane and line == "XLA Ops"
             and name.split(".")[0] not in tr.CONTAINERS], lo, hi))
        for k, v in _idle_by_span(subtract(steps, op_iv), pieces).items():
            idle[k] = idle.get(k, 0.0) + v
        edges = [lo] + [x for iv in op_iv for x in iv] + [hi]
        gaps.extend(heapq.nlargest(n_gaps, ((b - a, (a + b) / 2) for a, b
                                            in zip(edges[::2], edges[1::2])
                                            if b > a)))
    n = max(1, len(planes))
    starts = [h[1] for h in spans]
    gaps = [[_engine_label(pieces, t) or tr._label(spans, starts, t),
             d * 1e-9] for d, t in heapq.nlargest(n_gaps, gaps)]
    return dict(red, gaps=gaps, **_engine(engine, lo, hi),
                idle_by_span={k: v * 1e-9 / n for k, v in idle.items()})


def subtract(xs, ys) -> list:
    """The parts of merged intervals xs that no merged interval of ys
    covers."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > a:
                out.append([a, ys[k][0]])
            a = max(a, ys[k][1])
            k += 1
        if a < b:
            out.append([a, b])
    return out


def nest(spans) -> list:
    """[start, end, name] pieces of the time line, each under the
    innermost of the given spans ([name, start, dur, ...], which nest, as
    the spans of one thread do); time under no span is left out."""
    out, stack, t = [], [], None
    for name, s, d, *_ in sorted(spans, key=lambda h: (h[1], -h[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                out.append([t, end, top])
                t = end
        if stack and s > t:
            out.append([t, s, stack[-1][1]])
        t = s
        stack.append((s + d, name))
    while stack:
        end, top = stack.pop()
        if end > t:
            out.append([t, end, top])
            t = end
    return out


def _engine(engine, lo, hi) -> dict:
    """engine_spans, engine_steps and requests (see the module's doc)."""
    spans, steps, reqs = {}, [], {}
    # (key, at the span's end); spans in order of start, so a request's
    # first chunk comes first
    points = {"engine.admit": ("admitted", True),
              "engine.prefill_chunk": ("prefill_start", False),
              "engine.first_token": ("first_token", True)}
    for name, s, d, stats in sorted(engine, key=lambda h: h[1]):
        if lo <= s < hi:
            c = spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
            if name == "engine.step":
                steps.append(stats)
        if name in points and "rid" in stats:
            key, at_end = points[name]
            reqs.setdefault(stats["rid"], {}).setdefault(
                key, ((s + d if at_end else s) - lo) * 1e-9)
    return {"engine_spans": spans, "engine_steps": steps, "requests": reqs}


def _idle_by_span(idle, pieces) -> dict:
    """Nanoseconds of the merged idle intervals under each engine piece;
    the rest under `bench.step`."""
    out, j = {}, 0
    for a, b in idle:
        rest = b - a
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            x = min(b, pieces[k][1]) - max(a, pieces[k][0])
            out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + x
            rest -= x
            k += 1
        if rest > 0:
            out["bench.step"] = out.get("bench.step", 0.0) + rest
    return out


def _engine_label(pieces, t):
    """The innermost engine span around time t, or None."""
    i = bisect.bisect_right(pieces, [t, math.inf]) - 1
    return pieces[i][2] if i >= 0 and pieces[i][0] <= t < pieces[i][1] \
        else None


def request_wait_p50(rec, since: str, until: str):
    """Median, over the requests due in the window, of the seconds from
    one point of a request to a later one: `due`, or a point of
    `requests`.  A request without both points counts as a miss (+inf);
    None when the trace holds no engine spans."""
    reqs = rec["trace"]["requests"]
    waits = []
    for t in rec["tracks"]:
        if t.in_window:
            r = dict(reqs.get(t.rid, {}), due=t.due)
            waits.append(r[until] - r[since] if since in r and until in r
                         else math.inf)
    return percentile(waits, 50) if reqs and waits else None


def decode_occupancy(rec):
    """Mean share of the decode slots that held a request, over the
    window's steps that ran a decode, in %."""
    live = [s["decode_live"] for s in rec["trace"]["engine_steps"]
            if s.get("decode_live")]
    return 100.0 * sum(live) / (len(live) * rec["geometry"]["max_batch"]) \
        if live else None


def host_reads_per_step(rec):
    """Mean device-to-host reads per step in the window (one per decode
    step and one per finished prompt on the plain path)."""
    reads = [s["host_reads"] for s in rec["trace"]["engine_steps"]
             if "host_reads" in s]
    return sum(reads) / len(reads) if reads else None


METRICS = {
    # due to the end of `engine.admit`: a slot and pages given
    "scheduler.admit_wait_p50_s":
        lambda rec: request_wait_p50(rec, "due", "admitted"),
    # admitted to the first chunk: the wait for the staging cache
    "scheduler.prefill_wait_p50_s":
        lambda rec: request_wait_p50(rec, "admitted", "prefill_start"),
    # first chunk to the first token: chunks, shared steps, scatter, read
    "model.prompt_prefill_p50_s":
        lambda rec: request_wait_p50(rec, "prefill_start", "first_token"),
    "scheduler.decode_occupancy": decode_occupancy,
    "scheduler.host_reads_per_step": host_reads_per_step,
}


def traced(run_cell, cell, args, devices, root=None, fault=None):
    """`run_cell` of `bench/run.py`, traced with the trace kept; the
    result gains "engine": what the engine's spans say."""
    from bench import model, window
    from bench.run import ROOT, log
    root = root or ROOT
    kept = os.path.join(root, ".bench_trace", cell.name + ".engine.xplane.pb")
    os.makedirs(os.path.dirname(kept), exist_ok=True)
    recs, drive = [], window.drive

    def keep(*a, **kw):
        recs.append(drive(*a, **kw))
        return recs[-1]

    window.drive = keep
    try:
        out = run_cell(cell, argparse.Namespace(**dict(
            vars(args), trace=1, keep_trace=kept)), devices, root=root,
            fault=fault)
    finally:
        window.drive = drive
    if out is None:
        return None
    red = reduce(load(kept))
    os.remove(kept)
    rec = {"seconds": recs[-1]["seconds"], "tracks": recs[-1]["tracks"],
           "trace": red, "geometry": model.engine_geometry(cell.config)}
    for n, (c, sec) in sorted(red["engine_spans"].items(),
                              key=lambda kv: -kv[1][1]):
        log(f"engine span: {n} x{c} {sec!r} s idle "
            f"{red['idle_by_span'].get(n, 0.0)!r} s")
    vals = {k: f(rec) for k, f in METRICS.items()}
    out["engine"] = {
        "metrics": {k: v for k, v in vals.items()
                    if v is not None and math.isfinite(v)},
        "idle_by_span": red["idle_by_span"], "idle_gaps": red["gaps"],
        "spans": red["engine_spans"]}
    return out


def main(argv=None) -> int:
    """`bench/run.py`'s command line, each run made by `traced`."""
    from bench import run
    run.run_cell = functools.partial(traced, run.run_cell)
    return run.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
