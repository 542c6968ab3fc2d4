"""From a profiler trace to the numbers the per-layer metrics read.

`load` pulls two things out of the `.xplane.pb` the JAX profiler writes:
the device's operations (the "XLA Ops" and "XLA Modules" lines of each
`/device:TPU:<n>` plane) and the harness's own host spans (`bench.*`,
from `jax.profiler.TraceAnnotation`).  `reduce` works on that plain
event list, so a small recorded one can be checked by hand:

  window_s        length of the `bench.window` span;
  busy_s          union of the device's operation intervals inside the
                  window, averaged over the chips (loops and conditionals,
                  which hold other operations, do not count themselves);
  step_s          time inside `bench.step` spans in the window;
  busy_in_step_s  the part of busy_s that falls inside `bench.step` spans;
  modules         per XLA module: executions and device seconds;
  ops             per operation, named `<module>/<op>` after the module
                  whose execution holds it: calls and device seconds
                  (loops and conditionals, which hold other operations,
                  are left out);
  gaps            idle stretches of the device inside the window, each
                  labelled by the innermost host span around its middle
                  (`bench.step`, `bench.wait`, `bench.submit`, or `host`).
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os

DEVICE_LINES = ("XLA Ops", "XLA Modules")


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    return paths[0]


def load(path: str) -> dict:
    """{"device": [[plane, line, name, start_ns, dur_ns], ...],
        "host": [[name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    dev.extend([plane.name, line.name, op_name(e.name),
                                e.start_ns, e.duration_ns]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"device": dev, "host": host}


def op_name(text: str) -> str:
    """An operation's name from its trace label, which on a TPU is the
    whole HLO instruction (`%fusion.3 = f32[...] fusion(...)`)."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def intersect(xs, ys) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


SPAN_RANK = {"bench.submit": 0, "bench.wait": 1, "bench.step": 2}
# control-flow ops whose span holds other ops: busy time, not an op's time
CONTAINERS = ("while", "conditional", "call")


def reduce(events: dict, n_gaps: int = 10) -> dict:
    host = events["host"]
    win = [h for h in host if h[0] == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    spans = sorted([h for h in host if h[0] in SPAN_RANK],
                   key=lambda h: h[1])
    steps = union(clip([[s, s + d] for n, s, d in spans
                        if n == "bench.step"], lo, hi))
    planes = sorted({e[0] for e in events["device"]})
    busy, busy_in_steps, gaps = 0.0, 0.0, []
    modules, ops = {}, {}
    for plane in planes:
        evs = [e for e in events["device"] if e[0] == plane]
        op_iv = union(clip([[s, s + d] for _, line, name, s, d in evs
                            if line == "XLA Ops"
                            and name.split(".")[0] not in CONTAINERS],
                           lo, hi))
        busy += length(op_iv)
        busy_in_steps += length(intersect(op_iv, steps))
        edges = [lo] + [x for iv in op_iv for x in iv] + [hi]
        gaps.extend(heapq.nlargest(n_gaps, ((b - a, (a + b) / 2) for a, b
                                            in zip(edges[::2], edges[1::2])
                                            if b > a)))
        mods = sorted((s, s + d, name.split("(")[0]) for _, line, name, s, d
                      in evs if line == "XLA Modules")
        starts = [m[0] for m in mods]
        for _, line, name, s, d in evs:
            if not lo <= s < hi:
                continue
            if line == "XLA Modules":
                table, key = modules, name
            elif name.split(".")[0] in CONTAINERS:
                continue
            else:
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and s < mods[i][1]
                table, key = ops, (mods[i][2] + "/" if inside else "") + name
            c = table.setdefault(key, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
    n = max(1, len(planes))
    starts = [h[1] for h in spans]
    gaps = [[_label(spans, starts, t), d * 1e-9]
            for d, t in heapq.nlargest(n_gaps, gaps)]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9 / n,
            "step_s": length(steps) * 1e-9,
            "busy_in_step_s": busy_in_steps * 1e-9 / n,
            "chips": len(planes),
            "modules": modules, "ops": ops, "gaps": gaps}


def _label(spans, starts, t) -> str:
    """The innermost harness span around time t (spans sorted by start;
    they follow one another, so the few that start last before t hold
    it if any does)."""
    best = None
    for name, s, d in spans[max(0, bisect.bisect_right(starts, t) - 3):
                            bisect.bisect_right(starts, t)]:
        if s <= t < s + d and (best is None
                               or SPAN_RANK[name] < SPAN_RANK[best]):
            best = name
    return best or "host"


def top_ops(red: dict, k: int = 10) -> list:
    """The k operations that took the most device time: [[name, s]]."""
    return [[n, v[1]] for n, v in sorted(red["ops"].items(),
                                        key=lambda kv: -kv[1][1])[:k]]
