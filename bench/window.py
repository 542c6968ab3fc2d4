"""The measured window: open-loop arrivals driven into `Engine.step`.

Requests are submitted when due and the engine is stepped in this loop;
every stamp is the host clock right after `Engine.step` returns, and a
step that emits a token has waited for it on the host (the engine reads
each sampled token back).  Host spans go into the profiler's trace when
one is running: `bench.window` over the measured window, `bench.step`
around each `Engine.step`, `bench.submit` around submissions, and
`bench.wait` while nothing is due.

After the window closes, arrivals go on as scheduled until every
request due inside the window has its first token (or `tail_limit`
seconds pass): a late first token is late, and its wait is counted.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Track:
    """What the harness saw of one request."""

    __slots__ = ("rid", "due", "in_window", "n_prompt", "max_new", "req",
                 "submitted", "admitted", "first", "stamps", "prefill_seen",
                 "gen_seen", "done")

    def __init__(self, r, req):
        self.rid, self.due, self.in_window = r.rid, r.due, r.in_window
        self.n_prompt, self.max_new, self.req = len(r.prompt), r.max_new, req
        self.submitted = self.admitted = self.first = None
        self.stamps = []          # host time of each output token
        self.prefill_seen = self.gen_seen = 0
        self.done = False


def drive(engine, reqs, seconds: float, *, held=(), tail_limit: float = 60.0,
          on_open=None) -> dict:
    """Run the window; -> the run record (times relative to its opening).

    `held` are (request, engine request) pairs the server already holds
    when the window opens; their tokens from then on are recorded too.
    `on_open` is called just before the window opens (the traced run
    starts the profiler there)."""
    from repro.launch.engine import Request
    tracks = []
    active = []
    backlog = None
    for r, e in held:
        t = Track(r, e)
        t.submitted = t.admitted = t.first = r.due
        t.prefill_seen, t.gen_seen = e.prefill_done, e.n_generated
        t.done = e.state == "done"
        tracks.append(t)
        if not t.done:
            active.append(t)
    if on_open is not None:
        on_open()
    clock = time.monotonic
    n_window = sum(r.in_window for r in reqs)
    steps = []
    i = 0
    closed = False
    window_span = TraceAnnotation("bench.window")
    window_span.__enter__()
    t_open = clock()
    while True:
        now = clock() - t_open
        if not closed and now >= seconds:
            closed = True
            window_span.__exit__(None, None, None)
            backlog = len(engine.waiting)
        if closed and (now >= seconds + tail_limit or all(
                t.first is not None for t in tracks if t.in_window)
                and i >= n_window):
            break
        if i < len(reqs) and reqs[i].due <= now:
            with TraceAnnotation("bench.submit"):
                while i < len(reqs) and reqs[i].due <= now:
                    r = reqs[i]
                    req = Request(rid=r.rid, prompt=r.prompt,
                                  max_new=r.max_new, arrival=r.due)
                    engine.submit(req)
                    t = Track(r, req)
                    t.submitted = now
                    tracks.append(t)
                    active.append(t)
                    i += 1
        if not engine.waiting and not any(engine.slots):
            if i >= len(reqs):
                break
            wait = reqs[i].due - now
            if not closed:
                wait = min(wait, seconds - now)
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, wait))
            continue
        with TraceAnnotation("bench.step"):
            s0 = clock()
            engine.step(now)
            s1 = clock()
        prefill, decode_ctx, firsts = [], [], 0
        still = []
        for t in active:
            e = t.req
            if t.admitted is None and e.state != "waiting":
                t.admitted = s1 - t_open
            dp = e.prefill_done - t.prefill_seen
            if dp > 0:
                prefill.append((t.prefill_seen, dp))
                t.prefill_seen = e.prefill_done
            ng = e.n_generated - t.gen_seen
            if ng > 0:
                if t.gen_seen == 0:
                    t.first = s1 - t_open
                    firsts += 1
                    ndec, g0 = ng - 1, 1
                else:
                    ndec, g0 = ng, t.gen_seen
                # a decode of token g attends over prompt + g tokens
                decode_ctx.extend(t.n_prompt + g for g in range(g0, g0 + ndec))
                t.stamps.extend([s1 - t_open] * ng)
                t.gen_seen = e.n_generated
            if e.state == "done":
                t.done = True
            else:
                still.append(t)
        active = still
        steps.append({"t0": s0 - t_open, "t1": s1 - t_open,
                      "prefill": prefill, "decode_ctx": decode_ctx,
                      "firsts": firsts})
    if not closed:
        window_span.__exit__(None, None, None)
        backlog = len(engine.waiting)
    return {"seconds": seconds, "tracks": tracks, "steps": steps,
            "end": clock() - t_open, "backlog": backlog}
