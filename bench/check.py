"""Whether the timed path served the right tokens.

After the window closes, a sample of the requests the server finished
-- the longest of them and others drawn from the seed, until at least
`sample_tokens` served tokens are in it, or all of them -- runs through
the float32 reference of the configuration's family
(`bench/reference/<family>.py`) over its prompt and served tokens.  Each
served token is read for its gap: how far its reference logit lies
below the reference's best at that position.  Greedy decoding in a
sound server puts that gap near zero; the configuration file holds the
limits on the widest and on the mean gap, each set from sound runs and
from the control (the reference computed on the next narrower grid, fp4
for an fp8 policy).
"""
from __future__ import annotations

import numpy as np

from bench import spec


def sample(tracks, seed: int, min_tokens: int) -> list:
    """The finished requests to compare: the longest, then others in an
    order drawn from the seed, until `min_tokens` served tokens (or all
    of them)."""
    done = [t for t in tracks if t.done]
    if not done:
        return []
    done.sort(key=lambda t: (-(t.n_prompt + len(t.req.out_tokens)), t.rid))
    rest = done[1:]
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11]
                                  ).permutation(len(rest))
    picked, n = [done[0]], len(done[0].req.out_tokens)
    for j in order:
        if n >= min_tokens:
            break
        picked.append(rest[j])
        n += len(rest[j].req.out_tokens)
    return picked


def bucket(n: int, conf: dict) -> int:
    """The padded length a sequence of n tokens runs at: the next multiple
    of a quarter of the context, so that four compiled programs serve
    every request."""
    step = conf["max_position_embeddings"] // 4
    return -(-n // step) * step


def compare(weights, conf: dict, picked, *, quant=None,
            root: str = spec.ROOT) -> dict:
    """Gaps of every served token of the picked requests; with `quant`,
    also the control's gaps on the same positions."""
    ref = spec.load_reference(conf, root)
    gaps, cgaps = [], []
    for t in picked:
        out = np.asarray(t.req.out_tokens, np.int32)
        toks = np.concatenate([t.req.prompt, out[:-1]])
        tgt = np.full(len(toks), -1, np.int32)
        tgt[t.n_prompt - 1:] = out
        g, c = ref.served_gaps(weights, conf, toks, tgt,
                               length=bucket(len(toks), conf),
                               quant=quant)
        gaps.append(g)
        if c is not None:
            cgaps.append(c)
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    res = {"requests": len(picked), "tokens": int(g.size),
           "max_gap": float(g.max()) if g.size else None,
           "mean_gap": float(g.mean()) if g.size else None,
           "off_argmax": float((g > 0).mean()) if g.size else None}
    if cgaps:
        c = np.concatenate(cgaps)
        res.update(control_max_gap=float(c.max()),
                   control_mean_gap=float(c.mean()),
                   control_off_argmax=float((c > 0).mean()))
    return res
