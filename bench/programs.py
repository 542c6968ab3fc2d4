"""What the per-layer readers share: the steps of the measured window,
how many calls each program made in it, and device time by name."""
from __future__ import annotations

import math


def window_steps(rec) -> list:
    return [s for s in rec["steps"] if s["t0"] < rec["seconds"]]


def calls(rec) -> tuple:
    """(decode program calls, prefill chunk calls) in the window."""
    chunk = rec["geometry"]["prefill_chunk"]
    steps = window_steps(rec)
    n_dec = sum(1 for s in steps if s["decode_ctx"])
    n_chunks = sum(math.ceil(n / chunk) for s in steps
                   for _, n in s["prefill"])
    return n_dec, n_chunks


def _base(name: str) -> str:
    return name.split("(")[0].strip()


def module_ms(rec, module: str):
    """Mean device milliseconds per execution of an XLA module."""
    tr = rec["trace"]
    if not tr:
        return None
    hits = [v for n, v in tr["modules"].items() if _base(n) == module]
    count = sum(v[0] for v in hits)
    return 1e3 * sum(v[1] for v in hits) / count if count else None


def kernel_s(rec, kernel: str) -> float:
    """Device seconds of the operations named after a kernel."""
    tr = rec["trace"]
    if not tr:
        return 0.0
    return sum(v[1] for n, v in tr["ops"].items()
               if n.split("/")[-1].startswith(kernel))
