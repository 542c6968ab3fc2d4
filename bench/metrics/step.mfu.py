"""Model operations of the tokens the window processed (prompt tokens
through prefill, output tokens through decode, one unembedding per
output; the census of the configuration's family) over host time inside
`bench.step` spans times the chip's bf16 peak, in %.  The denominator is
step time, not the window, so at a fixed offered rate it moves with
speed."""
from bench import programs, spec


def read(rec):
    steps = programs.window_steps(rec)
    busy = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or busy <= 0:
        return None
    family = spec.load_family(rec["conf"], rec["root"])
    ops = sum(family.step_flops(rec["conf"], s) for s in steps)
    return 100.0 * ops / (busy * rec["peak"]["bf16_flops"])
