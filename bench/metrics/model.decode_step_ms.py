"""Device time per call of the batched decode program, the engine's
jitted decode step with sampling (XLA module `jit_step`), from the
trace."""
from bench.programs import module_ms


def read(rec):
    return module_ms(rec, "jit_step")
