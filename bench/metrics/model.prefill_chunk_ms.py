"""Device time per call of the prefill-chunk program, the engine's
jitted `model.decode_step` over the (1, S_max) staging cache (XLA module
`jit_decode_step`), from the trace."""
from bench.programs import module_ms


def read(rec):
    return module_ms(rec, "jit_decode_step")
