"""Share of its roofline the paged fp8 decode kernel reached, in %: the
least time of each call's work -- the live tokens' fp8 K/V with their
scales, q in and out, 4 H hd operations per live token -- over the
kernel's device time in the trace.  One call per paged-attention layer
of the family per decode step."""
from bench import programs, spec, workcount


def read(rec):
    t = programs.kernel_s(rec, "paged_decode_attention")
    if not t:
        return None
    conf = rec["conf"]
    layers = spec.load_family(conf, rec["root"]).paged_layers(conf)
    least = 0.0
    for s in programs.window_steps(rec):
        if s["decode_ctx"]:
            ops, nbytes = workcount.paged_decode_layer(conf, s["decode_ctx"])
            least += layers * workcount.roofline_seconds(
                ops, nbytes, rec["peak"])[0]
    return 100.0 * least / t
