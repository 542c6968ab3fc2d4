"""Share of its roofline the fused fp8 linear kernel reached, in %: the
least time of the work its calls had to do (the larger of operations at
the bf16 peak and bytes at HBM bandwidth, per call, from the call shapes
and the policy) over the kernel's device time in the trace.  Calls per
program: each of the family's fused linears once per layer that calls
it, at the decode batch in each decode step and at the prefill chunk in
each chunk."""
from bench import programs, spec, workcount


def read(rec):
    t = programs.kernel_s(rec, "dpa_matmul_fused")
    if not t:
        return None
    conf, g = rec["conf"], rec["geometry"]
    linears = spec.load_family(conf, rec["root"]).fused_linears(conf)
    n_dec, n_chunks = programs.calls(rec)
    least = 0.0
    for m, calls in ((g["max_batch"], n_dec), (g["prefill_chunk"], n_chunks)):
        for k, n, layers in linears:
            ops, nbytes = workcount.fp8_linear_call(m, k, n)
            least += calls * layers * \
                workcount.roofline_seconds(ops, nbytes, rec["peak"])[0]
    return 100.0 * least / t
