"""Share of its roofline the fused fp8 linear kernel reached, in %: the
least time of the work its calls had to do (the larger of operations at
the bf16 peak and bytes at HBM bandwidth, per call, from the call shapes
and the policy) over the kernel's device time in the trace.  Calls per
program: seven per layer, at the decode batch in each decode step and at
the prefill chunk in each chunk."""
from bench import programs, workcount


def read(rec):
    t = programs.kernel_s(rec, "dpa_matmul_fused")
    if not t:
        return None
    conf, g = rec["conf"], rec["geometry"]
    n_dec, n_chunks = programs.calls(rec)
    least = 0.0
    for m, calls in ((g["max_batch"], n_dec), (g["prefill_chunk"], n_chunks)):
        for k, n in workcount.linear_shapes(conf):
            ops, nbytes = workcount.fp8_linear_call(m, k, n)
            least += calls * conf["num_hidden_layers"] * \
                workcount.roofline_seconds(ops, nbytes, rec["peak"])[0]
    return 100.0 * least / t
