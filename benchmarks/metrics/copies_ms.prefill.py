"""Self time of `copy*` operations inside one prefill program: the pool copied
once per admission."""

UNIT = "ms/admission"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    if r is None:
        return None
    return r.per_run_ms(r.copies_s, "jit_tds_prefill")
