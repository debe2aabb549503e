"""Device time of one prefill: the `jit_tds_prefill` events of the `XLA Modules`
line over their number (one run per admission, whatever its bucket)."""

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
    return r.per_run_ms(r.programs_s, "jit_tds_prefill")
