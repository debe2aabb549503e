"""Self time of `copy*` operations inside the decode program, per tick: where the
pool is copied although it is donated, on the decode side."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    return None if r is None else r.per_unit_ms(
        r.copies_s.get("jit_tds_decode"))
