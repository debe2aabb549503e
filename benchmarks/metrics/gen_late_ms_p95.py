"""95th percentile over measured requests of `submit()` minus the DUE instant:
how late the generator ran (it submits between ticks, so about a tick)."""

UNIT = "ms"
BETTER = "lower"
LAYER = "load generator"
MOVES = "tpot_p95_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.serve_arith import gen_late_ms, percentile
    measured = ctx.host.get("measured")
    return (percentile([gen_late_ms(r) for r in measured], 95)
            if measured else None)
