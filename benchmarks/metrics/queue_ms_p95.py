"""95th percentile over measured requests of admission (`Request.t_admitted`)
minus the DUE instant: the wait in the generator and in the queue."""

UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmarks.serve_arith import percentile, queue_ms
    measured = ctx.host.get("measured")
    return percentile([queue_ms(r) for r in measured], 95) if measured else None
